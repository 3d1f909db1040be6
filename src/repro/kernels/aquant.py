"""Pallas TPU kernel: fused dynamic activation quantization (the ``Ax`` side).

At every quantized layer boundary the QAT/serving path computes
``amax → scale → clip(round(x/s))·s`` over the activation tensor. Unfused,
that is three full HBM round-trips of ``x``; this kernel does the row-tiled
two-phase version in VMEM:

  phase 1 (grid pass 1): per-row-block max|x| → partial amax accumulator
  phase 2 (grid pass 2): quantize the same blocks against the final scale

A single ``pl.pallas_call`` with a 2×-length grid walks the row blocks twice
(sequential grid on TPU); the scalar amax lives in SMEM scratch between the
passes, so ``x`` streams HBM→VMEM exactly twice (once per phase) instead of
three+ times, and the rounding grid matches ``fake_quant`` bit-exactly
(po2 scale, round-half-away-from-zero, signed non-symmetric range).

Oracle: ``ref.aquant_ref`` (== core.quantizers.fake_quant numerics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["aquant_pallas"]


def _kernel(x_ref, o_ref, amax_ref, *, n_blocks: int, bits: int, po2: bool):
    i = pl.program_id(0)
    phase1 = i < n_blocks

    @pl.when(i == 0)
    def _init():
        amax_ref[0] = 1e-9

    @pl.when(phase1)
    def _reduce():
        amax_ref[0] = jnp.maximum(amax_ref[0], jnp.max(jnp.abs(x_ref[...])))

    @pl.when(jnp.logical_not(phase1))
    def _quantize():
        qmax = 2.0 ** (bits - 1) - 1.0
        qmin = -(2.0 ** (bits - 1))
        scale = amax_ref[0] / (-qmin)
        if po2:
            scale = jnp.exp2(jnp.ceil(jnp.log2(scale)))
        r = x_ref[...].astype(jnp.float32) / scale
        q = jnp.clip(jnp.sign(r) * jnp.floor(jnp.abs(r) + 0.5), qmin, qmax)
        o_ref[...] = (q * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "po2", "block_rows",
                                             "interpret"))
def aquant_pallas(x: jax.Array, *, bits: int = 8, po2: bool = True,
                  block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """Fake-quantize ``x [M, N]`` onto the dynamic ``bits`` grid (float out)."""
    m, n = x.shape
    br = min(block_rows, m)
    pad = (-m) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n_blocks = (m + pad) // br

    kernel = functools.partial(_kernel, n_blocks=n_blocks, bits=bits, po2=po2)
    out = pl.pallas_call(
        kernel,
        grid=(2 * n_blocks,),
        in_specs=[pl.BlockSpec((br, n), lambda i: (i % n_blocks, 0))],
        out_specs=pl.BlockSpec((br, n), lambda i: (i % n_blocks, 0)),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), x.dtype),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x)
    return out[:m]
