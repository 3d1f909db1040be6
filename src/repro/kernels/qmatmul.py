"""Pallas TPU kernel: fused dequant(int8/int4) × bf16 matmul (+ fused requant).

The paper's hot spot is the quantized conv MAC bound to cheap fixed-point
hardware; the LM-family analogue is the projection matmul with weight-only
integer storage. The kernel keeps the paper's two wins:

* **data approximation** — weights travel HBM→VMEM as int8 (or int4 packed
  two-per-byte) and are dequantized *in VMEM*, so HBM traffic shrinks 2–4×
  versus bf16 (the memory-roofline win reported in EXPERIMENTS §Perf);
* **inter-layer precision boundary** — the optional fused requant clamps the
  f32 accumulator onto the next layer's ``Ax`` fixed-point grid before it ever
  leaves VMEM (the streaming-architecture FIFO-width analogue).

Grid: ``(M/bm, N/bn, K/bk)`` with K innermost; an f32 VMEM scratch accumulates
across the K loop and is flushed (optionally requantized) on the last K step.
Tile sides are multiples of 128 to align with the MXU systolic array; defaults
keep the working set (x-tile + w-tile + acc) well under VMEM:

  bm=256, bk=512, bn=256 → 256·512·2B + 512·256·1B + 256·256·4B ≈ 0.6 MiB.

Validated in ``interpret=True`` mode against ``ref.qmatmul_ref`` (CPU has no
MXU; the TPU path is the deployment target).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["qmatmul_pallas", "DEFAULT_BLOCKS"]

DEFAULT_BLOCKS = (256, 512, 256)  # (bm, bk, bn)


def _unpack_int4_tile(p: jax.Array) -> jax.Array:
    """Unpack a ``[bk, bn//2]`` int8 tile of packed int4 → ``[bk, bn]`` int8.

    Layout matches :func:`repro.core.qtypes.pack_int4`: low nibble = even
    column. Arithmetic shifts sign-extend the nibbles.
    """
    lo = (p << 4) >> 4
    hi = p >> 4
    bk, half = p.shape
    out = jnp.stack([lo, hi], axis=-1)          # [bk, half, 2]
    return out.reshape(bk, half * 2)


def _qmatmul_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *,
                    bits: int, n_k: int,
                    out_bits: int | None, out_scale: float | None):
    """One (m, n, k) grid step: acc += x_tile @ dequant(w_tile)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_q = w_ref[...]
    if bits <= 4:
        w_q = _unpack_int4_tile(w_q)
    # Dequant in VMEM: int carrier → f32 → per-channel scale → bf16 MXU input.
    w = (w_q.astype(jnp.float32) * scale_ref[...][None, :]).astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.bfloat16), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        acc = acc_ref[...]
        if out_bits is not None:
            # Fused static fixed-point requant onto the consumer's Ax grid.
            qmax = 2.0 ** (out_bits - 1) - 1.0
            qmin = -(2.0 ** (out_bits - 1))
            r = acc / out_scale
            q = jnp.clip(jnp.sign(r) * jnp.floor(jnp.abs(r) + 0.5), qmin, qmax)
            acc = q * out_scale
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "blocks", "out_bits", "out_scale", "interpret", "out_dtype"),
)
def qmatmul_pallas(x: jax.Array, w_q: jax.Array, scale: jax.Array, *,
                   bits: int = 8,
                   blocks: tuple[int, int, int] = DEFAULT_BLOCKS,
                   out_bits: int | None = None,
                   out_scale: float | None = None,
                   out_dtype=jnp.float32,
                   interpret: bool = False) -> jax.Array:
    """``x[M,K] @ dequant(w_q, scale)[K,N] -> [M,N]``.

    ``w_q``: int8 ``[K, N]`` for 5..8-bit weights, or packed int4 ``[K, N//2]``
    for ≤4-bit. ``scale``: per-output-channel ``[N]`` f32 (wrappers broadcast
    scalars). Shapes must divide the block sizes — ``ops.qmatmul`` pads.
    """
    m, k = x.shape
    bm, bk, bn = blocks
    if bits <= 4:
        kw, n_half = w_q.shape
        n = n_half * 2
        w_block = (bk, bn // 2)
        w_index = lambda i, j, kk: (kk, j)
    else:
        kw, n = w_q.shape
        w_block = (bk, bn)
        w_index = lambda i, j, kk: (kk, j)
    assert kw == k, f"contraction mismatch {kw} vs {k}"
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, \
        f"shapes ({m},{k},{n}) must divide blocks {blocks}"
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)

    kernel = functools.partial(_qmatmul_kernel, bits=bits, n_k=n_k,
                               out_bits=out_bits, out_scale=out_scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec(w_block, w_index),
            pl.BlockSpec((bn,), lambda i, j, kk: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w_q, scale)
