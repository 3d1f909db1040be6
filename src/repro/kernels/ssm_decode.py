"""Pallas TPU kernel: one decode token's Mamba-2 state update, in place.

For every row of one layer, in one pass over the state::

    h <- h * exp(dt * A) + (dt * x) (x) B        y = h . C + D * x

The state of a whole stack of layers, ``h [L, R, H, P, N]`` f32, comes in
with the layer to update as a scalar-prefetch operand; the index maps read
and write only that layer's blocks, and ``input_output_aliases`` makes the
output the input buffer, so the other layers are never copied and the
layer's state crosses HBM once each way. Donated by the caller (the decode
segment threads the state through its scan carry), the update is in place.

Layout (one grid step = one row and ``hb`` heads of one group):
  h        [L, R, H, P, N]       f32, aliased to the second output
  x        [R, H/hb, P, hb]      f32 — the heads' inputs, P on sublanes, so
                                 each head's column broadcasts along N
  dt       [R, H/hb, 1, hb]      f32, after softplus
  a, d     [H/hb, 1, hb]         f32 (A = -exp(A_log), the D skip)
  b, c     [R, G, 1, N]          f32, one row per group
  y        [R, H/hb, P, hb]      f32

The body loops over the block's heads (static): each head's ``[P, N]``
tile is decayed, takes its input column times the group's ``B`` row, is
stored, and is reduced against ``C`` along the lanes into the head's ``y``
column. ``ssm_recur`` (:mod:`repro.models.ssm`) is the XLA form and the
oracle (``tests/test_ssm_decode_kernel.py``, interpret mode); the kernel is
compiled for a v5e in ``tests/test_tpu_compile.py``, which pins its op name
``ssm_decode_pallas`` (the benchmark's trace reduction matches it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_decode_pallas", "heads_per_block"]

# largest state block one grid step holds (double-buffered in and out: 4x)
BLOCK_BYTES = 2 << 20


def heads_per_block(n_heads: int, n_groups: int, p: int, n: int) -> int:
    """Heads one grid step updates: the most that divide a group's heads
    and keep the state block within ``BLOCK_BYTES``."""
    rep = n_heads // n_groups
    return max(hb for hb in range(1, rep + 1)
               if rep % hb == 0 and (hb == 1 or hb * p * n * 4 <= BLOCK_BYTES))


def _kernel(layer_ref, h_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
            y_ref, h_out):
    del layer_ref                           # read by the index maps only
    dt = dt_ref[...]                        # [1, hb]
    dec = jnp.exp(dt * a_ref[...])          # [1, hb]
    x = x_ref[...]                          # [P, hb]
    xdt, dx = x * dt, x * d_ref[...]
    brow, crow = b_ref[...], c_ref[...]     # [1, N]
    for j in range(h_ref.shape[0]):
        hn = h_ref[j] * dec[:, j:j + 1] + xdt[:, j:j + 1] * brow   # [P, N]
        h_out[j] = hn
        y_ref[:, j:j + 1] = (jnp.sum(hn * crow, axis=1, keepdims=True)
                             + dx[:, j:j + 1])


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def ssm_decode_pallas(h: jax.Array, layer: jax.Array, x: jax.Array,
                      dt: jax.Array, a: jax.Array, b: jax.Array,
                      c: jax.Array, d: jax.Array, *,
                      interpret: bool = False):
    """Update layer ``layer`` of the stacked state ``h [L, R, H, P, N]``.

    x ``[R, H, P]``, dt ``[R, H]``, a/d ``[H]``, b/c ``[R, G, N]`` (as
    :func:`repro.models.ssm.ssm_recur` takes them) → (y ``[R, H, P]`` f32,
    ``h`` with the layer updated). ``h`` is donated.
    """
    n_l, r, nh, p, n = h.shape
    g = b.shape[1]
    rep = nh // g
    hb = heads_per_block(nh, g, p, n)
    nb = nh // hb
    f32 = jnp.float32

    def blocks(t, *lead):                   # [..., H] -> [..., H/hb, 1, hb]
        return t.astype(f32).reshape(*lead, nb, 1, hb)

    xt = x.astype(f32).reshape(r, nb, hb, p).swapaxes(-1, -2)
    row = lambda i, k, lay: (i, k, 0, 0)                     # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, nb),
        in_specs=[
            pl.BlockSpec((None, None, hb, p, n),
                         lambda i, k, lay: (lay[0], i, k, 0, 0)),
            pl.BlockSpec((None, None, p, hb), row),
            pl.BlockSpec((None, None, 1, hb), row),
            pl.BlockSpec((None, 1, hb), lambda i, k, lay: (k, 0, 0)),
            pl.BlockSpec((None, 1, hb), lambda i, k, lay: (k, 0, 0)),
            pl.BlockSpec((None, None, 1, n),
                         lambda i, k, lay: (i, k * hb // rep, 0, 0)),
            pl.BlockSpec((None, None, 1, n),
                         lambda i, k, lay: (i, k * hb // rep, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, p, hb), row),
            pl.BlockSpec((None, None, hb, p, n),
                         lambda i, k, lay: (lay[0], i, k, 0, 0)),
        ],
    )
    y, h = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((r, nb, p, hb), f32),
                   jax.ShapeDtypeStruct(h.shape, f32)),
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_decode_pallas",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), h, xt,
      blocks(dt, r), blocks(a), blocks(d),
      b.astype(f32).reshape(r, g, 1, n), c.astype(f32).reshape(r, g, 1, n))
    return y.swapaxes(-1, -2).reshape(r, nh, p), h
