"""Pallas TPU kernel: decode attention over an int8-quantized KV cache.

Decode (one new token against an S-long cache) is memory-roofline-bound: the
whole KV cache streams HBM→VMEM per step. Storing K/V on the paper's 8-bit
grid halves that traffic vs bf16 — the serving-side twin of the weight-only
``qmatmul`` kernel — and the dequant happens in VMEM right before the MXU.

Layout (GQA-native): queries grouped by KV head.
  q   [G, Hg, D]   bf16/f32 — G = batch×kv_heads groups, Hg = q-heads/kv-head
  k_q [G, S, D]    int8, per-group scale [G]
  v_q [G, S, D]    int8, per-group scale [G]
  len [G]          valid cache length per group (int32, SMEM)

Grid ``(G, S/bs)`` with the S axis sequential; online-softmax scratch
(running max ``m``, denominator ``l``, accumulator) lives in VMEM across the
S loop and is flushed on the last block. Validated in interpret mode against
``ref.qkv_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["qkv_attention_pallas"]

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, n_s: int, sm_scale: float):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # [Hg, D]
    k = k_ref[0].astype(jnp.float32) * ks_ref[0]           # [bs, D] dequant in VMEM
    v = v_ref[0].astype(jnp.float32) * vs_ref[0]

    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale  # [Hg, bs]
    col = s * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(col < len_ref[pl.program_id(0)], scores, NEG_INF)

    m_prev = m_ref[...]                                    # [Hg, 1]
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                            # [Hg, bs]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def qkv_attention_pallas(q: jax.Array, k_q: jax.Array, v_q: jax.Array,
                         k_scale: jax.Array, v_scale: jax.Array,
                         lengths: jax.Array, *,
                         block_s: int = 512,
                         interpret: bool = False) -> jax.Array:
    """Softmax(q·dequant(K)ᵀ)·dequant(V) per GQA group; see module docstring."""
    g, hg, d = q.shape
    _, s, _ = k_q.shape
    bs = min(block_s, s)
    assert s % bs == 0, f"S={s} must divide block_s={bs} (wrapper pads)"
    n_s = s // bs

    kernel = functools.partial(_kernel, bs=bs, n_s=n_s, sm_scale=1.0 / d**0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, n_s),
        in_specs=[
            # index maps get the prefetched scalar ref as a trailing arg
            pl.BlockSpec((1, hg, d), lambda b, s_, L: (b, 0, 0)),
            pl.BlockSpec((1, bs, d), lambda b, s_, L: (b, s_, 0)),
            pl.BlockSpec((1, bs, d), lambda b, s_, L: (b, s_, 0)),
            pl.BlockSpec((1,), lambda b, s_, L: (b,)),
            pl.BlockSpec((1,), lambda b, s_, L: (b,)),
        ],
        out_specs=pl.BlockSpec((1, hg, d), lambda b, s_, L: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hg, 1), jnp.float32),
            pltpu.VMEM((hg, 1), jnp.float32),
            pltpu.VMEM((hg, d), jnp.float32),
        ],
    )
    # Scalar-prefetch arg: per-group valid lengths, one row per grid b.
    len_arg = lengths.astype(jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, hg, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(len_arg, q, k_q, v_q,
      jnp.asarray(k_scale, jnp.float32).reshape(g),
      jnp.asarray(v_scale, jnp.float32).reshape(g))
