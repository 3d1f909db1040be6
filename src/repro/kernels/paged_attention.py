"""Pallas TPU kernel: decode attention reading paged KV blocks **in place**.

The gather-view serving path (`repro.models.attention.paged_view`) rebuilds a
dense ``[B, n_lblk*bs]`` copy of every row's KV before `decode_attention` can
run — per segment that is a full extra round-trip of the pool through HBM, and
the fold-back at segment exit doubles it. This kernel deletes both copies: the
per-row ``block_table`` rides in as a **scalar-prefetch** operand, the
BlockSpec index maps resolve each grid step's logical block to its physical
pool block, and the DMA engine streams exactly the mapped blocks HBM→VMEM.
Unmapped table entries (``< 0`` or ``>= n_blocks`` — free rows, retired rows,
copy-on-write guards) are clamped for the DMA and masked to ``-inf`` in the
scores, so a dead row reads garbage bytes but contributes nothing.

Layout (matches :class:`repro.models.attention.PagedKVCache`):
  q        [B, Hkv, Hg, D]   f32/bf16 — one decode token per row
  k/v pool [n_blocks, bs, Hkv, D]     bf16 (kv16) or int8 (kv8);
           [n_blocks, bs, Hkv, D/2]   int8 at kv4 — two nibbles per byte
           (low nibble = even index)
  tidx     [n_blocks, bs]    int32 absolute token index per slot, −1 = empty
  scales   [B, Hkv]          f32 per-row dequant scales (kv8/kv4), in SMEM
  bt       [B * n_lblk]      int32 flattened block table (scalar prefetch)
  pos      [B]               int32 current absolute position (scalar prefetch)

Grid ``(B, n_lblk)`` with the logical-block axis sequential. One grid step
DMAs a whole pool block, every KV head at once: the TPU lowering requires a
block's last two dims to be ``(8k, 128k)`` or the array's own, and the pool's
last two dims are ``(Hkv, D)``. The heads are then a static loop in the
kernel body. Online-softmax scratch (running max ``m``, denominator ``l``,
accumulator) lives in VMEM across the block loop and is flushed on the last
block. The int8 path contracts on the int grid and folds the per-(B,Hkv)
scale into the scores/output afterwards — the exact operation order of the
jnp ``decode_attention`` int8 fast path, so the two stay numerically aligned.
The int4 path DMAs the packed half-width block and dequantizes **before** the
contraction — `decode_attention`'s kv4 (dequantize-first) order — so kv4
streams half of kv8's pool bytes per step. Mosaic cannot interleave lanes, so
the nibbles are never re-interleaved in VMEM: the wrapper splits ``q`` into
its even and odd halves of D, the kernel contracts the low nibbles against
the even half and the high nibbles against the odd half, keeps the output in
that split order, and the wrapper interleaves it back.
Validated in interpret mode against ``ref.paged_attention_ref`` and the
gather-view oracle (``tests/test_paged_attention_kernel.py``), and compiled
for a v5e chip in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_pallas", "paged_attention_pallas_multi"]

NEG_INF = -1e30


def _nibbles(packed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sign-extended (low, high) nibbles of a packed int4 block, as f32."""
    p = packed.astype(jnp.int32)
    return (((p << 28) >> 28).astype(jnp.float32),
            ((p << 24) >> 28).astype(jnp.float32))


def _dot_t(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b.T`` with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, tidx_ref, ks_ref, vs_ref,
            o_ref, m_ref, l_ref, acc_ref, *,
            n_lblk: int, n_blocks: int, bits: int, window: int,
            sm_scale: float, w: int, hg: int):
    """One (row, logical block) grid step over every KV head.

    The W queries of a row fold into the head-group compute dim (``[W*Hg,
    D]`` q block per head, ``[W*Hg, bs]`` scores); the plain decode step is
    ``W = 1``. Query ``wi = r // hg`` sits at absolute position ``pos + wi``
    (per-query causal mask) and folds its own entry of the per-position
    dequant-scale ladder, read from SMEM.
    """
    b = pl.program_id(0)
    lb = pl.program_id(1)
    hkv = q_ref.shape[0]

    @pl.when(lb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    entry = bt_ref[b * n_lblk + lb]
    mapped = (entry >= 0) & (entry < n_blocks)
    row_w = jax.lax.broadcasted_iota(jnp.int32, (w * hg, 1), 0) // hg
    qp = pos_ref[b] + row_w                                  # [W*Hg, 1]
    tidx = tidx_ref[...]                                     # [1, bs]
    keep = (mapped & (tidx >= 0) & (tidx <= qp)
            & (qp - tidx < window))                          # [W*Hg, bs]

    def scale_col(ref, h):
        col = jnp.zeros((w * hg, 1), jnp.float32)
        for wi in range(w):
            col = jnp.where(row_w == wi, ref[(b * w + wi) * hkv + h], col)
        return col

    for h in range(hkv):
        if bits == 8:
            ks, vs = scale_col(ks_ref, h), scale_col(vs_ref, h)
        q = q_ref[h].astype(jnp.float32) * sm_scale          # [W*Hg, D]
        half = q.shape[-1] // 2
        if bits == 4:
            # packed nibbles, dequantized before the dot — decode_attention's
            # kv4 (dequantize-first) order; q arrives split [even | odd] in D
            k_lo, k_hi = _nibbles(k_ref[:, h, :])            # [bs, D/2] each
            k_s = ks_ref[b * hkv + h]                        # kv4 has W = 1
            scores = (_dot_t(q[:, :half], k_lo * k_s)
                      + _dot_t(q[:, half:], k_hi * k_s))
        else:
            scores = _dot_t(q, k_ref[:, h, :].astype(jnp.float32))
            if bits == 8:
                # int-grid contraction, scale folded after (decode_attention)
                scores = scores * ks
        scores = jnp.where(keep, scores, NEG_INF)            # [W*Hg, bs]

        m_prev = m_ref[h]                                    # [W*Hg, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit zero on masked columns: with every key masked so far,
        # exp(NEG_INF − NEG_INF) would otherwise contribute 1 per dead slot
        p = jnp.where(keep, jnp.exp(scores - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if bits == 4:
            v_lo, v_hi = _nibbles(v_ref[:, h, :])
            v_s = vs_ref[b * hkv + h]
            acc_ref[h, :, :half] = acc_ref[h, :, :half] * alpha + jnp.dot(
                p, v_lo * v_s, preferred_element_type=jnp.float32)
            acc_ref[h, :, half:] = acc_ref[h, :, half:] * alpha + jnp.dot(
                p, v_hi * v_s, preferred_element_type=jnp.float32)
        else:
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v_ref[:, h, :].astype(jnp.float32),
                preferred_element_type=jnp.float32)
        m_ref[h] = m_new

        @pl.when(lb == n_lblk - 1)
        def _flush():
            # rows with no attendable key flush exact zeros; the ref oracle
            # pins the same corner to zero (an unmapped table's gather-fill
            # would yield zeros under a uniform softmax anyway), so dead rows
            # agree across backends bit-for-bit
            any_valid = m_ref[h] > NEG_INF * 0.5
            out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            if bits == 8:
                out = out * vs
            o_ref[h] = jnp.where(any_valid, out, 0.0).astype(o_ref.dtype)


def _paged_call(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                k_ladder: jax.Array, v_ladder: jax.Array,
                token_idx: jax.Array, block_table: jax.Array, pos: jax.Array,
                *, bits: int, window: int, interpret: bool) -> jax.Array:
    """q ``[B, W, Hkv, Hg, D]``, ladders ``[B, W, Hkv]`` → ``[B, W, Hkv,
    Hg, D]`` f32."""
    b, w, hkv, hg, d = q.shape
    n_blocks, bs, _, dk = k_pool.shape   # dk = D (kv8/kv16) or D/2 (kv4 packed)
    assert dk == (d // 2 if bits == 4 else d)
    assert bits != 4 or w == 1, "kv4 has no speculative window"
    _, n_lblk = block_table.shape
    # full-attention sentinel must exceed max(qpos - tidx) = pos + w - 1
    win = window if window > 0 else n_lblk * bs + w
    if bits == 4:
        q = jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1)

    kernel = functools.partial(
        _kernel, n_lblk=n_lblk, n_blocks=n_blocks, bits=bits, window=win,
        sm_scale=1.0 / d ** 0.5, w=w, hg=hg)

    def phys(r, lb, bt):
        # block-table indirection happens HERE, in the index map: the grid
        # cell's DMA source is the physical pool block the table names.
        # Unmapped entries clamp to a resident block (the bytes are fetched
        # but masked off in the kernel body) — the DMA must stay in bounds.
        return jnp.clip(bt[r * n_lblk + lb], 0, n_blocks - 1)

    pool_spec = pl.BlockSpec((None, bs, hkv, dk),
                             lambda r, lb, bt, p: (phys(r, lb, bt), 0, 0, 0))
    row_spec = pl.BlockSpec((None, hkv, w * hg, d),
                            lambda r, lb, bt, p: (r, 0, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # (block_table, pos)
        grid=(b, n_lblk),
        in_specs=[
            row_spec, pool_spec, pool_spec,
            pl.BlockSpec((None, 1, bs),
                         lambda r, lb, bt, p: (phys(r, lb, bt), 0, 0)),
            smem, smem,
        ],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, w * hg, 1), jnp.float32),
            pltpu.VMEM((hkv, w * hg, 1), jnp.float32),
            pltpu.VMEM((hkv, w * hg, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, w * hg, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_table.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      q.transpose(0, 2, 1, 3, 4).reshape(b, hkv, w * hg, d),
      k_pool, v_pool, token_idx.reshape(n_blocks, 1, bs),
      jnp.asarray(k_ladder, jnp.float32).reshape(-1),
      jnp.asarray(v_ladder, jnp.float32).reshape(-1))
    out = out.reshape(b, hkv, w, hg, d).transpose(0, 2, 1, 3, 4)
    if bits == 4:
        # [even | odd] split order back to interleaved D
        out = out.reshape(b, w, hkv, hg, 2, d // 2).swapaxes(-1, -2)
        out = out.reshape(b, w, hkv, hg, d)
    return out


@functools.partial(jax.jit,
                   static_argnames=("bits", "window", "interpret"))
def paged_attention_pallas(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           k_scale: jax.Array, v_scale: jax.Array,
                           token_idx: jax.Array, block_table: jax.Array,
                           pos: jax.Array, *, bits: int = 16,
                           window: int = 0,
                           interpret: bool = False) -> jax.Array:
    """In-place paged decode attention; see module docstring for layout.

    ``window <= 0`` means full attention. Returns ``[B, Hkv, Hg, D]`` f32.
    """
    assert bits in (4, 8, 16), \
        f"paged kernel supports kv16/kv8/kv4, got kv{bits}"
    b, hkv = q.shape[:2]
    ks = jnp.asarray(k_scale, jnp.float32).reshape(b, 1, hkv)
    vs = jnp.asarray(v_scale, jnp.float32).reshape(b, 1, hkv)
    return _paged_call(q[:, None], k_pool, v_pool, ks, vs, token_idx,
                       block_table, pos, bits=bits, window=window,
                       interpret=interpret)[:, 0]


@functools.partial(jax.jit,
                   static_argnames=("bits", "window", "interpret"))
def paged_attention_pallas_multi(q: jax.Array, k_pool: jax.Array,
                                 v_pool: jax.Array, k_ladder: jax.Array,
                                 v_ladder: jax.Array, token_idx: jax.Array,
                                 block_table: jax.Array, pos: jax.Array, *,
                                 bits: int = 16, window: int = 0,
                                 interpret: bool = False) -> jax.Array:
    """In-place paged attention for a W-query speculative window.

    q ``[B, W, Hkv, Hg, D]`` — query ``j`` at absolute position
    ``pos + j``; ``k_ladder``/``v_ladder`` ``[B, W, Hkv]`` are the
    per-position int8 dequant scale ladders (ignored at kv16).
    ``window <= 0`` means full attention. Returns ``[B, W, Hkv, Hg, D]``
    f32. Same kernel as :func:`paged_attention_pallas` — W rides in the q
    block, not the grid.
    """
    assert bits in (8, 16), f"paged kernel supports kv16/kv8, got kv{bits}"
    return _paged_call(q, k_pool, v_pool, k_ladder, v_ladder, token_idx,
                       block_table, pos, bits=bits, window=window,
                       interpret=interpret)
