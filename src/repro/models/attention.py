"""Attention: GQA with blockwise online-softmax (train/prefill) and cached decode.

Design notes (DESIGN §5):

* **Blockwise train/prefill** — ``lax.scan`` over KV blocks with an online
  softmax; the full ``[S, S]`` score matrix never materializes, so 32k-token
  prefill fits the per-device memory budget and the HLO stays compact for the
  multi-pod dry-run.
* **Window-as-data** — the causal window rides in as a traced int32 (`>= S`
  means full attention), so hybrid stacks (Hymba) mix SWA/global layers inside
  one ``lax.scan`` over layers, and the merged adaptive engine stays
  branch-free.
* **Decode** — one-token attention against a (optionally int8-quantized) KV
  cache; ring buffer for SWA. The Pallas ``qkv_attention`` kernel is the TPU
  deployment path for the int8 cache; the jnp path here has identical
  numerics/roofline and is what the dry-run lowers.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .pshard import constrain

__all__ = ["gqa_attention", "swa_attention", "decode_attention", "KVCache",
           "init_kv_cache", "update_kv_cache",
           "PagedKVCache", "init_paged_kv_cache", "update_paged_kv_cache",
           "paged_view", "paged_decode_attention", "prefix_attention",
           "kv_refine"]

NEG_INF = -1e30


def kv_refine(x: jax.Array, eff_bits: jax.Array) -> jax.Array:
    """Per-layer precision-policy fake-quant of fresh K/V projections.

    ``eff_bits`` is a traced int32 scalar — one entry of the searched
    per-layer bit-width schedule (``kv_table[profile, layer]``), so
    switching schedules never retraces. Applied at the attention boundary
    (immediately after the QKV projection) in **every** path that births
    K/V — cold prefill, continuation/chunked prefill suffixes, and decode
    steps — so attention reads, cache writes, and collected full-precision
    masters all see the same refined values; replayed prefix masters are
    already refined and must never pass through here again (fake-quant is
    not bit-stable under scale recomputation).

    Numerics: deterministic symmetric fake-quant on a per-position grid —
    ``amax`` over the head dim, ``qmax = 2^(bits-1) - 1``, round-to-nearest,
    clip. ``eff_bits >= 16`` is an exact passthrough (`jnp.where` with the
    f32 round-trip of ``x``), which is what pins a critical-class profile
    row of all-16 entries token-identical to the no-policy baseline.
    """
    eff = jnp.asarray(eff_bits, jnp.int32)
    qmax = jnp.exp2(jnp.minimum(eff, 15).astype(jnp.float32) - 1.0) - 1.0
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-9) / qmax
    fq = jnp.clip(jnp.round(xf / scale), -qmax, qmax) * scale
    return jnp.where(eff >= 16, xf, fq).astype(x.dtype)


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True,
                  window: jax.Array | int | None = None,
                  q_offset: jax.Array | int = 0,
                  block_k: int = 512,
                  unroll: bool = False,
                  kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """Blockwise GQA attention.

    q ``[B, S, H, D]``; k/v ``[B, Skv, Hkv, D]``; returns ``[B, S, H, D]``.
    ``window``: traced or static int; positions further back than ``window``
    are masked (full attention when ``window >= Skv``). ``q_offset`` shifts
    query positions (prefill continuation). ``kv_valid`` ``[B, Skv]`` bool
    masks per-row invalid keys (left-pad slots of ragged batches), exactly
    like the block-pad index check masks the block-padding keys.
    """
    b, s, h, d = q.shape
    _, skv, hkv, _ = k.shape
    assert h % hkv == 0
    hg = h // hkv
    bk = min(block_k, skv)
    # pad kv to a block multiple; padded keys are masked by the index check
    pad = (-skv) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blk = (skv + pad) // bk
    kvv_blocks = None
    if kv_valid is not None:
        kvv = jnp.pad(kv_valid, ((0, 0), (0, pad))) if pad else kv_valid
        kvv_blocks = kvv.reshape(b, n_blk, bk).transpose(1, 0, 2)  # [n_blk,B,bk]

    # bf16 until the score einsum (f32 accumulation preserved via
    # preferred_element_type): the S-resharding permutes then move half the
    # bytes (§Perf iteration 3)
    qh = (q * (d ** -0.5)).astype(q.dtype).reshape(b, s, hkv, hg, d)
    qh = qh.transpose(0, 2, 3, 1, 4)                     # [B, Hkv, Hg, S, D]
    # sequence-sharded attention compute: S over "tp" (GQA head counts rarely
    # divide the model axis); KV replicated across the s-shards (§Perf iter 1)
    qh = constrain(qh, "dp", None, None, "tp", None)
    kb = k.transpose(0, 2, 1, 3).reshape(b, hkv, n_blk, bk, d)
    vb = v.transpose(0, 2, 1, 3).reshape(b, hkv, n_blk, bk, d)
    kb = constrain(kb, "dp", None, None, None, None)
    vb = constrain(vb, "dp", None, None, None, None)

    win = jnp.asarray(skv + s if window is None else window, jnp.int32)
    qpos = jnp.asarray(q_offset, jnp.int32) + jnp.arange(s, dtype=jnp.int32)

    def body(carry, blk):
        m, l, acc = carry
        if kvv_blocks is None:
            kblk, vblk, j0 = blk                          # [B,Hkv,bk,D], scalar
            kvb = None
        else:
            kblk, vblk, j0, kvb = blk                     # kvb [B, bk]
        scores = jnp.einsum("bkgsd,bkud->bkgsu", qh, kblk.astype(qh.dtype),
                            preferred_element_type=jnp.float32)
        jpos = j0 + jnp.arange(bk, dtype=jnp.int32)       # global kv indices
        valid = jpos[None, :] < skv                       # [1, bk] (pad mask)
        if causal:
            keep = (jpos[None, :] <= qpos[:, None]) & \
                   (qpos[:, None] - jpos[None, :] < win) & valid
        else:
            keep = jnp.broadcast_to(valid, (s, bk))
        if kvb is not None:                               # per-row ragged mask
            keep = keep[None, :, :] & kvb[:, None, :]     # [B, s, bk]
            scores = jnp.where(keep[:, None, None], scores, NEG_INF)
        else:
            scores = jnp.where(keep[None, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("bkgsu,bkud->bkgsd", p,
                                           vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = constrain(jnp.full((b, hkv, hg, s, 1), NEG_INF, jnp.float32),
                   "dp", None, None, "tp", None)
    l0 = constrain(jnp.zeros((b, hkv, hg, s, 1), jnp.float32),
                   "dp", None, None, "tp", None)
    a0 = constrain(jnp.zeros((b, hkv, hg, s, d), jnp.float32),
                   "dp", None, None, "tp", None)
    j0s = jnp.arange(n_blk, dtype=jnp.int32) * bk
    xs = (kb.transpose(2, 0, 1, 3, 4), vb.transpose(2, 0, 1, 3, 4), j0s)
    if kvv_blocks is not None:
        xs = xs + (kvv_blocks,)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs,
                                  unroll=n_blk if unroll else 1)
    # cast before the transpose/reshape so the S→residual reshard moves bf16
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d)


def swa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int, block_q: int = 512,
                  q_offset: int = 0,
                  kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """Sliding-window attention with **block skipping** (§Perf iteration):
    each q block only touches the ``window + block_q`` keys it can see, so
    FLOPs scale with ``S·(window+bq)`` instead of ``S²`` (21× at S=32k,
    w=1024). Requires a *static* window (architectural, not profile-driven).

    q ``[B, S, H, D]``, k/v ``[B, S, Hkv, D]`` (self-attention lengths equal).
    ``kv_valid`` ``[B, S]`` bool masks per-row left-pad keys (ragged batches).
    """
    b, s, h, d = q.shape
    _, skv, hkv, _ = k.shape
    assert s == skv and q_offset == 0, "swa path is self-attention prefill"
    hg = h // hkv
    bq = min(block_q, s)
    pad_q = (-s) % bq
    nq = (s + pad_q) // bq
    w = window
    width = w + bq                     # static kv slice per q block

    qh = (q.astype(jnp.float32) * d ** -0.5)
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    qh = qh.reshape(b, nq, bq, hkv, hg, d).transpose(1, 0, 3, 4, 2, 5)
    # [nq, B, Hkv, Hg, bq, D]
    qh = constrain(qh, None, "dp", None, None, "tp", None)

    # left-pad keys by `w` so block i's visible range starts at index i·bq
    kp = jnp.pad(k.astype(jnp.float32), ((0, 0), (w, pad_q), (0, 0), (0, 0)))
    vp = jnp.pad(v.astype(jnp.float32), ((0, 0), (w, pad_q), (0, 0), (0, 0)))
    kp = constrain(kp, "dp", None, None, None)
    vp = constrain(vp, "dp", None, None, None)
    kvp = (None if kv_valid is None
           else jnp.pad(kv_valid, ((0, 0), (w, pad_q))))  # pads are invalid

    def one_block(i, q_blk):
        ks = jax.lax.dynamic_slice_in_dim(kp, i * bq, width, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vp, i * bq, width, axis=1)
        ks = ks.transpose(0, 2, 1, 3)                    # [B, Hkv, W, D]
        vs = vs.transpose(0, 2, 1, 3)
        scores = jnp.einsum("bkgsd,bkud->bkgsu", q_blk, ks)
        qpos = i * bq + jnp.arange(bq, dtype=jnp.int32)  # global q indices
        jpos = i * bq - w + jnp.arange(width, dtype=jnp.int32)
        keep = ((jpos[None, :] >= 0) & (jpos[None, :] <= qpos[:, None])
                & (qpos[:, None] - jpos[None, :] < w)
                & (qpos[:, None] < s) & (jpos[None, :] < s))
        if kvp is not None:                              # per-row ragged mask
            kvs = jax.lax.dynamic_slice_in_dim(kvp, i * bq, width, axis=1)
            scores = jnp.where(keep[None, None, None]
                               & kvs[:, None, None, None, :], scores, NEG_INF)
        else:
            scores = jnp.where(keep[None, None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bkgsu,bkud->bkgsd", p, vs)

    out = jax.lax.map(lambda iq: one_block(iq[0], iq[1]),
                      (jnp.arange(nq, dtype=jnp.int32), qh))
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, (s + pad_q), h, d)
    return out[:, :s].astype(q.dtype)


# ---------------------------------------------------------------------------
# decode path with KV cache (ring buffer for SWA, optional int8 storage)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Per-layer-stacked KV cache.

    ``k``/``v``: ``[B, S_slots, Hkv, D]`` — bf16; int8 when 8-bit quantized;
    int4 packs two values per byte along D (``[B, S_slots, Hkv, D/2]``,
    ``bits`` static field = 4). ``k_scale``/``v_scale`` are per ``[B, Hkv]``
    dequant scales. ``token_idx``: ``[B, S_slots]`` absolute token index per
    slot, −1 = empty (doubles as the ring-buffer validity mask).
    """

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array
    v_scale: jax.Array
    token_idx: jax.Array
    bits: int = 16  # static (pytree aux)


# `bits` must be aux data (static), not a traced leaf; keyed registration
# keeps the "kv/k"-style paths the sharding rules match on.
jax.tree_util.register_pytree_with_keys(
    KVCache,
    lambda c: ([(jax.tree_util.GetAttrKey(n), getattr(c, n))
                for n in ("k", "v", "k_scale", "v_scale", "token_idx")],
               (c.bits,)),
    lambda aux, ch: KVCache(*ch, bits=aux[0]),
)


def init_kv_cache(batch: int, slots: int, hkv: int, d: int, *,
                  bits: int = 16, dtype=jnp.bfloat16) -> KVCache:
    if bits == 4:
        assert d % 2 == 0
        shape = (batch, slots, hkv, d // 2)
        cdt = jnp.int8
    else:
        shape = (batch, slots, hkv, d)
        cdt = jnp.int8 if bits == 8 else dtype
    return KVCache(
        k=jnp.zeros(shape, cdt),
        v=jnp.zeros(shape, cdt),
        k_scale=jnp.ones((batch, hkv), jnp.float32),
        v_scale=jnp.ones((batch, hkv), jnp.float32),
        token_idx=jnp.full((batch, slots), -1, jnp.int32),
        bits=bits,
    )


def _quantize_kv(x: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Quantize new K/V rows onto the cache's running per-(B,Hkv) int grid
    (int4 packed two-per-byte along D)."""
    from repro.core.qtypes import pack_int4
    s = scale[:, None, :, None]
    qmax = 127 if bits == 8 else 7
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -qmax, qmax)
    if bits == 4:
        return pack_int4(q.astype(jnp.int8))
    return q.astype(jnp.int8)


def _dequantize_kv(data: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    from repro.core.qtypes import unpack_int4
    q = unpack_int4(data) if bits == 4 else data
    return q.astype(jnp.float32) * scale[:, None, :, None]


def _kv_step_quantize(cache, k_new: jax.Array, v_new: jax.Array):
    """Decode-step scale update + row quantization, shared by the contiguous
    and paged cache writers — they must stay bit-identical (the paged
    cache's token-identity to the contiguous path rides on this block), so
    it exists exactly once. Returns ``(k_scale, v_scale, k_row, v_row)``.

    Int caches keep a running max-abs scale (monotone → previously written
    rows stay valid); bf16 caches just cast.
    """
    if cache.bits in (4, 8):
        qmax = 127.0 if cache.bits == 8 else 7.0
        k_amax = jnp.max(jnp.abs(k_new.astype(jnp.float32)), axis=(1, 3))
        v_amax = jnp.max(jnp.abs(v_new.astype(jnp.float32)), axis=(1, 3))
        k_scale = jnp.maximum(cache.k_scale, k_amax / qmax + 1e-9)
        v_scale = jnp.maximum(cache.v_scale, v_amax / qmax + 1e-9)
        k_row = _quantize_kv(k_new, k_scale, cache.bits)[:, 0]
        v_row = _quantize_kv(v_new, v_scale, cache.bits)[:, 0]
    else:
        k_scale, v_scale = cache.k_scale, cache.v_scale
        k_row = k_new[:, 0].astype(cache.k.dtype)
        v_row = v_new[:, 0].astype(cache.v.dtype)
    return k_scale, v_scale, k_row, v_row


def _kv_window_quantize(cache, k_new: jax.Array, v_new: jax.Array):
    """W-token generalization of :func:`_kv_step_quantize` for speculative
    draft/verify windows. ``k_new``/``v_new`` are ``[B, W, Hkv, D]``.

    Int caches get a per-position **scale ladder** ``[B, W, Hkv]``:
    ``ladder[:, j]`` is exactly the running-max scale the greedy stepwise
    path would hold *after* folding position ``j`` (``cummax`` over the
    window's per-position amax, floored at the cache's current scale — max
    is associative, so this is bit-identical to folding one step at a
    time). Position ``j``'s rows are quantized under ``ladder[:, j]``, and
    the caller commits ``ladder[:, m-1]`` as the cache scale once the
    accepted count ``m`` is known — rejected tail positions never pollute
    the committed scale. Returns ``(k_ladder, v_ladder, k_rows, v_rows)``.

    int4 is not supported here (speculation is gated to kv8/kv16 upstream,
    see ``transformer.supports_speculation``).
    """
    b, w = k_new.shape[:2]
    if cache.bits in (4, 8):
        assert cache.bits == 8, "speculative windows require kv8/kv16"
        qmax = 127.0
        k_amax = jnp.max(jnp.abs(k_new.astype(jnp.float32)), axis=3)
        v_amax = jnp.max(jnp.abs(v_new.astype(jnp.float32)), axis=3)
        k_lad = jnp.maximum(cache.k_scale[:, None],
                            jax.lax.cummax(k_amax / qmax + 1e-9, axis=1))
        v_lad = jnp.maximum(cache.v_scale[:, None],
                            jax.lax.cummax(v_amax / qmax + 1e-9, axis=1))

        def quant(x, lad):
            q = jnp.round(x.astype(jnp.float32) / lad[..., None])
            return jnp.clip(q, -qmax, qmax).astype(jnp.int8)

        k_rows, v_rows = quant(k_new, k_lad), quant(v_new, v_lad)
    else:
        k_lad = jnp.broadcast_to(cache.k_scale[:, None],
                                 (b, w) + cache.k_scale.shape[1:])
        v_lad = jnp.broadcast_to(cache.v_scale[:, None],
                                 (b, w) + cache.v_scale.shape[1:])
        k_rows = k_new.astype(cache.k.dtype)
        v_rows = v_new.astype(cache.v.dtype)
    return k_lad, v_lad, k_rows, v_rows


def update_kv_cache_window(cache: KVCache, k_new: jax.Array,
                           v_new: jax.Array, pos: jax.Array):
    """Write a W-token draft/verify window at ring slots
    ``(pos + j) % slots`` for ``j in [0, W)``.

    The cache's committed ``k_scale``/``v_scale`` are left **unchanged** —
    the caller commits the per-position ladder entry of the last *accepted*
    position after the verify pass (rollback-free: rejected tail slots hold
    junk that the next window's write span always covers before any query
    reads it). Returns ``(cache', k_ladder, v_ladder)``.
    """
    b, slots = cache.token_idx.shape
    w = k_new.shape[1]
    qpos = pos[:, None] + jnp.arange(w, dtype=pos.dtype)[None]   # [B, W]
    slot = (qpos % slots).astype(jnp.int32)
    k_lad, v_lad, k_rows, v_rows = _kv_window_quantize(cache, k_new, v_new)
    bidx = jnp.arange(b)[:, None]
    new = KVCache(
        k=cache.k.at[bidx, slot].set(k_rows),
        v=cache.v.at[bidx, slot].set(v_rows),
        k_scale=cache.k_scale,
        v_scale=cache.v_scale,
        token_idx=cache.token_idx.at[bidx, slot].set(qpos.astype(jnp.int32)),
        bits=cache.bits,
    )
    return new, k_lad, v_lad


def update_kv_cache(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                    pos: jax.Array) -> KVCache:
    """Write one decode step (``k_new [B, 1, Hkv, D]``) at ring slot
    ``pos % slots``; updates running scales for int caches on the fly."""
    b, slots = cache.token_idx.shape
    slot = (pos % slots).astype(jnp.int32)                 # [B]
    k_scale, v_scale, k_row, v_row = _kv_step_quantize(cache, k_new, v_new)
    bidx = jnp.arange(b)
    return KVCache(
        k=cache.k.at[bidx, slot].set(k_row),
        v=cache.v.at[bidx, slot].set(v_row),
        k_scale=k_scale,
        v_scale=v_scale,
        token_idx=cache.token_idx.at[bidx, slot].set(pos.astype(jnp.int32)),
        bits=cache.bits,
    )


# ---------------------------------------------------------------------------
# paged KV cache: global block pool + per-row block tables (vLLM-style)
# ---------------------------------------------------------------------------

class PagedKVCache(NamedTuple):
    """Per-layer-stacked *paged* KV cache: a global pool of fixed-size blocks.

    Instead of reserving a contiguous ``[B, S_slots]`` row per slot, K/V live
    in a shared pool of ``n_blocks`` physical blocks of ``block_size`` tokens
    each, and every pool row maps its *logical* blocks onto physical ones
    through ``block_table`` — an int32 array, i.e. **data**, so remapping rows
    at admission/retirement never retraces or recompiles anything.

    ``k``/``v``: ``[n_blocks, bs, Hkv, D]`` (int8 when 8-bit quantized; int4
    packs two values per byte along D). ``token_idx``: ``[n_blocks, bs]``
    absolute token index per pool slot, −1 = empty — the same validity
    sentinel the contiguous :class:`KVCache` uses, so the dense per-row view
    built by :func:`paged_view` drops straight into
    :func:`decode_attention`. ``k_scale``/``v_scale`` stay per *row*
    (``[B, Hkv]``), carrying the exact running-max semantics of the
    contiguous cache — what keeps paged decode bit-identical to it at int KV
    precisions. ``block_table``: ``[B, n_lblk]``; entries ``>= n_blocks``
    (out of bounds) mean "unmapped" — reads of them fill with empty slots and
    writes to them are dropped, which is both the free-row representation and
    the copy-on-write guard for shared prefix blocks.
    """

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array
    v_scale: jax.Array
    token_idx: jax.Array
    block_table: jax.Array
    bits: int = 16  # static (pytree aux)


jax.tree_util.register_pytree_with_keys(
    PagedKVCache,
    lambda c: ([(jax.tree_util.GetAttrKey(n), getattr(c, n))
                for n in ("k", "v", "k_scale", "v_scale", "token_idx",
                          "block_table")],
               (c.bits,)),
    lambda aux, ch: PagedKVCache(*ch, bits=aux[0]),
)


def init_paged_kv_cache(batch: int, n_blocks: int, block_size: int,
                        n_lblk: int, hkv: int, d: int, *,
                        bits: int = 16, dtype=jnp.bfloat16) -> PagedKVCache:
    """Empty pool: ``n_blocks`` physical blocks, every row's table unmapped.

    ``n_lblk`` logical blocks per row bound each row's *virtual* sequence
    length at ``n_lblk * block_size`` slots (the analogue of the contiguous
    cache's ``slots``); the pool is sized independently — that decoupling of
    logical capacity from physical allocation is the entire point.
    """
    if bits == 4:
        assert d % 2 == 0
        shape = (n_blocks, block_size, hkv, d // 2)
        cdt = jnp.int8
    else:
        shape = (n_blocks, block_size, hkv, d)
        cdt = jnp.int8 if bits == 8 else dtype
    return PagedKVCache(
        k=jnp.zeros(shape, cdt),
        v=jnp.zeros(shape, cdt),
        k_scale=jnp.ones((batch, hkv), jnp.float32),
        v_scale=jnp.ones((batch, hkv), jnp.float32),
        token_idx=jnp.full((n_blocks, block_size), -1, jnp.int32),
        block_table=jnp.full((batch, n_lblk), n_blocks, jnp.int32),
        bits=bits,
    )


def paged_view(cache: PagedKVCache) -> KVCache:
    """Dense per-row gather view: ``[B, n_lblk*bs, ...]`` :class:`KVCache`.

    One gather per field, keyed off the block table; unmapped logical blocks
    fill with zeros / ``token_idx`` −1, i.e. *empty* slots, exactly the
    contiguous cache's pad representation (``kv_valid`` masking in attention
    falls out of ``token_idx`` as usual). Because a row's logical block
    ``l`` holds the tokens the contiguous ring would keep at slots
    ``[l*bs, (l+1)*bs)``, the view reconstructs the contiguous layout
    byte-for-byte and :func:`decode_attention` runs on it unchanged — paged
    decode stays token-identical to the contiguous path by construction.
    """
    b, n_lblk = cache.block_table.shape
    bs = cache.k.shape[1]

    def gather(pool, fill):
        g = jnp.take(pool, cache.block_table, axis=0, mode="fill",
                     fill_value=fill)                 # [B, n_lblk, bs, ...]
        return g.reshape(b, n_lblk * bs, *pool.shape[2:])

    return KVCache(
        k=gather(cache.k, 0), v=gather(cache.v, 0),
        k_scale=cache.k_scale, v_scale=cache.v_scale,
        token_idx=gather(cache.token_idx, -1),
        bits=cache.bits,
    )


def update_paged_kv_cache(cache: PagedKVCache, k_new: jax.Array,
                          v_new: jax.Array, pos: jax.Array) -> PagedKVCache:
    """Write one decode step through the block table.

    Virtual ring slot ``pos % (n_lblk*bs)`` resolves to physical block
    ``block_table[row, slot // bs]``, offset ``slot % bs`` — identical
    placement to the contiguous ring, so the gathered view stays
    bit-identical. Rows whose mapping is unmapped (retired rows whose table
    was cleared, never-admitted free rows) scatter with ``mode="drop"`` —
    a dead row can never write into a block that has been handed to another
    request. Scale updates share :func:`update_kv_cache`'s code exactly.
    """
    b, n_lblk = cache.block_table.shape
    bs = cache.k.shape[1]
    slot = (pos % (n_lblk * bs)).astype(jnp.int32)            # [B] virtual
    phys = jnp.take_along_axis(cache.block_table,
                               (slot // bs)[:, None], axis=1)[:, 0]
    off = slot % bs
    k_scale, v_scale, k_row, v_row = _kv_step_quantize(cache, k_new, v_new)
    return PagedKVCache(
        k=cache.k.at[phys, off].set(k_row, mode="drop"),
        v=cache.v.at[phys, off].set(v_row, mode="drop"),
        k_scale=k_scale, v_scale=v_scale,
        token_idx=cache.token_idx.at[phys, off].set(pos.astype(jnp.int32),
                                                    mode="drop"),
        block_table=cache.block_table,
        bits=cache.bits,
    )


def update_paged_kv_cache_window(cache: PagedKVCache, k_new: jax.Array,
                                 v_new: jax.Array, pos: jax.Array):
    """Paged counterpart of :func:`update_kv_cache_window`: scatter a
    W-token window through the block table with ``mode="drop"``.

    Placement matches :func:`update_paged_kv_cache` per position, so the
    gathered view stays bit-identical to the contiguous window writer.
    Two drop guards protect the pool: unmapped table entries (dead /
    CoW-guarded rows) drop as usual, and window positions past the row's
    virtual capacity are redirected to the unmapped sentinel instead of
    ring-wrapping — a speculative tail must never wrap onto logical block
    0, which may be a *shared* prefix master. Returns
    ``(cache', k_ladder, v_ladder)`` with committed scales unchanged.
    """
    b, n_lblk = cache.block_table.shape
    n_blocks, bs = cache.k.shape[0], cache.k.shape[1]
    w = k_new.shape[1]
    cap = n_lblk * bs
    qpos = pos[:, None] + jnp.arange(w, dtype=pos.dtype)[None]   # [B, W]
    slot = (qpos % cap).astype(jnp.int32)
    phys = jnp.take_along_axis(cache.block_table, slot // bs, axis=1)
    phys = jnp.where(qpos < cap, phys, n_blocks)        # no wrap onto masters
    off = slot % bs
    k_lad, v_lad, k_rows, v_rows = _kv_window_quantize(cache, k_new, v_new)
    new = PagedKVCache(
        k=cache.k.at[phys, off].set(k_rows, mode="drop"),
        v=cache.v.at[phys, off].set(v_rows, mode="drop"),
        k_scale=cache.k_scale, v_scale=cache.v_scale,
        token_idx=cache.token_idx.at[phys, off].set(qpos.astype(jnp.int32),
                                                    mode="drop"),
        block_table=cache.block_table,
        bits=cache.bits,
    )
    return new, k_lad, v_lad


def paged_decode_attention(q: jax.Array, cache: PagedKVCache, pos: jax.Array,
                           *, window: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """One-token attention **in place** against the paged block pool.

    The Pallas serving hot path: no ``[B, n_lblk*bs]`` gather view is ever
    materialized — the kernel's BlockSpec index maps resolve each logical
    block through ``cache.block_table`` (scalar-prefetched) and stream only
    the mapped physical blocks. Masking falls out of the pool's per-slot
    ``token_idx`` exactly as in :func:`decode_attention`, so ring wraparound
    and unmapped (free/retired/CoW-guarded) table entries are safe by the
    same argument. q ``[B, 1, H, D]`` → ``[B, 1, H, D]``; ``pos [B]`` is the
    current absolute position. ``window`` must be static (``None`` / ``>=
    slots`` = full attention). ``interpret=None`` auto-selects interpret
    mode off-TPU (the CPU oracle path); :func:`paged_view` +
    :func:`decode_attention` remains the gather-backend oracle.
    """
    from repro.kernels.paged_attention import paged_attention_pallas
    b, _, h, d = q.shape
    _, bs, hkv, _ = cache.k.shape
    hg = h // hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    slots = cache.block_table.shape[1] * bs
    win = 0 if window is None or int(window) > slots else int(window)
    out = paged_attention_pallas(
        q.reshape(b, hkv, hg, d), cache.k, cache.v,
        cache.k_scale, cache.v_scale, cache.token_idx, cache.block_table,
        pos, bits=cache.bits, window=win, interpret=bool(interpret))
    return out.reshape(b, 1, h, d).astype(q.dtype)


def paged_decode_attention_window(q: jax.Array, cache: PagedKVCache,
                                  pos: jax.Array, k_ladder: jax.Array,
                                  v_ladder: jax.Array, *,
                                  window: int | None = None,
                                  interpret: bool | None = None) -> jax.Array:
    """W-query speculative window attention **in place** against the pool.

    The multi-query analogue of :func:`paged_decode_attention`: q
    ``[B, W, H, D]`` with query ``j`` at absolute position ``pos + j`` and
    per-query int8 scale ladders ``[B, W, Hkv]`` (see
    :func:`decode_attention_window` for the ladder semantics). Streams only
    mapped physical blocks via the scalar-prefetched block table — still no
    dense gather view.
    """
    from repro.kernels.paged_attention import paged_attention_pallas_multi
    b, w, h, d = q.shape
    _, bs, hkv, _ = cache.k.shape
    hg = h // hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    slots = cache.block_table.shape[1] * bs
    win = 0 if window is None or int(window) > slots else int(window)
    out = paged_attention_pallas_multi(
        q.reshape(b, w, hkv, hg, d), cache.k, cache.v,
        k_ladder, v_ladder, cache.token_idx, cache.block_table,
        pos, bits=cache.bits, window=win, interpret=bool(interpret))
    return out.reshape(b, w, h, d).astype(q.dtype)


def prefix_attention(q: jax.Array, k_pre: jax.Array, v_pre: jax.Array,
                     k_suf: jax.Array, v_suf: jax.Array, *,
                     positions: jax.Array, prefix_len: jax.Array,
                     suffix_valid: jax.Array) -> jax.Array:
    """Continuation-prefill attention: suffix queries vs [prefix ++ suffix] keys.

    The shared-prefix admission path prefills only the *suffix* of a prompt
    whose prefix KV already exists; each suffix query must still attend over
    the full causal history. ``q``/``k_suf``/``v_suf`` are the suffix
    projections (``[B, S, H|Hkv, D]``, rows left-padded); ``k_pre``/``v_pre``
    ``[B, Pp, Hkv, D]`` hold the prefix keys/values (zero-padded past
    ``prefix_len[row]``); ``positions [B, S]`` are the suffix tokens'
    absolute positions (``prefix_len + local index``; negative on pads) and
    ``suffix_valid [B, S]`` masks the pads. Prefix keys sit at absolute
    positions ``0..prefix_len−1`` by construction — the logical-position
    invariant that makes a prefix shareable at all. Admission waves are small
    (``S``, ``Pp`` ≤ a few hundred), so a dense masked softmax is used rather
    than the blockwise online form. Full causal attention only — sliding-
    window stacks don't take the shared-prefix path.
    """
    b, s, h, d = q.shape
    _, pp, hkv, _ = k_pre.shape
    hg = h // hkv
    qh = (q.astype(jnp.float32) * d ** -0.5).reshape(b, s, hkv, hg, d)
    qh = qh.transpose(0, 2, 3, 1, 4)                      # [B, Hkv, Hg, S, D]
    kc = jnp.concatenate([k_pre, k_suf], axis=1).astype(jnp.float32)
    vc = jnp.concatenate([v_pre, v_suf], axis=1).astype(jnp.float32)
    kc = kc.transpose(0, 2, 1, 3)                         # [B, Hkv, Pp+S, D]
    vc = vc.transpose(0, 2, 1, 3)
    scores = jnp.einsum("bkgsd,bkud->bkgsu", qh, kc)
    ppos = jnp.arange(pp, dtype=jnp.int32)
    keep_pre = (ppos[None, None, :] < prefix_len[:, None, None]) & \
               (ppos[None, None, :] <= positions[:, :, None])    # [B, S, Pp]
    kqpos = positions                                      # suffix key pos
    keep_suf = suffix_valid[:, None, :] & \
               (kqpos[:, None, :] <= positions[:, :, None])      # [B, S, S]
    keep = jnp.concatenate([keep_pre, keep_suf], axis=-1)  # [B, S, Pp+S]
    scores = jnp.where(keep[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsu,bkud->bkgsd", p, vc)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d).astype(q.dtype)


def decode_attention(q: jax.Array, cache: KVCache, pos: jax.Array, *,
                     window: jax.Array | int | None = None) -> jax.Array:
    """One-token attention vs the cache. q ``[B, 1, H, D]`` → ``[B, 1, H, D]``.

    ``pos [B]`` is the current absolute position (the new token's index);
    masking uses the per-slot ``token_idx`` so ring-buffer wraparound is safe.
    """
    b, _, h, d = q.shape
    _, slots, hkv, _ = cache.k.shape
    hg = h // hkv
    qh = (q.astype(jnp.float32) * d ** -0.5).reshape(b, 1, hkv, hg, d)

    def qk(kf):
        # the W-query contraction of decode_attention_window at W = 1: XLA
        # sums a 4-D "bkgd" contraction in another order, and a one-token
        # step must match a speculative verify window bit for bit
        return jnp.einsum("bwkgd,bskd->bwkgs", qh, kf)[:, 0]

    if cache.bits == 8:
        # int8 fast path: contract on the int grid and fold the per-(B,Hkv)
        # dequant scale into the result — the same layout/order the Pallas
        # ``qkv_attention`` kernel uses, and no cache-sized scaled temporary
        # inside the decode scan.
        scores = qk(cache.k.astype(jnp.float32))
        scores = scores * cache.k_scale[:, :, None, None]
    else:
        if cache.bits == 4:
            kf = _dequantize_kv(cache.k, cache.k_scale, cache.bits)
        else:
            kf = cache.k.astype(jnp.float32)
        scores = qk(kf)                                    # [B,Hkv,Hg,slots]
    win = jnp.asarray(slots + 1 if window is None else window, jnp.int32)
    tidx = cache.token_idx                                  # [B, slots]
    keep = (tidx >= 0) & (tidx <= pos[:, None]) & (pos[:, None] - tidx < win)
    scores = jnp.where(keep[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    if cache.bits == 8:
        out = jnp.einsum("bkgs,bskd->bkgd", p, cache.v.astype(jnp.float32))
        out = out * cache.v_scale[:, :, None, None]
    else:
        vf = (_dequantize_kv(cache.v, cache.v_scale, cache.bits)
              if cache.bits == 4 else cache.v.astype(jnp.float32))
        out = jnp.einsum("bkgs,bskd->bkgd", p, vf)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def decode_attention_window(q: jax.Array, cache: KVCache, pos: jax.Array,
                            k_ladder: jax.Array, v_ladder: jax.Array, *,
                            window: jax.Array | int | None = None
                            ) -> jax.Array:
    """W-query attention vs the cache for a speculative draft/verify window.

    q ``[B, W, H, D]`` → ``[B, W, H, D]``; query ``j`` sits at absolute
    position ``pos + j`` and attends causally with the same per-slot
    ``token_idx`` mask as :func:`decode_attention`, restricted to
    ``token_idx <= pos + j``. Int8 caches fold the per-position scale
    **ladder** (``[B, W, Hkv]``): query ``j`` dequantizes every entry under
    ``ladder[:, j]`` — exactly the current-scale fold the greedy stepwise
    path applies after writing position ``j`` — which is what keeps a
    W-wide verify pass bit-identical to W greedy steps.
    """
    b, w, h, d = q.shape
    _, slots, hkv, _ = cache.k.shape
    hg = h // hkv
    qh = (q.astype(jnp.float32) * d ** -0.5).reshape(b, w, hkv, hg, d)
    if cache.bits == 8:
        scores = jnp.einsum("bwkgd,bskd->bwkgs", qh,
                            cache.k.astype(jnp.float32))
        scores = scores * k_ladder[..., None, None]
    else:
        assert cache.bits == 16, "speculative windows require kv8/kv16"
        scores = jnp.einsum("bwkgd,bskd->bwkgs", qh,
                            cache.k.astype(jnp.float32))
    qpos = pos[:, None] + jnp.arange(w, dtype=pos.dtype)[None]    # [B, W]
    win = jnp.asarray(slots + 1 if window is None else window, jnp.int32)
    tidx = cache.token_idx                                        # [B, slots]
    keep = ((tidx[:, None] >= 0) & (tidx[:, None] <= qpos[:, :, None])
            & (qpos[:, :, None] - tidx[:, None] < win))           # [B, W, S]
    scores = jnp.where(keep[:, :, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bwkgs,bskd->bwkgd", p, cache.v.astype(jnp.float32))
    if cache.bits == 8:
        out = out * v_ladder[..., None, None]
    return out.reshape(b, w, h, d).astype(q.dtype)
