"""Pallas TPU kernel: decode attention reading paged KV blocks **in place**.

The gather-view serving path (`repro.models.attention.paged_view`) rebuilds a
dense ``[B, n_lblk*bs]`` copy of every row's KV before `decode_attention` can
run — per segment that is a full extra round-trip of the pool through HBM, and
the fold-back at segment exit doubles it. This kernel deletes both copies: the
per-row ``block_table`` rides in as a **scalar-prefetch** operand, the
BlockSpec index maps resolve each grid step's logical blocks to physical
pool blocks, and the DMA engine streams those blocks HBM→VMEM. Unmapped
table entries (``< 0`` or ``>= n_blocks`` — free rows, retired rows,
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

**Grid and live bound.** The grid is ``(B, ceil(n_lblk / P))``: one step
covers a chunk of ``P = min(n_lblk, STEP_TOKENS // bs)`` consecutive logical
blocks of one row. Logical block ``lb`` holds only positions ``[lb·bs,
(lb+1)·bs)`` (the ring slot is ``pos % (n_lblk·bs)``), so a full-attention
row whose W queries have not wrapped (``pos + W <= n_lblk·bs``) can attend
to nothing past its first ``ceil((pos + W) / bs)`` blocks — its *live*
blocks. Chunks past that bound issue no copy and skip the body; a row that
has wrapped, and every windowed call, visits the whole table. Within the
blocks a step visits, masking comes from ``token_idx`` as in
`decode_attention`, so ring wraparound and stale slots stay safe.

**Pages per step.** Each of a chunk's ``P`` pages is an operand of its own
(the K pool, the V pool and ``token_idx`` are each passed ``P`` times) whose
index map names the page's physical block, every KV head of it at once.
(The TPU lays the pool's minor dim, D = 64, out padded to 128 lanes, and
Mosaic refuses a manual DMA that slices such an array; a whole-block
BlockSpec is the copy it accepts.) The pipeline double-buffers every
operand: it fetches the next step's pages while the current step computes,
also across row boundaries, so both grid axes run in order
(``"arbitrary"``; a v5e has one TensorCore, so nothing is lost). It issues
no copy when an operand's block index repeats from one step to the next,
and the index maps (:func:`_page_index`) make every page a step does not
need repeat: a chunk past the live bound names the next row's first chunk,
which is thus fetched while the row's last live chunk computes.

**Body.** Per KV head (a static loop): two MXU contractions over the
chunk's ``P·bs`` keys and an online-softmax update (running max ``m``,
denominator ``l``, accumulator in VMEM scratch across the row's chunks,
flushed on the row's last grid step; a row with no attendable key flushes
exact zeros). The int8 path contracts on the int grid and folds the
per-(B,Hkv) scale into the scores/output afterwards — the exact operation
order of the jnp ``decode_attention`` int8 fast path, so the two stay
numerically aligned. The int4 path copies the packed half-width pages and
dequantizes **before** the contraction — `decode_attention`'s kv4
(dequantize-first) order — so kv4 streams half of kv8's pool bytes. Mosaic
cannot interleave lanes, so the nibbles are never re-interleaved in VMEM:
the wrapper splits ``q`` into its even and odd halves of D, the kernel
contracts the low nibbles against the even half and the high nibbles
against the odd half, keeps the output in that split order, and the
wrapper interleaves it back.

Validated in interpret mode against ``ref.paged_attention_ref`` and the
gather-view oracle (``tests/test_paged_attention_kernel.py``), and compiled
for a v5e chip in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_pallas", "paged_attention_pallas_multi",
           "pages_per_step"]

NEG_INF = -1e30
# KV tokens one grid step covers (chosen by an on-chip sweep, PERF.md)
STEP_TOKENS = 128


def pages_per_step(block_size: int, n_lblk: int) -> int:
    """Pages ``P`` one grid step copies and contracts."""
    return max(1, min(n_lblk, STEP_TOKENS // block_size))


def _nibbles(packed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sign-extended (low, high) nibbles of a packed int4 block, as f32."""
    p = packed.astype(jnp.int32)
    return (((p << 28) >> 28).astype(jnp.float32),
            ((p << 24) >> 28).astype(jnp.float32))


def _dot_t(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b.T`` with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# The index maps below are traced once per page operand (3P of them), so
# their scalar arithmetic, like the kernel body's per-page work, uses lax
# directly: a jnp operator on a tracer costs ~0.6 ms to trace, which over
# 3P maps and p pages per head more than doubled the kernel's share of a
# server's set-up.
_i32 = np.int32


def _where(pred, a, b):
    return lax.select(pred, _i32(a) if isinstance(a, int) else a,
                      _i32(b) if isinstance(b, int) else b)


def _n_live(pos_ref, r, *, w: int, bs: int, n_lblk: int, full: bool):
    """Logical blocks row ``r`` visits, at least one: up to its last query
    position for a full-attention row that has not wrapped, else all."""
    if not full:
        return _i32(n_lblk)
    end = lax.add(pos_ref[r], _i32(w))
    live = lax.div(lax.add(lax.max(end, _i32(1)), _i32(bs - 1)), _i32(bs))
    return _where(lax.le(end, _i32(n_lblk * bs)), live, n_lblk)


def _page_index(r, c, bt_ref, pos_ref, *, i: int, p: int, n_rows: int,
                n_lblk: int, n_blocks: int, w: int, bs: int, full: bool):
    """Physical pool block that page ``i`` of grid step ``(r, c)`` copies.

    The pipeline issues no copy when a block index repeats from one step to
    the next, so every page a step does not need repeats one already in
    flight: a chunk past row ``r``'s live bound names the next row's first
    chunk (fetched while ``r``'s last live chunk computes; the last row
    repeats its own last live chunk), and a page past the bound inside a
    live chunk names the page it held in the chunk before. Unmapped entries
    clamp into the pool (fetched, then masked in the body)."""
    live = functools.partial(_n_live, pos_ref, w=w, bs=bs, n_lblk=n_lblk,
                             full=full)
    nl = live(r)
    last = lax.div(lax.sub(nl, _i32(1)), _i32(p))
    dead = lax.gt(c, last)
    r1 = lax.add(r, _i32(1))
    nxt = lax.lt(r1, _i32(n_rows))
    rr = _where(lax.bitwise_and(dead, nxt), r1, r)
    cc = _where(dead, _where(nxt, 0, last), c)
    nl = _where(lax.bitwise_and(dead, nxt),
                live(lax.min(r1, _i32(n_rows - 1))), nl)
    lb = lax.add(lax.mul(cc, _i32(p)), _i32(i))
    lb = _where(lax.lt(lb, nl), lb,
                _where(lax.gt(cc, _i32(0)), lax.sub(lb, _i32(p)),
                       lax.sub(nl, _i32(1))))
    entry = bt_ref[lax.add(lax.mul(rr, _i32(n_lblk)), lb)]
    return lax.clamp(_i32(0), entry, _i32(n_blocks - 1))


def _kernel(bt_ref, pos_ref, q_ref, *refs, n_lblk: int, n_blocks: int,
            bits: int, window: int, full: bool, sm_scale: float, w: int,
            hg: int, bs: int, p: int):
    """One (row, chunk of ``p`` logical blocks) grid step over every KV head.

    ``refs`` are the chunk's ``p`` K pages, ``p`` V pages and ``p`` token
    index pages, the two scale ladders (SMEM), the output and the
    online-softmax scratch. The W queries of a row fold into the head-group
    compute dim (``[W*Hg, D]`` q block per head, ``[W*Hg, p*bs]`` scores);
    the plain decode step is ``W = 1``. Query ``wi = r // hg`` sits at
    absolute position ``pos + wi`` (per-query causal mask) and folds its own
    entry of the per-position dequant-scale ladder, read from SMEM.
    """
    k_pages, v_pages, t_pages = refs[:p], refs[p:2 * p], refs[2 * p:3 * p]
    ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs[3 * p:]
    b, c = pl.program_id(0), pl.program_id(1)
    hkv = q_ref.shape[0]
    row_w = jax.lax.broadcasted_iota(jnp.int32, (w * hg, 1), 0) // hg
    nl = _n_live(pos_ref, b, w=w, bs=bs, n_lblk=n_lblk, full=full)

    def scale_col(ref, h):
        col = jnp.zeros((w * hg, 1), jnp.float32)
        for wi in range(w):
            col = jnp.where(row_w == wi, ref[(b * w + wi) * hkv + h], col)
        return col

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c * p < nl)
    def _chunk():
        # key columns of live, mapped pages; the others hold a repeated
        # page (past the live bound) or a clamped one (unmapped)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, p * bs), 1)
        real = jnp.zeros((1, p * bs), jnp.int32)
        for i in range(p):
            lb = lax.add(lax.mul(c, _i32(p)), _i32(i))
            entry = bt_ref[lax.add(lax.mul(b, _i32(n_lblk)),
                                   lax.min(lb, _i32(n_lblk - 1)))]
            mapped = lax.bitwise_and(
                lax.lt(lb, nl), lax.bitwise_and(
                    lax.ge(entry, _i32(0)), lax.lt(entry, _i32(n_blocks))))
            real = jnp.where((col >= i * bs) & (col < (i + 1) * bs),
                             lax.convert_element_type(mapped, jnp.int32),
                             real)

        qp = pos_ref[b] + row_w                              # [W*Hg, 1]
        tidx = jnp.concatenate([t[...] for t in t_pages], axis=1)
        keep = ((real > 0) & (tidx >= 0) & (tidx <= qp)
                & (qp - tidx < window))                      # [W*Hg, p*bs]

        def head(pages, h, dtype):
            """Head ``h`` of the chunk's pages: ``[p*bs, dk]`` (lax, not
            jnp: traced 2 × Hkv × p times)."""
            return lax.concatenate([lax.convert_element_type(pg[:, h, :],
                                                             dtype)
                                    for pg in pages], 0)

        for h in range(hkv):
            q = q_ref[h].astype(jnp.float32) * sm_scale      # [W*Hg, D]
            half = q.shape[-1] // 2
            if bits == 4:
                # packed nibbles, dequantized before the dot —
                # decode_attention's kv4 (dequantize-first) order; q arrives
                # split [even | odd] in D
                k_lo, k_hi = _nibbles(head(k_pages, h, jnp.int32))
                k_s = ks_ref[b * hkv + h]                    # kv4 has W = 1
                scores = (_dot_t(q[:, :half], k_lo * k_s)
                          + _dot_t(q[:, half:], k_hi * k_s))
            else:
                scores = _dot_t(q, head(k_pages, h, jnp.float32))
                if bits == 8:
                    # int-grid contraction, scale folded after
                    # (decode_attention)
                    scores = scores * scale_col(ks_ref, h)
            scores = jnp.where(keep, scores, NEG_INF)        # [W*Hg, p*bs]

            m_prev = m_ref[h]                                # [W*Hg, 1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # explicit zero on masked columns: with every key masked so far,
            # exp(NEG_INF − NEG_INF) would otherwise contribute 1 per dead
            # slot
            pr = jnp.where(keep, jnp.exp(scores - m_new), 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
            if bits == 4:
                v_lo, v_hi = _nibbles(head(v_pages, h, jnp.int32))
                v_s = vs_ref[b * hkv + h]
                acc_ref[h, :, :half] = acc_ref[h, :, :half] * alpha + jnp.dot(
                    pr, v_lo * v_s, preferred_element_type=jnp.float32)
                acc_ref[h, :, half:] = acc_ref[h, :, half:] * alpha + jnp.dot(
                    pr, v_hi * v_s, preferred_element_type=jnp.float32)
            else:
                acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                    pr, head(v_pages, h, jnp.float32),
                    preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(c == pl.num_programs(1) - 1)
    def _flush():
        # rows with no attendable key flush exact zeros; the ref oracle pins
        # the same corner to zero (an unmapped table's gather-fill would
        # yield zeros under a uniform softmax anyway), so dead rows agree
        # across backends bit-for-bit
        for h in range(hkv):
            any_valid = m_ref[h] > NEG_INF * 0.5
            out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            if bits == 8:
                out = out * scale_col(vs_ref, h)
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
    p = pages_per_step(bs, n_lblk)
    # full-attention sentinel must exceed max(qpos - tidx) = pos + w - 1
    win = window if window > 0 else n_lblk * bs + w
    if bits == 4:
        q = jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1)

    kernel = functools.partial(
        _kernel, n_lblk=n_lblk, n_blocks=n_blocks, bits=bits, window=win,
        full=window <= 0, sm_scale=1.0 / d ** 0.5, w=w, hg=hg, bs=bs, p=p)

    def page_spec(i, block):
        def index(r, cc, bt_ref, pos_ref):
            blk = _page_index(r, cc, bt_ref, pos_ref, i=i, p=p, n_rows=b,
                              n_lblk=n_lblk, n_blocks=n_blocks, w=w, bs=bs,
                              full=window <= 0)
            return (blk,) + (0,) * (len(block) - 1)
        return pl.BlockSpec(block, index)

    row_spec = pl.BlockSpec((None, hkv, w * hg, d),
                            lambda r, cc, bt, ps: (r, 0, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    pool_block, tidx_block = (None, bs, hkv, dk), (None, 1, bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # (block_table, pos)
        grid=(b, -(-n_lblk // p)),
        in_specs=([row_spec]
                  + [page_spec(i, pool_block) for i in range(p)] * 2
                  + [page_spec(i, tidx_block) for i in range(p)]
                  + [smem, smem]),
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, w * hg, 1), jnp.float32),
            pltpu.VMEM((hkv, w * hg, 1), jnp.float32),
            pltpu.VMEM((hkv, w * hg, d), jnp.float32),
        ],
    )
    tidx = token_idx.reshape(n_blocks, 1, bs)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, w * hg, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(block_table.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      q.transpose(0, 2, 1, 3, 4).reshape(b, hkv, w * hg, d),
      *[k_pool] * p, *[v_pool] * p, *[tidx] * p,
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
