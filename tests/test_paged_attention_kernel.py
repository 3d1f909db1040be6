"""In-place Pallas paged-attention kernel + chunked prefill.

The load-bearing properties of the serving hot-path rewrite:

* the kernel (interpret mode) matches the gather-view ``decode_attention``
  oracle to float precision at kv16, kv8 and kv4, single-query and in a
  W = 5 window, across contexts ending on block and chunk (pages per grid
  step) boundaries, a full table, a wrapped ring, sliding windows,
  fragmented/out-of-order block tables, and dead rows (both the ``-1`` and
  the ``>= n_blocks`` unmapped sentinels);
* pages past a row's live bound are neither fetched nor computed: NaN
  there leaves the output finite and equal to the oracle's with zeros;
* the ``pallas`` segment backend is token-identical to the ``gather``
  backend / solo generation at kv16 and kv8 — including shared-prefix
  copy-on-write rows — while materializing **no** ``[B, n_lblk*bs]`` view
  and no exit fold-back (guarded at the dispatch level and in the jaxpr);
* chunked prefill emits exactly the tokens of an unchunked admission.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.budgets import SegmentBudget, trace_segment
from repro.analysis.jaxpr_check import has_adjacent_dims
from repro.configs import get_smoke
from repro.core.engine import AdaptiveEngine, QuantIndex
from repro.core.profiles import paper_profiles
from repro.core.qtypes import pack_int4
from repro.kernels import ref
from repro.kernels.paged_attention import (pages_per_step,
                                           paged_attention_pallas,
                                           paged_attention_pallas_multi)
from repro.models import transformer as T
from repro.serving.engine import AdaptiveServer, Request, ServingConfig
from repro.serving.scheduler import ContinuousScheduler


def _build(arch="granite-3-2b"):
    cfg = get_smoke(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    names = T.quant_layer_names(cfg)
    profs = paper_profiles(names, inner_layers=[])
    eng = AdaptiveEngine(tuple(profs), QuantIndex(names),
                         lambda p, br, b: T.train_loss(p, cfg, br, b))
    return cfg, params, eng


@pytest.fixture(scope="module")
def dense_parts():
    return _build()


def _solo_tokens(parts, req, kv_bits=16, slots=64):
    cfg, params, eng = parts
    srv = AdaptiveServer(cfg, params, eng,
                         ServingConfig(slots=slots, max_batch=4,
                                       kv_bits=kv_bits))
    return srv.generate(req.tokens[None, :], req.max_new)["tokens"][0]


# ---------------------------------------------------------------------------
# kernel vs gather-view oracle (interpret mode)
# ---------------------------------------------------------------------------

BS, N_LBLK = 16, 20                 # 320 table slots
P = pages_per_step(BS, N_LBLK)      # pages per grid step: chunks P*BS long
CAP = N_LBLK * BS
# contexts on block and chunk boundaries, and one holding the whole table
LENGTHS = (BS - 1, BS, BS + 1, 2 * BS + 1, P * BS - 1, P * BS, P * BS + 1,
           CAP)
WRAPPED = (CAP + 37,)               # ring positions past the table


def _pool_case(seed, lengths, *, n_blocks=128, bs=BS, n_lblk=N_LBLK, hkv=2,
               hg=2, d=16, kv_bits=16, w=1, wrapped=(), dead_sentinels=(),
               map_all=False):
    """Fragmented paged state: per-row out-of-order physical blocks, cache
    lengths straddling block and chunk boundaries, rows whose ring has
    wrapped (every block mapped, ``token_idx`` holding each slot's latest
    position), optional dead rows whose tables hold only unmapped
    sentinels. Row ``r`` with context ``n`` decodes W queries at ``n - w
    ..n - 1``. ``map_all`` maps every logical block of the live rows, the
    blocks past the context too (empty: ``token_idx`` −1). At ``w > 1`` q
    is ``[B, W, Hkv, Hg, D]`` and the scales ``[B, W, Hkv]`` ladders."""
    rng = np.random.default_rng(seed)
    b = len(lengths) + len(wrapped) + len(dead_sentinels)
    lead = (b,) if w == 1 else (b, w)
    q = jnp.asarray(rng.normal(size=(*lead, hkv, hg, d)), jnp.float32)
    pool = (n_blocks, bs, hkv, d)
    if kv_bits == 16:
        kp, vp = (jnp.asarray(rng.normal(size=pool), jnp.float32)
                  .astype(jnp.bfloat16) for _ in range(2))
        ks = vs = jnp.ones((*lead, hkv), jnp.float32)
    else:
        qmax = 127 if kv_bits == 8 else 7
        kp, vp = (jnp.asarray(rng.integers(-qmax, qmax + 1, pool), jnp.int8)
                  for _ in range(2))
        if kv_bits == 4:
            kp, vp = pack_int4(kp), pack_int4(vp)
        ks, vs = (jnp.asarray(rng.uniform(0.01, 0.1, (*lead, hkv)),
                              jnp.float32) for _ in range(2))
    perm = iter(rng.permutation(n_blocks))
    tidx = np.full((n_blocks, bs), -1, np.int32)
    bt = np.full((b, n_lblk), n_blocks, np.int32)
    pos = np.zeros((b,), np.int32)
    cap = n_lblk * bs
    for r, ln in enumerate(lengths):
        pos[r] = ln - w
        for lb in range(n_lblk if map_all else -(-ln // bs)):
            bt[r, lb] = next(perm)
            nv = min(max(ln - lb * bs, 0), bs)
            tidx[bt[r, lb], :nv] = lb * bs + np.arange(nv)
    for r, last in enumerate(wrapped, len(lengths)):
        pos[r] = last - w + 1
        for lb in range(n_lblk):
            bt[r, lb] = next(perm)
            v = lb * bs + np.arange(bs)
            tidx[bt[r, lb]] = last - (last - v) % cap
    for i, sent in enumerate(dead_sentinels):
        bt[len(lengths) + len(wrapped) + i, :] = sent   # -1 / n_blocks
    return (q, kp, vp, ks, vs, jnp.asarray(tidx), jnp.asarray(bt),
            jnp.asarray(pos))


def _oracle(case, bits, window=0):
    """``ref.paged_attention_ref``; a W-query window is W single-query
    calls, query ``j`` at ``pos + j`` with ladder entry ``j``."""
    q, kp, vp, ks, vs, tidx, bt, pos = case
    if q.ndim == 4:
        return ref.paged_attention_ref(*case, bits=bits, window=window)
    return jnp.stack([ref.paged_attention_ref(
        q[:, j], kp, vp, ks[:, j], vs[:, j], tidx, bt, pos + j, bits=bits,
        window=window) for j in range(q.shape[1])], axis=1)


@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_kernel_matches_gather_oracle(kv_bits):
    """Contexts on block and chunk boundaries (P*bs − 1, P*bs, P*bs + 1), a
    row holding the whole table and a wrapped row, through fragmented
    out-of-order tables + two dead rows (−1 and ≥ n_blocks sentinels): the
    kernel's output equals the gather-view oracle to float precision, and
    dead rows flush exact zeros on both paths."""
    case = _pool_case(3, LENGTHS, kv_bits=kv_bits, wrapped=WRAPPED,
                      dead_sentinels=(-1, 128))
    out_k = paged_attention_pallas(*case, bits=kv_bits, interpret=True)
    out_r = _oracle(case, kv_bits)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=1e-5)
    assert np.all(np.asarray(out_k)[-2:] == 0)      # dead rows: exact zeros
    assert np.all(np.asarray(out_r)[-2:] == 0)


@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_kernel_windowed_matches_oracle(kv_bits):
    """Sliding-window masking (ring semantics via token_idx) agrees; a
    windowed call visits every page, a wrapped row's too."""
    case = _pool_case(11, (9, 17, 23, P * BS + 1), kv_bits=kv_bits,
                      wrapped=WRAPPED)
    out_k = paged_attention_pallas(*case, bits=kv_bits, window=8,
                                   interpret=True)
    out_r = _oracle(case, kv_bits, window=8)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("kv_bits,window", [(16, 0), (8, 0), (16, 24)])
def test_kernel_multi_matches_oracle(kv_bits, window):
    """The W = 5 speculative window: each query's causal mask and scale
    ladder entry, with windows whose last query ends on, before and past a
    chunk boundary, a full table and both dead-row sentinels."""
    case = _pool_case(17, LENGTHS, kv_bits=kv_bits, w=5,
                      dead_sentinels=(-1, 128))
    out_k = paged_attention_pallas_multi(*case, bits=kv_bits, window=window,
                                         interpret=True)
    out_r = _oracle(case, kv_bits, window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=1e-5)
    assert np.all(np.asarray(out_k)[-2:] == 0)


@pytest.mark.parametrize("w", [1, 5])
def test_kernel_skips_pages_past_live_bound(w):
    """Every logical block of every row is mapped, and the pages past each
    row's last query position hold NaN keys and values (``token_idx`` −1):
    the kernel neither fetches nor computes them, so its output is finite
    and equals the oracle's on the same pool with those pages zeroed."""
    case = _pool_case(23, LENGTHS[:-1], n_blocks=160, w=w, map_all=True,
                      dead_sentinels=(-1,))
    q, kp, vp, ks, vs, tidx, bt, pos = case
    past = [int(bt[r, lb]) for r, ln in enumerate(LENGTHS[:-1])
            for lb in range(-(-ln // BS), N_LBLK)]
    assert past
    poisoned = (kp.at[np.asarray(past)].set(jnp.nan),
                vp.at[np.asarray(past)].set(jnp.nan))
    kernel = paged_attention_pallas if w == 1 else paged_attention_pallas_multi
    out_k = np.asarray(kernel(q, *poisoned, ks, vs, tidx, bt, pos, bits=16,
                              interpret=True))
    zeroed = (kp.at[np.asarray(past)].set(0), vp.at[np.asarray(past)].set(0))
    out_r = np.asarray(_oracle((q, *zeroed, ks, vs, tidx, bt, pos), 16))
    assert np.isfinite(out_k).all()
    np.testing.assert_allclose(out_k, out_r, atol=2e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# pallas segment backend: token identity + no-view guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [16, 8])
def test_pallas_backend_token_identity(dense_parts, kv_bits):
    """The in-place kernel backend emits exactly the gather/solo tokens for
    prompts straddling block boundaries, at bf16 and int8 KV."""
    cfg, params, eng = dense_parts
    scfg = ServingConfig(slots=64, max_batch=4, kv_bits=kv_bits,
                         block_size=8, paged_backend="pallas")
    srv = AdaptiveServer(cfg, params, eng, scfg)
    assert srv.paged_backend == "pallas"
    sched = ContinuousScheduler(srv, quantum=4)
    rng = np.random.default_rng(13)
    reqs = [Request(tokens=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new=mn)
            for n, mn in [(7, 6), (9, 5), (17, 6)]]
    for r in reqs:
        sched.submit(r)
    results = sched.run()
    for req, res in zip(reqs, results):
        assert res["tokens"] == _solo_tokens(dense_parts, req, kv_bits)


def test_pallas_backend_shared_cow_identity(dense_parts):
    """Shared-prefix CoW rows decode through the kernel against blocks they
    map but must never write: both sharers match solo and the shared
    blocks' bytes are untouched."""
    cfg, params, eng = dense_parts
    scfg = ServingConfig(slots=64, max_batch=4, block_size=8,
                         paged_backend="pallas")
    srv = AdaptiveServer(cfg, params, eng, scfg)
    sched = ContinuousScheduler(srv, quantum=2)
    rng = np.random.default_rng(29)
    sys_prompt = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    r1 = Request(tokens=np.concatenate(
        [sys_prompt, rng.integers(0, cfg.vocab, 4).astype(np.int32)]),
        max_new=8)
    r2 = Request(tokens=np.concatenate(
        [sys_prompt, rng.integers(0, cfg.vocab, 7).astype(np.int32)]),
        max_new=6)
    sched.submit(r1)
    sched.step()                              # r1 admitted cold + registered
    entry = max(sched.registry._entries.values(), key=lambda e: e.n_tokens)
    bids = np.asarray(entry.block_ids)
    pool = sched._caches["kv"]
    snap_k = np.asarray(pool.k[:, bids]).copy()
    sched.submit(r2)                          # shares while r1 is still live
    while sched.step():
        pass
    assert sched.registry.hits == 1
    pool = sched._caches["kv"]
    assert np.array_equal(np.asarray(pool.k[:, bids]), snap_k)
    results = sched.run()
    for req, res in zip((r1, r2), results):
        assert res["tokens"] == _solo_tokens(dense_parts, req)


_VIEW_BUDGET = SegmentBudget(
    name="test-no-view", arch="granite-3-2b", batch=3, slots=40,
    block_size=8, pool_blocks=None, kv_bits=16, steps=4,
    max_aval_bytes=10 ** 9)


def test_segment_pallas_no_view_materialization(dense_parts, monkeypatch):
    """Dispatch + jaxpr guard for the acceptance criterion: the pallas
    segment executable contains NO ``[B, n_lblk*bs]`` view materialization
    or exit fold-back. ``paged_view`` is never even traced, and no
    intermediate in the jaxpr carries the dense-view shape — while the
    gather backend (the oracle) demonstrably produces both, proving the
    guard detects what it claims to. Enforced via the named ``analysis``
    invariant ``no-gather-view`` (budgets.trace_segment +
    jaxpr_check.has_adjacent_dims)."""
    import repro.models.transformer as TT
    calls = {"n": 0}
    orig = TT.paged_view

    def counting(cache):
        calls["n"] += 1
        return orig(cache)

    monkeypatch.setattr(TT, "paged_view", counting)
    dims = (_VIEW_BUDGET.batch, _VIEW_BUDGET.slots_padded)
    jaxpr_p = trace_segment(dense_parts, "pallas", _VIEW_BUDGET)
    assert calls["n"] == 0                      # never dispatched
    assert not has_adjacent_dims(jaxpr_p, dims)

    jaxpr_g = trace_segment(dense_parts, "gather", _VIEW_BUDGET)
    assert calls["n"] > 0                       # oracle path gathers
    assert has_adjacent_dims(jaxpr_g, dims)


# ---------------------------------------------------------------------------
# intra-wave prefix dedup
# ---------------------------------------------------------------------------

def test_intra_wave_prefix_dedup(dense_parts):
    """Two identical prompts admitted in the SAME cold wave: the second
    defers its lookup past the wave that registers the prefix and rides
    the shared path (registry hit) instead of prefilling the prefix again
    — and both still match solo generation exactly."""
    cfg, params, eng = dense_parts
    scfg = ServingConfig(slots=64, max_batch=4, block_size=8)
    srv = AdaptiveServer(cfg, params, eng, scfg)
    sched = ContinuousScheduler(srv, quantum=4)
    rng = np.random.default_rng(5)
    sys_p = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    r1 = Request(tokens=np.concatenate(
        [sys_p, rng.integers(0, cfg.vocab, 4).astype(np.int32)]), max_new=6)
    r2 = Request(tokens=r1.tokens.copy(), max_new=6)        # identical
    r3 = Request(tokens=np.concatenate(                     # same sys prefix
        [sys_p, rng.integers(0, cfg.vocab, 3).astype(np.int32)]), max_new=5)
    for r in (r1, r2, r3):
        sched.submit(r)
    assert sched.admit() == 3                 # ONE round admits all three
    assert sched.registry.hits == 2           # r2 and r3 both deduped
    results = sched.run()
    for req, res in zip((r1, r2, r3), results):
        assert res["tokens"] == _solo_tokens(dense_parts, req)


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [16, 8])
def test_chunked_prefill_token_identity(dense_parts, kv_bits):
    """Long prompts admitted in block-aligned chunks (interleaved with
    decode segments) emit exactly the unchunked-admission tokens — at kv8
    the accumulated-amax recalibration reproduces the cold scale."""
    cfg, params, eng = dense_parts
    scfg = ServingConfig(slots=64, max_batch=4, kv_bits=kv_bits,
                         block_size=8, prefill_chunk=16)
    srv = AdaptiveServer(cfg, params, eng, scfg)
    assert srv.chunk_tokens == 16
    sched = ContinuousScheduler(srv, quantum=2)
    rng = np.random.default_rng(41)
    reqs = [Request(tokens=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new=mn)
            for n, mn in [(40, 5), (33, 4), (6, 3)]]   # 2 chunked, 1 short
    for r in reqs:
        sched.submit(r)
    sched.admit()
    assert len(sched._chunk_state) == 2          # long prompts mid-admission
    results = sched.run()
    assert not sched._chunk_state
    for req, res in zip(reqs, results):
        assert res["tokens"] == _solo_tokens(dense_parts, req, kv_bits)


def test_chunked_prefill_interleaves_decode(dense_parts):
    """While a long prompt chunks in, already-live rows keep emitting: the
    short request completes before the chunked one's admission finishes —
    the admission-wave stall the feature removes."""
    cfg, params, eng = dense_parts
    scfg = ServingConfig(slots=96, max_batch=4, block_size=8,
                         prefill_chunk=16)
    srv = AdaptiveServer(cfg, params, eng, scfg)
    sched = ContinuousScheduler(srv, quantum=2)
    rng = np.random.default_rng(7)
    short = Request(tokens=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new=4)
    long = Request(tokens=rng.integers(0, cfg.vocab, 80).astype(np.int32),
                   max_new=4)
    sched.submit(short)
    sched.submit(long)
    sched.step()                 # both admitted: short live, long chunk 1/5
    assert sched._chunk_state and sched.live_rows == 1
    while sched._chunk_state:
        sched.step()
    done = [rid for rid, _ in sched.poll_completed()]
    assert 0 in done             # short finished while long was still chunking
    results = sched.run()
    assert len(results[1]["tokens"]) == long.max_new
    assert results[1]["tokens"] == _solo_tokens(dense_parts, long, slots=96)
