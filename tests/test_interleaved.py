"""granite-4.0-h-micro's interleaved stack — Mamba-2 layers and NoPE GQA
layers by a per-layer pattern — at a tiny size on the CPU, against the
plain reference ``bench/arch/mamba2_hybrid.py`` (f32, the SSD's quadratic
form) on seeded random weights.

Tolerance: the program runs in f32 here with an f32 KV pool, and the
reference is called with f32 keys and values to match, so the two differ
only by the order of f32 arithmetic (chunked scan or recurrent step against
the quadratic form) and by the few activations that land on the other side
of a 16-bit grid step because of it: 7.9e-5 (prefill) and 9.5e-5 (decode)
at most on logits of magnitude ~0.7. ``TOL`` allows five times that. A state
stored in bf16 moves decode logits by 2.4e-3 here and a dropped D skip by
0.8; each must fail the same comparison by twice ``TOL``.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from repro.configs import get_config, get_smoke
from repro.core.engine import AdaptiveEngine, QuantIndex
from repro.core.profiles import paper_profiles
from repro.models import ssm as S
from repro.models import transformer as T
from repro.serving.engine import AdaptiveServer, Request, ServingConfig
from repro.serving.scheduler import ContinuousScheduler

REF = spec.arch("mamba2_hybrid")
CFG = get_smoke("granite-4.0-h-micro")
MODEL = {
    "num_hidden_layers": 4, "layer_types": list(CFG.layer_types),
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "shared_intermediate_size": 128, "vocab_size": 512,
    "mamba_expand": 2, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 16, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "normalization_function": "rmsnorm", "tie_word_embeddings": True,
    "attention_bias": False}
SEED = 2**33 + 11
TOL = 5e-4
LENS = (5, 17, 30)


@pytest.fixture(scope="module")
def parts():
    params = REF.make_weights(MODEL, SEED, jnp.float32)
    names = T.quant_layer_names(CFG)
    eng = AdaptiveEngine(tuple(paper_profiles(names, inner_layers=[])),
                         QuantIndex(names), None)
    ref = REF.make_forward(REF.sizes(MODEL), a_bits=16, w_bits=8,
                           kv_bits=32)
    return params, eng, ref


def _prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(0, CFG.vocab, n).astype(np.int32) for n in LENS]


def test_weights_have_the_programs_layout(parts):
    params, _, _ = parts
    want = jax.eval_shape(lambda k: T.init_params(CFG, k),
                          jax.random.PRNGKey(0))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), params)
            == jax.tree.map(lambda a: (a.shape, a.dtype), want))
    assert params["layers"]["attention"]["qkv"]["w"].shape[0] == 1
    assert params["layers"]["mamba"]["ssm"]["A_log"].shape[0] == 3


def test_ragged_prefill_matches_reference(parts):
    params, eng, ref = parts
    prompts = _prompts()
    s = max(LENS)
    toks = np.zeros((len(LENS), s), np.int32)
    for i, p in enumerate(prompts):
        toks[i, s - len(p):] = p
    logits, caches = T.prefill(
        params, CFG, eng.table[0],
        {"tokens": jnp.asarray(toks), "prompt_len": jnp.asarray(LENS)},
        slots=48, kv_bits=32)
    assert caches["kv"].k.shape[0] == 1 and caches["ssm"].h.shape[0] == 3
    for i, p in enumerate(prompts):
        want = np.asarray(ref(params, jnp.asarray(p)))[-1]
        assert np.abs(np.asarray(logits[i]) - want).max() <= TOL


def _serve(params, eng, backend="auto", kv_bits=32, quantum=2):
    """Serve the ragged prompts through ContinuousScheduler on the paged
    pool; record the logits of each segment's first step (the decode step
    the segment runs first, on the pool state the scheduler hands it)."""
    srv = AdaptiveServer(CFG, params, eng,
                         ServingConfig(slots=64, max_batch=4, block_size=8,
                                       kv_bits=kv_bits, pool_blocks=24,
                                       paged_backend=backend))
    table0 = jnp.asarray(eng.table[0])
    probe = jax.jit(lambda p, pq, tok, pos, caches: T.decode_step(
        T.overlay_params(p, T.decode_image(pq, 0)), CFG, table0,
        tok[:, None], pos, caches, paged_backend=srv.paged_backend)[0])
    seg, seen = srv._segment, []

    def segment(schedule, tok, pos, caches, remaining, fault_step):
        seen.append((list(sched.slot_req), np.asarray(pos),
                     np.asarray(remaining),
                     np.asarray(probe(srv.params, srv._prequant, tok, pos,
                                      caches))))
        return seg(schedule, tok, pos, caches, remaining, fault_step)

    srv._segment = segment
    sched = ContinuousScheduler(srv, quantum=quantum)
    assert sched.paged
    rids = [sched.submit(Request(tokens=p, max_new=12)) for p in _prompts()]
    res = sched.run()
    served = {rid: np.asarray(res[rid]["tokens"]) for rid in rids}
    return served, seen, sched


def _widest(params, ref, served, seen, prompts):
    """Largest |program − reference| over every recorded decode step's
    logits, and the largest gap of a served token below the reference's
    best."""
    rid_of = {}
    for rid, p in zip(sorted(served), prompts):
        rid_of[rid] = np.asarray(ref(params, jnp.asarray(
            np.concatenate([p, served[rid]]))))
    diff = gap = 0.0
    n = 0
    for slot_req, pos, rem, logits in seen:
        for s, rid in enumerate(slot_req):
            if rid is None or rem[s] <= 0:
                continue
            diff = max(diff, np.abs(logits[s] - rid_of[rid][pos[s]]).max())
            n += 1
    for rid, p in zip(sorted(served), prompts):
        r = rid_of[rid][len(p) - 1:len(p) - 1 + len(served[rid])]
        gap = max(gap, (r.max(-1) - r[np.arange(len(r)),
                                      served[rid]]).max())
    return diff, gap, n


def _bf16_state(recur):
    def f(*a):
        y, h = recur(*a)
        return y, h.astype(jnp.bfloat16).astype(jnp.float32)
    return f


def _no_d_skip(recur):
    def f(h, x, dt, a, b, c, d):
        return recur(h, x, dt, a, b, c, jnp.zeros_like(d))
    return f


@pytest.mark.parametrize("fault", [None, "bf16_state", "no_d_skip"])
def test_served_decode_matches_reference(parts, monkeypatch, fault):
    """Prefill then cached decode through the scheduler's paged pool, ragged
    prompts: every recorded step's logits lie within TOL of the reference's
    full forward over prompt + served tokens, and every served token is the
    reference's best within TOL — and a stored bf16 state or a dropped D
    skip breaks the logits comparison."""
    params, eng, ref = parts
    if fault is not None:
        wrap = {"bf16_state": _bf16_state, "no_d_skip": _no_d_skip}[fault]
        monkeypatch.setattr(S, "ssm_recur", wrap(S.ssm_recur))
    served, seen, sched = _serve(params, eng)
    assert all(len(t) == 12 for t in served.values())
    diff, gap, n = _widest(params, ref, served, seen, _prompts())
    assert n >= 15
    if fault is None:
        assert diff <= TOL and gap <= TOL, (diff, gap)
        st = sched.paged_stats()
        assert st["ssm_state_bytes"] == T.cache_bytes(sched._caches["ssm"])
        assert st["ssm_state_bytes"] == 3 * 4 * 4 * (8 * 16 * 16 + 3 * 160)
    else:
        assert diff > 2 * TOL, (fault, diff)


def test_kernel_backend_serves_what_the_xla_path_serves(parts):
    """``paged_backend="pallas"``: the paged-attention and state-update
    kernels (interpret mode here) serve the tokens of the XLA path, with
    live rows' decode logits within 1e-4 of it: both read the same bf16
    keys and values and differ by f32 summation order (the kernel's online
    softmax over pages, its lane sums over the state), ~4e-5 here."""
    params, eng, _ = parts
    got, seen_k, _ = _serve(params, eng, backend="pallas", kv_bits=16)
    want, seen_x, _ = _serve(params, eng, backend="gather", kv_bits=16)
    assert all((got[r] == want[r]).all() for r in want)
    assert len(seen_k) == len(seen_x)
    for (req, _, rem, a), (_, _, _, b) in zip(seen_k, seen_x):
        live = [s for s, rid in enumerate(req) if rid is not None
                and rem[s] > 0]
        assert np.abs(a[live] - b[live]).max() <= 1e-4


def _file_model():
    conf = json.loads((spec.BENCH / "configs"
                       / "granite-4.0-h-micro.json").read_text())
    return conf["model"]


def test_published_file_runs_the_registry_config():
    model = _file_model()
    REF.check_config(get_config("granite-4.0-h-micro"), model)
    sz = REF.sizes(model)
    assert (sz["L"], sz["L_attn"], sz["nh"], sz["P"], sz["N"], sz["cd"]) \
        == (40, 4, 64, 64, 128, 4352)


@pytest.mark.parametrize("change", [
    {"layer_types": ["attention"] + ["mamba"] * 39},
    {"position_embedding_type": "rope"},
    {"mamba_d_state": 64},
    {"shared_intermediate_size": 4096},
    {"mamba_n_groups": 2},
])
def test_check_config_refuses_a_file_that_disagrees(change):
    with pytest.raises(ValueError):
        REF.check_config(get_config("granite-4.0-h-micro"),
                         _file_model() | change)


@pytest.mark.parametrize("head_bits", [8, 4])
def test_image_step_reads_the_head_at_its_own_bits(parts, head_bits):
    """The decode step under the weight image takes the tied head from the
    embedding's image, or from an image of its own where a profile gives
    the head other bits than the embedding: either way it computes what
    the master path (weights fake-quanted in the step) computes."""
    params, _, _ = parts
    names = T.quant_layer_names(CFG)
    profs = paper_profiles(names, inner_layers=[])
    prof = profs[0].override("head", {"lm_head": (16, head_bits)})
    eng = AdaptiveEngine((prof,) + tuple(profs[1:]), QuantIndex(names), None)
    table = jnp.asarray(eng.table)
    pq = T.prequant_decode_weights(params, CFG, table)
    assert ("lm_head" in pq["images"]) == (head_bits != 8)
    caches = T.init_caches(CFG, 2, 16, kv_bits=32)
    tok, pos = jnp.asarray([[3], [7]], jnp.int32), jnp.zeros((2,), jnp.int32)
    images = dict(pq["images"])
    lay = images.pop("layers")
    img = pq["image_of"][0]
    got, _ = T._decode_interleaved(
        T.overlay_params(params, jax.tree.map(lambda a: a[img], images)),
        CFG, table[0], tok, pos, caches, "gather", None,
        layer_images=(lay, img))
    want, _ = T.decode_step(params, CFG, table[0], tok, pos, caches)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
