"""granite-4.0-h-micro in the harness: ``arch/mamba2_hybrid.py``'s counts
worked out by hand, the new metric reader, and whole runs of a tiny
interleaved cell on the CPU (the registry's model, 4 layers — Mamba,
attention, Mamba, Mamba — at width 64 with the published Mamba-2 head
geometry: 2 heads of 64, state 128). A sound run comes out correct; the A8
control and a token altered in the decode segment do not."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run, spec
from bench.spec import BENCH
from bench.tests import tiny
from repro.configs import get_config
from repro.models import transformer as T

MH = spec.arch("mamba2_hybrid")
NAME = "granite-4.0-h-micro"
SEED = 2**33 + 54321
LAYERS = ["mamba", "attention", "mamba", "mamba"]
TINY_MODEL = {
    "num_hidden_layers": 4, "layer_types": LAYERS, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "shared_intermediate_size": 128,
    "vocab_size": 512, "mamba_expand": 2, "mamba_n_heads": 2,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_chunk_size": 256, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "normalization_function": "rmsnorm", "tie_word_embeddings": True,
    "attention_bias": False}
TINY_OVERRIDES = {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv": 2,
                  "d_ff": 128, "vocab": 512, "head_dim": 16, "remat": False,
                  "attn_block_k": 32, "layer_types": LAYERS}


def _sz():
    conf = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
    assert spec.arch(conf["arch"]) is MH
    return MH.sizes(conf["model"])


def test_published_keys_at_the_top_level_are_the_ones_run():
    """The file carries the source's keys at its top level, as run, and the
    harness reads them under ``"model"``: the two copies are one model."""
    conf = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
    assert {k: conf[k] for k in conf["model"]} == conf["model"]
    assert set(conf["reduced"]) == set(conf["published"])
    assert all(conf[k] != v for k, v in conf["published"].items())


def test_parameter_count_matches_the_model():
    sz = _sz()
    attn = 2048 * 3072 + 2048 * 2048           # qkv, out
    mamba = 2048 * 8512 + 4096 * 2048          # in_proj [z|xBC|dt], out
    mlp = 2048 * 16384 + 8192 * 2048
    assert MH.matmul_params(sz) == (4 * attn + 36 * mamba + 40 * mlp
                                    + 2048 * 100352) == 3_190_292_480
    # with norms, conv, A_log, D, dt_bias and the gated norm: the program's
    norms = 40 * 2 * 2048 + 2048
    extra = 36 * (4 * 4352 + 4352 + 3 * 64 + 4096)
    params = jax.eval_shape(lambda k: T.init_params(get_config(NAME), k),
                            jax.random.PRNGKey(0))
    assert T.param_count(params) == 3_190_292_480 + norms + extra


def test_ssm_decode_call_counts():
    ops, byts = MH.ssm_decode_call(_sz())
    # per head: decay, outer product, sum (3 P N), h.C (2 P N), D skip (2 P)
    assert ops == 64 * (5 * 64 * 128 + 2 * 64) == 2_629_632
    # f32 state read and written, x, dt, B, C (one group) and y
    assert byts == 4 * (2 * 64 * 64 * 128 + 64 * 64 + 64 + 2 * 128
                        + 64 * 64) == 4_228_352


def test_decode_and_prefill_flops():
    sz = _sz()
    p = MH.matmul_params(sz)
    ssm = 2_629_632 + 2 * 4 * 4352              # state update + conv window
    assert MH.decode_token_flops(sz, 0) == 2 * p + 36 * ssm
    # attention reads ctx keys in the 4 attention layers only
    assert MH.decode_token_flops(sz, 10) - MH.decode_token_flops(sz, 0) \
        == 4 * 10 * 32 * 64 * 4
    n = 37
    assert MH.prefill_flops(sz, n) == sum(
        MH.decode_token_flops(sz, i + 1) for i in range(n))


def _record(kernel_s):
    sz = _sz()
    return {"trace": {"kernels": {"ssm_decode_pallas": {"s": kernel_s,
                                                        "calls": 36}}},
            "arch": MH, "sz": sz, "quantum": 2, "traced_steps": (0, 2),
            "segments": [(0, [(100, 2), (300, 1)]), (1, [(50, 2)]),
                         (2, [(60, 2)])],
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_ssm_roofline_reader():
    reader = spec.metric_reader("ssm_decode_roofline.decode")
    _, byts = MH.ssm_decode_call(_sz())
    # live rows per step: 2, 1 (segment 0), 1, 1 (segment 1); step 2 is
    # outside the traced steps; 36 layers, bandwidth-bound
    need = 36 * (2 + 1 + 1 + 1) * byts / 819e9
    assert reader.read(_record(2 * need)) == pytest.approx(50.0)
    assert reader.read(_record(0.0)) is None
    dense = _record(1.0) | {"arch": spec.arch("dense_decoder")}
    assert reader.read(dense) is None
    assert reader.read(_record(1.0) | {"trace": {}}) is None


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-hybrid")
    tiny.TINY[NAME] = (TINY_MODEL, TINY_OVERRIDES)
    try:
        name = tiny.make_tree(root, max_gap=0.005, base=NAME)
    finally:
        del tiny.TINY[NAME]
    cell = root / "bench" / "cells" / f"{name}.json"
    c = json.loads(cell.read_text())
    c["kernels"] = ["paged_attention_pallas", "ssm_decode_pallas"]
    cell.write_text(json.dumps(c))
    return root, name


def _run(tree, seed=SEED, trace=False, **kw):
    root, name = tree
    return run.run_cell(name, seed, 2.0, trace, require_chip=False,
                        root=root, bench=root / "bench", log=lambda m: None,
                        **kw)


def test_sound_run_is_correct(tree):
    res = _run(tree)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_control_comes_out_not_correct(tree):
    res = _run(tree, seed=SEED + 1, control=True)
    assert res["program"]["correct"], res["program"]
    assert not res["correct"], res["compared"]


def test_token_altered_in_the_segment_is_caught(tree):
    def hook(srv):
        seg = srv._segment

        def altered(*a):
            toks, ok, tok, pos, caches = seg(*a)
            toks = jnp.where(toks >= 0, (toks + 1) % 512, toks)
            return toks, ok, tok, pos, caches

        srv._segment = altered

    res = _run(tree, hook=hook)
    assert not res["correct"], res["compared"]


def test_traced_run_reports_the_host_side_metrics(tree):
    res = _run(tree, trace=True)
    # off the chip the trace has no device plane: the device readers find
    # nothing (test_ssm_roofline_reader reads a device record)
    assert "batch_occupancy.decode" in res["metrics"]
    assert "ssm_decode_roofline.decode" not in res["metrics"]
    assert res["correct"]
