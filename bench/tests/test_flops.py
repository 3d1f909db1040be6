"""Operation and byte counts at two geometries, read from the
configuration files: granite-3-2b's (8 KV heads of 64) and Qwen2-VL-2B's
text backbone (2 KV heads of 128, a 151,936-row tied head, QKV bias).
Both are dense decoders, so the model-level counts are
``arch/dense_decoder.py``'s."""
import json

import pytest

from bench import flops, spec
from bench.spec import BENCH


DD = spec.arch("dense_decoder")


def _sz(name):
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert spec.arch(conf["arch"]) is DD
    return DD.sizes(conf["model"])


def test_granite_parameter_count_matches_the_model():
    sz = _sz("granite-3-2b")
    # per layer: qkv 2048x3072, out 2048x2048, w_in 2048x16384, w_out 8192x2048
    layer = 2048 * 3072 + 2048 * 2048 + 2048 * 16384 + 8192 * 2048
    assert DD.matmul_params(sz) == 40 * layer + 2048 * 49155
    # with the embedding and the norms this is the program's 2,533,531,648
    norms = 40 * 2 * 2048 + 2048
    assert DD.matmul_params(sz) + norms == 2_533_531_648


def test_qwen_parameter_count_matches_the_model():
    sz = _sz("qwen2-vl-2b-text")
    assert (sz["hd"], sz["K"], sz["qkv_bias"], sz["L_attn"]) == (128, 2,
                                                                True, 28)
    layer = 1536 * 2048 + 1536 * 1536 + 1536 * 17920 + 8960 * 1536
    assert DD.matmul_params(sz) == 28 * layer + 1536 * 151936
    bias, norms = 28 * 2048, 28 * 2 * 1536 + 1536
    assert DD.matmul_params(sz) + bias + norms == 1_543_714_304


@pytest.mark.parametrize("name,kv_token", [("granite-3-2b", 81920),
                                           ("qwen2-vl-2b-text", 28672)])
def test_kv_bytes_per_token(name, kv_token):
    assert DD.kv_bytes_per_token(_sz(name), 16) == kv_token


@pytest.mark.parametrize("name", ["granite-3-2b", "qwen2-vl-2b-text"])
def test_paged_kernel_call_counts(name):
    sz = _sz(name)
    H, K, hd = sz["H"], sz["K"], sz["hd"]
    ops, byts = flops.paged_kernel_call(sz, 1000, 16)
    assert ops == 4 * 1000 * H * hd
    assert byts == 1000 * (2 * K * hd * 2 + 4) + H * hd * 6
    # one layer's share of the KV bytes of a token, plus its index
    assert byts - H * hd * 6 == 1000 * (
        DD.kv_bytes_per_token(sz, 16) // sz["L"] + 4)


@pytest.mark.parametrize("name", ["granite-3-2b", "qwen2-vl-2b-text"])
def test_decode_and_prefill_flops(name):
    sz = _sz(name)
    p = DD.matmul_params(sz)
    assert DD.decode_token_flops(sz, 0) == 2 * p
    # one query over 10 keys in every layer: q.k and p.v
    assert DD.decode_token_flops(sz, 10) == 2 * p + 4 * 10 * sz["H"] \
        * sz["hd"] * sz["L"]
    # a prompt of n tokens is n decode steps over 1..n keys
    n = 37
    assert DD.prefill_flops(sz, n) == sum(
        DD.decode_token_flops(sz, i + 1) for i in range(n))


def test_least_time_takes_the_binding_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time_s(1000, 1, peak) == 10.0
    assert flops.least_time_s(1, 1000, peak) == 100.0
