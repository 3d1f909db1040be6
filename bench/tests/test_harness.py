"""A whole run of a tiny cell on the CPU, past the look for a chip: it
comes out correct, also with QKV bias, M-RoPE and heads of 128; it comes
out not correct when the timed path is broken underneath (a served token
altered where the decode segment produces it; a decode segment that hands
back the KV pool it was given, its writes lost), and when the control
(the reference one activation precision lower) stands in for the
program."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.tests import tiny

SEED = 2**33 + 12345


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    return root, tiny.make_tree(root, max_gap=0.005)


def _run(tree, seed=SEED, **kw):
    root, name = tree
    return run.run_cell(name, seed, 2.0, False, require_chip=False,
                        root=root, bench=root / "bench", log=lambda m: None,
                        **kw)


def test_sound_run_is_correct(tree):
    res = _run(tree)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert list(res)[-1] == "compared"
    json.dumps(res)


@pytest.fixture(scope="module")
def qwen_tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-qwen")
    return root, tiny.make_tree(root, max_gap=0.005,
                                base="qwen2-vl-2b-text")


def test_sound_run_with_qkv_bias_and_mrope_is_correct(qwen_tree):
    res = _run(qwen_tree)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_control_with_qkv_bias_and_mrope_comes_out_not_correct(qwen_tree):
    res = _run(qwen_tree, seed=SEED + 1, control=True)
    assert res["program"]["correct"], res["program"]
    assert not res["correct"], res["compared"]


def _wrap(srv, broken):
    """Replace the server's decode segment by ``broken(segment, *args)``."""
    seg = srv._segment

    class Broken:
        def __call__(self, *a):
            return broken(seg, *a)

        def __getattr__(self, k):
            return getattr(seg, k)

    srv._segment = Broken()


def test_token_altered_in_the_segment_is_caught(tree):
    def altered(seg, *a):
        toks, ok, tok, pos, caches = seg(*a)
        toks = jnp.where(toks >= 0, (toks + 1) % 512, toks)
        return toks, ok, tok, pos, caches

    res = _run(tree, hook=lambda srv: _wrap(srv, altered))
    assert not res["correct"], res["compared"]
    assert res["compared"]["widest_gap"]["value"] > 0.005


def test_segment_returning_its_state_unchanged_is_caught(tree):
    def unchanged(seg, *a):
        # the KV pool as given, copied before the segment can donate it
        before = jax.tree.map(jnp.copy, a[3])
        toks, ok, tok, pos, _ = seg(*a)
        return toks, ok, tok, pos, before

    res = _run(tree, hook=lambda srv: _wrap(srv, unchanged))
    assert not res["correct"], res["compared"]
    assert res["compared"]["widest_gap"]["value"] > 0.005


@pytest.mark.parametrize("seed", [SEED + 1, SEED + 2, SEED + 3])
def test_control_comes_out_not_correct(tree, seed):
    res = _run(tree, seed=seed, control=True)
    assert res["program"]["correct"], res["program"]
    assert not res["correct"], res["compared"]
    c = res["compared"]["widest_gap"]
    assert c["value"] > c["limit"]


def test_traced_run_reports_per_layer_metrics_it_can_read(tree):
    root, name = tree
    res = run.run_cell(name, SEED, 2.0, True, require_chip=False, root=root,
                       bench=root / "bench", log=lambda m: None)
    # off the chip the trace has no device plane: only host-side readers
    # find something, and none reports a zero share of a peak
    assert "batch_occupancy.decode" in res["metrics"]
    assert "paged_attn_roofline.decode" not in res["metrics"]
    assert res["correct"]


def test_no_chip_exits_nonzero_without_a_result(capsys):
    rc = run.main(["--workload", "granite-3-2b.batch-decode", "--seed", "1",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
