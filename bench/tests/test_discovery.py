"""A configuration, a traffic mix, a cell and a per-layer metric added as
new files are found by name, and no existing file changes."""
import hashlib
import json
import shutil

from bench import spec


def _digest(tree):
    return {p.relative_to(tree): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(tree.rglob("*")) if p.is_file()}


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    bj = spec.benchmark()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    before = _digest(bench)

    conf = json.loads((bench / "configs" / "granite-3-2b.json").read_text())
    conf["registry"] = "granite-3-2b"
    (bench / "configs" / "new-model.json").write_text(json.dumps(conf))
    mix = {"kind": "offline_batch", "queue_rows": 3, "cycle": 8,
           "prompt": {"dist": "uniform", "lo": 10, "hi": 20},
           "output": {"dist": "uniform", "lo": 5, "hi": 9}}
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (bench / "cells" / "new-model.new-mix.json").write_text(json.dumps(
        {"serving": {"max_batch": 2}, "check": {"max_gap": 0.5}}))
    (bench / "metrics" / "new_metric.mix.py").write_text(
        'UNIT, LAYER, MOVES = "ms", "scheduler", "output_tok_s"\n\n'
        'def read(rec):\n    return rec["x"] * 2\n')
    bj["configs"].append({"name": "new-model", "source": "x",
                          "file": "bench/configs/new-model.json",
                          "reduced": [], "why": "x"})
    bj["workloads"].append({"name": "new-model.new-mix",
                            "config": "new-model", "traffic": "new-mix",
                            "chips": 1, "why": "x"})
    bj["end_to_end"][0]["workloads"].append("new-model.new-mix")
    bj["per_layer"].append({"name": "new_metric.mix", "unit": "ms",
                            "better": "lower", "source": "host_clock",
                            "layer": "scheduler", "moves": "output_tok_s",
                            "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))

    w = spec.workload("new-model.new-mix", root=tmp_path, bench=bench)
    assert w["traffic"]["queue_rows"] == 3
    assert w["cell"]["check"]["max_gap"] == 0.5
    assert w["config"]["registry"] == "granite-3-2b"
    assert [m["name"] for m in w["per_layer"]] == ["new_metric.mix"]
    assert {m["name"] for m in w["end_to_end"]} == {"output_tok_s",
                                                     "setup_s"}
    reader = spec.metric_reader("new_metric.mix", bench)
    assert (reader.UNIT, reader.MOVES) == ("ms", "output_tok_s")
    assert reader.read({"x": 4}) == 8
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_declared_metric_has_a_reader():
    bj = spec.benchmark()
    for m in bj["per_layer"]:
        r = spec.metric_reader(m["name"])
        assert (r.UNIT, r.LAYER, r.MOVES) == (m["unit"], m["layer"],
                                              m["moves"]), m["name"]
    for w in bj["workloads"]:
        got = spec.workload(w["name"])
        assert got["per_layer"], w["name"]


def test_unknown_device_has_no_peaks():
    import pytest
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
