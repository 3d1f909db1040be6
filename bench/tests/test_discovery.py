"""A configuration, a traffic mix, a cell, a per-layer metric and an
architecture added as new files are found by name, and no existing file
changes."""
import dataclasses
import hashlib
import json
import shutil

import pytest

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
    conf["arch"] = "toy_arch"
    (bench / "configs" / "new-model.json").write_text(json.dumps(conf))
    (bench / "arch" / "toy_arch.py").write_text(
        'def sizes(model):\n    return {"arch": "toy_arch", "L": 1, '
        '"L_attn": 0}\n')
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
    assert w["arch"].sizes({}) == {"arch": "toy_arch", "L": 1, "L_attn": 0}
    assert w["arch"] is spec.arch("toy_arch", bench)
    assert [m["name"] for m in w["per_layer"]] == ["new_metric.mix"]
    assert {m["name"] for m in w["end_to_end"]} == {"output_tok_s",
                                                     "setup_s"}
    reader = spec.metric_reader("new_metric.mix", bench)
    assert (reader.UNIT, reader.MOVES) == ("ms", "output_tok_s")
    assert reader.read({"x": 4}) == 8
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_architecture_names_the_known_ones(tmp_path):
    with pytest.raises(KeyError, match=r"'no_such_arch'.*dense_decoder"):
        spec.arch("no_such_arch")
    bench = tmp_path / "bench"
    (bench / "arch").mkdir(parents=True)
    for name in ("dense_decoder", "other"):
        (bench / "arch" / f"{name}.py").write_text("")
    with pytest.raises(KeyError, match=r"\['dense_decoder', 'other'\]"):
        spec.arch(None, bench)


def test_qwen2_vl_text_backbone_is_what_the_registry_runs():
    from repro.configs import get_config
    conf = json.loads((spec.BENCH / "configs" / "qwen2-vl-2b-text.json")
                      .read_text())
    arch = spec.arch(conf["arch"])
    cfg = dataclasses.replace(get_config(conf["registry"]),
                              **conf["overrides"])
    arch.check_config(cfg, conf["model"])
    assert arch.sizes(conf["model"])["L_attn"] == 28
    # the registry's own model carries the vision frontend: not this file's
    with pytest.raises(ValueError, match="frontend"):
        arch.check_config(get_config(conf["registry"]), conf["model"])
    # nor does a registry model of other sizes pass
    with pytest.raises(ValueError):
        arch.check_config(get_config("granite-3-2b"), conf["model"])


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
