"""A benchmark tree with one tiny cell, for running the harness on a CPU.

The cell runs a registry model (granite-3-2b by default; Qwen2-VL-2B's text
backbone for QKV bias, M-RoPE and heads of 128) with its sizes overridden
to a 2-layer, 64-wide model, so a whole run (set-up, window, reference
check) takes seconds.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 10000.0, "hidden_act": "silu",
    "tie_word_embeddings": True, "attention_bias": False,
    "rms_norm_eps": 1e-06,
}
TINY_OVERRIDES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv": 2,
                  "d_ff": 128, "vocab": 512, "head_dim": 16, "remat": False,
                  "attn_block_k": 32}
# QKV bias, M-RoPE at text positions (sections of Qwen2-VL, so heads of 128)
TINY_QWEN_MODEL = TINY_MODEL | {
    "head_dim": 128, "rope_theta": 1000000.0, "attention_bias": True,
    "rope_scaling": {"type": "mrope", "mrope_section": [16, 24, 24]}}
TINY_QWEN_OVERRIDES = TINY_OVERRIDES | {"head_dim": 128, "frontend": None,
                                        "n_patches": 0}
TINY = {"granite-3-2b": (TINY_MODEL, TINY_OVERRIDES),
        "qwen2-vl-2b-text": (TINY_QWEN_MODEL, TINY_QWEN_OVERRIDES)}


def make_tree(root: Path, *, max_gap: float = 1.0,
              base: str = "granite-3-2b") -> str:
    """Copy ``bench/`` under ``root`` with a BENCHMARK.json holding one tiny
    cell of configuration ``base``; returns the cell's name."""
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    conf = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    model, overrides = TINY[base]
    conf.update(model=model, overrides=overrides, reduced=[], published={})
    (bench / "configs" / "tiny.json").write_text(json.dumps(conf))
    mix = {"kind": "offline_batch",
           "prompt": {"dist": "uniform", "lo": 8, "hi": 24},
           "output": {"dist": "uniform", "lo": 12, "hi": 40},
           "queue_rows": 2, "cycle": 16}
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    cell = {"serving": {"max_batch": 4, "slots": 80, "pool_blocks": 20,
                        "block_size": 16, "quantum": 4,
                        "prefix_cache": True},
            "kernels": ["paged_attention_pallas"],
            "check": {"max_gap": max_gap, "min_tokens": 60,
                      "max_requests": 4, "pad_to": 80}}
    name = "tiny.tiny-mix"
    (bench / "cells" / f"{name}.json").write_text(json.dumps(cell))
    bj = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bj["configs"] = [{"name": "tiny", "source": "test",
                      "file": "bench/configs/tiny.json", "reduced": [],
                      "why": "test"}]
    bj["workloads"] = [{"name": name, "config": "tiny",
                        "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for m in bj["end_to_end"] + bj["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bj))
    return name
