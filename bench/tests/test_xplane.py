"""The trace reduction on a small trace recorded on a TPU v5e (the tiny
cell: 2 layers, 4 rows, quantum 4, a fraction of a second)."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import xplane

DATA = Path(__file__).parent / "data"
PB = DATA / "tiny.xplane.pb.gz"


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(gzip.decompress(PB.read_bytes()))


@pytest.fixture(scope="module")
def red(pd):
    return xplane.reduce(pd, kernels=("paged_attention_pallas",))


def _window(pd):
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.trace":
                        return e.start_ns, e.start_ns + e.duration_ns


def test_busy_is_the_union_of_device_ops_in_the_window(pd, red):
    lo, hi = _window(pd)
    # an independent union: mark every nanosecond-bucket an op covers
    ivs = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
           for p in pd.planes if p.name.startswith("/device:TPU:")
           for line in p.lines if line.name == "XLA Ops"
           for e in line.events if lo <= e.start_ns < hi]
    edges = np.array(sorted({x for iv in ivs for x in iv}), dtype=np.int64)
    covered = np.zeros(len(edges) - 1, bool)
    for s, e in ivs:
        covered[np.searchsorted(edges, s):np.searchsorted(edges, e)] = True
    busy = float(np.sum(np.diff(edges)[covered])) / 1e9
    assert red["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"] == pytest.approx(
        (hi - lo) / 1e9)


def test_kernel_runs_once_per_layer_and_decode_step(red):
    seg = red["modules"]["jit_segment_fn"]
    kern = red["kernels"]["paged_attention_pallas"]
    layers, quantum = 2, 4
    assert seg["runs"] > 0
    assert kern["calls"] == seg["runs"] * quantum * layers
    assert 0 < kern["s"] < seg["s"]


def test_breakdown_lists_are_bounded_and_attributed(red):
    assert 0 < len(red["device_ops"]) <= 10
    times = [t for _, t in red["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert len(red["idle_gaps"]) <= 10
    assert all(n.startswith(("bench.", "serve.")) or n == "no host span"
               for n, _ in red["idle_gaps"])


def test_matches_the_reduction_recorded_with_the_trace(red):
    want = json.loads((DATA / "tiny.reduced.json").read_text())
    assert red == want
