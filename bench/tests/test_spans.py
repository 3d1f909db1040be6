"""The program-span reductions on a hand-built trace, and on the trace
recorded on a TPU v5e (which predates the program's spans)."""
import gzip
from pathlib import Path

import pytest

from bench import spans, xplane

US = 1_000_000          # picoseconds in a microsecond


def _events(rows):
    return "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {s * US} "
        f"duration_ps: {(e - s) * US} }}" for m, s, e in rows)


def _metadata(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in enumerate(names, 1))


HOST = ["bench.trace", "serve.submit", "serve.step", "serve.flush",
        "serve.flush.wait"]
# times in microseconds; the window is [10, 210)
HOST_ROWS = [(1, 10, 210),
             (2, 0, 12),         # starts before the window
             (3, 15, 110),       # a round: flush, and the wait inside it
             (4, 65, 105),
             (5, 68, 102),
             (2, 198, 201),      # beside the next round, inside a gap
             (3, 201, 215)]
# device busy [10,20) [40,70) [100,160) [190,200) [205,210): gaps
# [20,40) under the round alone, [70,100) under the wait, [160,190) under
# no span, [200,205) under the submit for 1 and the round for 4 (the
# whole gap goes to the round)
OPS = [(1, 10, 20), (1, 40, 70), (1, 100, 160), (1, 190, 200),
       (1, 205, 210)]
XSPACE = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {_events(HOST_ROWS)} }}
  {_metadata(HOST)} }}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {_events(OPS)} }}
  {_metadata(["%fusion.1 = f32[8] fusion(%p)"])} }}
"""


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(XSPACE)


def test_program_spans_count_what_starts_in_the_window(pd):
    got = spans.program_spans(pd)
    assert set(got) == {"serve.submit", "serve.step", "serve.flush",
                        "serve.flush.wait"}
    assert got["serve.step"]["n"] == 2
    assert got["serve.step"]["s"] == pytest.approx((95 + 14) * 1e-6)
    assert got["serve.submit"] == {"s": pytest.approx(3e-6), "n": 1}
    assert got["serve.flush"] == {"s": pytest.approx(40e-6), "n": 1}
    assert got["serve.flush.wait"] == {"s": pytest.approx(34e-6), "n": 1}


def test_idle_goes_to_the_innermost_span_covering_most(pd):
    got = spans.idle_by_span(pd)
    assert got == {"serve.step": pytest.approx(25e-6),
                   "serve.flush.wait": pytest.approx(30e-6),
                   spans.OUTSIDE: pytest.approx(30e-6)}
    red = xplane.reduce(pd)
    assert sum(got.values()) == pytest.approx(red["window_s"]
                                              - red["busy_s"])


def test_breakdown_names_each_gap_by_the_innermost_span_covering_most(pd):
    gaps = xplane.reduce(pd)["idle_gaps"]
    assert [n for n, _ in gaps] == ["serve.flush.wait", "no host span",
                                    "serve.step", "serve.step"]
    assert [t for _, t in gaps] == pytest.approx([30e-6, 30e-6, 20e-6,
                                                  5e-6])


def test_recorded_trace_has_no_program_spans_and_all_idle_outside():
    from jax.profiler import ProfileData
    path = Path(__file__).parent / "data" / "tiny.xplane.pb.gz"
    pd = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    red = xplane.reduce(pd)
    assert spans.program_spans(pd) == {}
    got = spans.idle_by_span(pd)
    assert list(got) == [spans.OUTSIDE]
    assert got[spans.OUTSIDE] == pytest.approx(red["window_s"]
                                               - red["busy_s"], rel=1e-9)


def test_without_a_window_or_a_device_nothing_is_read(pd):
    assert spans.program_spans(pd, window_span="bench.none") == {}
    assert spans.idle_by_span(pd, window_span="bench.none") == {}
