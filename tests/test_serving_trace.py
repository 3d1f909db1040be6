"""The serving scheduler's profiler spans and KV counters.

* Under ``jax.profiler.trace`` every ``serve.*`` span is recorded and nests
  as the round does: ``serve.flush.wait`` inside ``serve.flush`` inside
  ``serve.step``, each dispatch inside the admission or the segment that
  made it.
* The spans change nothing the device sees: the tokens, the dispatch count
  of every executable and the trace count (none after the warm run) are
  the same with the profiler on and off.
* ``kv_blocks_reserved`` / ``kv_blocks_written`` / ``kv_blocks_table``
  equal a count worked out from the requests' lengths, the block size, the
  block table's length and the tokens each round delivered, in greedy and
  in speculative segments.
* The executables and the kernel keep the names the benchmark's trace
  reduction matches (``jit_segment_fn``, ``jit_admit_paged_fn``; the
  kernel's is pinned in ``test_tpu_compile.py``).
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.tracker import SchedulerAudit
from repro.configs import get_smoke
from repro.core.engine import AdaptiveEngine, QuantIndex
from repro.core.profiles import paper_profiles
from repro.models import transformer as T
from repro.serving.engine import AdaptiveServer, Request, ServingConfig
from repro.serving.scheduler import ContinuousScheduler

BS, QUANTUM, SLOTS = 8, 4, 64
# (prompt, max_new): more requests than rows, so retired rows refill; the
# max_new == 1 request completes at admission (a clear_rows dispatch)
LENGTHS = [(5, 1), (12, 7), (20, 13), (9, 21), (30, 6), (17, 11)]
SEGMENT_FN = {"greedy": "segment_fn", "spec": "segment_spec_fn"}


@pytest.fixture(scope="module")
def parts():
    cfg = get_smoke("granite-3-2b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    names = T.quant_layer_names(cfg)
    eng = AdaptiveEngine(tuple(paper_profiles(names, inner_layers=[])),
                         QuantIndex(names), None)
    return cfg, params, eng


def _server(parts, mode):
    cfg, params, eng = parts
    return AdaptiveServer(cfg, params, eng, ServingConfig(
        slots=SLOTS, max_batch=4, block_size=BS, speculate=mode == "spec",
        draft_k=2))


def _requests(cfg):
    rng = np.random.default_rng(7)
    return [Request(tokens=rng.integers(1, cfg.vocab, p).astype(np.int32),
                    max_new=m) for p, m in LENGTHS]


def _serve(srv, reqs, warm=True):
    """Drive a fresh scheduler step by step; on a ``warm`` server nothing
    may trace. Beside the scheduler's own
    counters, count the KV blocks from outside: a row the round's flush
    delivered segment tokens to (any beyond its admission token) held
    ``ceil((prompt + max_new) / BS)`` blocks in that segment, filled
    ``ceil((prompt + delivered) / BS)`` of them, and had a table of
    ``slots / BS`` logical blocks. Each round's flush lands at most one
    segment per row (greedy keeps one segment in flight, speculation
    none)."""
    sched = ContinuousScheduler(srv, quantum=QUANTUM)
    seen = [0] * len(reqs)
    reserved = written = table = 0
    with SchedulerAudit(sched, extra_names=["_clear"]) as audit:
        rids = [sched.submit(r) for r in reqs]
        more = True
        while more:
            more = sched.step()
            for i, (rid, r) in enumerate(zip(rids, reqs)):
                got = len(sched.results.get(rid, {"tokens": ()})["tokens"])
                if got > max(seen[i], 1):
                    held = -(-(len(r.tokens) + r.max_new) // BS)
                    reserved += held
                    written += min(held, -(-(len(r.tokens) + got) // BS))
                    table += -(-SLOTS // BS)
                seen[i] = got
        if warm:
            audit.assert_no_retrace()
        audit.assert_single_segment()
        calls = {n: audit.calls(n) for n in audit.names}
    stats = sched.paged_stats()
    return {"tokens": [sched.results[rid]["tokens"] for rid in rids],
            "calls": calls, "counted": (reserved, written, table),
            "counters": (stats["kv_blocks_reserved"],
                         stats["kv_blocks_written"],
                         stats["kv_blocks_table"])}


@pytest.fixture(scope="module", params=["greedy", "spec"])
def runs(request, parts, tmp_path_factory):
    """(mode, profiler off, profiler on, host spans of the traced run) on
    one server, after a run that warms every executable."""
    mode = request.param
    srv = _server(parts, mode)
    reqs = _requests(parts[0])
    _serve(srv, reqs, warm=False)
    off = _serve(srv, reqs)
    d = str(tmp_path_factory.mktemp(f"trace-{mode}"))
    with jax.profiler.trace(d):
        on = _serve(srv, reqs)
    return mode, off, on, _host_spans(d)


def _host_spans(trace_dir):
    """``serve.*`` host events, per thread: [(name, start, end)]."""
    from jax.profiler import ProfileData
    (pb,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events if e.name.startswith("serve.")]
            if ev:
                out.append(ev)
    return out


def _inside(span, parents):
    _, s, e = span
    return any(ps <= s and e <= pe for _, ps, pe in parents)


def test_spans_nest_and_leave_the_device_path_alone(runs):
    mode, off, on, threads = runs
    assert on["tokens"] == off["tokens"]
    assert [len(t) for t in on["tokens"]] == [m for _, m in LENGTHS]
    assert on["calls"] == off["calls"]
    assert on["calls"]["_segment"] > 0 and on["calls"]["_clear"] > 0

    assert len(threads) == 1              # the scheduler runs on one thread
    spans = threads[0]
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    fn = SEGMENT_FN[mode]
    assert set(by) == {"serve.step", "serve.submit", "serve.admit",
                       "serve.segment", "serve.flush", "serve.flush.wait",
                       f"serve.dispatch.{fn}", "serve.dispatch.admit_paged_fn",
                       "serve.dispatch.clear_rows_fn"}
    assert len(by["serve.submit"]) == len(LENGTHS)
    assert len(by[f"serve.dispatch.{fn}"]) == on["calls"]["_segment"]
    assert len(by["serve.dispatch.admit_paged_fn"]) == \
        on["calls"]["_admit_paged"]
    assert len(by["serve.dispatch.clear_rows_fn"]) == on["calls"]["_clear"]
    assert not any(_inside(s, by["serve.step"]) for s in by["serve.submit"])
    for name in ("serve.admit", "serve.segment", "serve.flush"):
        assert all(_inside(s, by["serve.step"]) for s in by[name]), name
    assert all(_inside(s, by["serve.flush"]) for s in by["serve.flush.wait"])
    rounds = by["serve.admit"] + by["serve.segment"]
    for name in by:
        if name.startswith("serve.dispatch."):
            assert all(_inside(s, rounds) for s in by[name]), name


def test_kv_counters_match_an_independent_count(runs):
    _, off, on, _ = runs
    for run in (off, on):
        reserved, written, table = run["counters"]
        assert (reserved, written, table) == run["counted"]
        assert 0 < written < reserved < table


@pytest.mark.parametrize("attr,module", [("_segment", "jit_segment_fn"),
                                         ("_admit_paged",
                                          "jit_admit_paged_fn")])
def test_executable_names_are_pinned(parts, attr, module):
    """The benchmark finds these executables in a device trace by their HLO
    module names; a rename must fail here first."""
    srv = _server(parts, "greedy")
    sched = ContinuousScheduler(srv, quantum=QUANTUM)
    mb = sched.n_slots
    st = (sched._tok, sched._pos, sched._caches)
    if attr == "_segment":
        args = (jnp.zeros((QUANTUM,), jnp.int32), *st,
                jnp.asarray(sched.remaining, jnp.int32),
                jnp.full((mb,), -1, jnp.int32))
    else:
        batch = {"tokens": jnp.ones((2, 16), jnp.int32),
                 "prompt_len": jnp.full((2,), 16, jnp.int32)}
        args = (0, batch, jnp.full((2,), mb, jnp.int32),
                jnp.full((2, sched.n_lblk), sched.allocator.n_blocks,
                         jnp.int32), *st)
    hlo = getattr(srv, attr).lower(*args).as_text(dialect="hlo")
    assert re.match(r"HloModule (\S+?),", hlo).group(1) == module
