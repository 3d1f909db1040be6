"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, the server, a warm call of every executable
shape the cell's traffic can reach) is timed as ``setup_s``; then the
window drives the scheduler for ``--seconds``, with no compilation inside
it; then the program's state is freed and the plain reference checks a
sample of what the window served (``check.py``). ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the middle of the window plus the benchmark's own spans
and the scheduler's counters.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_S = 6.0          # traced part of the window, taken from its middle


class NoChip(SystemExit):
    pass


def compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache`` (a fixed path: it is part of the cache key)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT
                                                              / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


def devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu") or len(devs) < chips:
        raise NoChip(f"bench: needs {chips} accelerator chip(s), JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


class CompileCounter:
    """Programs built while the process runs, from the listener
    ``chip_smoke.py`` uses: backend compilations (``n``, ``s``) and jit
    traces (``traces``; a trace is a jit cache miss, which also loads from
    the persistent cache without compiling)."""

    def __init__(self):
        import jax
        self.n, self.s, self.traces = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += secs
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


class GcPauses:
    """Seconds the collector paused the process, by generation."""

    def __init__(self):
        self.t0, self.total, self.longest, self.n = None, 0.0, 0.0, 0

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            dt = time.perf_counter() - self.t0
            self.total += dt
            self.longest = max(self.longest, dt)
            self.n += 1


_COMPILES = None


def e2e_metrics(win, names: list[str], setup_s: float) -> dict:
    out = {"setup_s": (setup_s, "s")}
    if "output_tok_s" in names:
        out["output_tok_s"] = (sum(r.n for r in win.reqs.values())
                               / win.t_end, "tokens/s")
    return {k: {"value": float(v), "unit": u} for k, (v, u) in out.items()
            if k in names}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, require_chip: bool = True,
             control: bool = False, hook=None, root: Path = ROOT,
             bench: Path | None = None,
             log=print) -> dict:
    """One run of workload ``name``; returns the result line's object.
    ``hook(server)`` may wrap the server's executables (tests break the
    timed path with it). ``control`` judges the control's tokens in place of
    the served ones, and adds the served tokens' verdict as ``program``."""
    import jax
    import numpy as np

    from bench import check, spec
    from bench import serve as S
    from bench.load import Traffic

    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileCounter()
    t_start = time.perf_counter() if t_start is None else t_start
    w = spec.workload(name, root=root, bench=bench or spec.BENCH)
    conf, mix, cell, arch = w["config"], w["traffic"], w["cell"], w["arch"]
    devs = (devices(w["workload"]["chips"]) if require_chip
            else jax.devices())
    dev = devs[0]
    # without a chip (tests), the v5e's peaks stand in
    peak = spec.peaks(dev.device_kind if require_chip else "TPU v5 lite",
                      bench or spec.BENCH)
    cfg = S.model_config(conf, arch)
    sz = arch.sizes(conf["model"])
    traffic = Traffic(mix, seed, cfg.vocab)
    srv, params = S.build(cfg, conf, cell, seed, arch)
    if traffic.longest() >= srv.slots_p:
        raise ValueError(f"{w['traffic']['kind']} needs {traffic.longest()} "
                         f"slots, the cell gives {srv.slots_p}")
    if hook is not None:
        hook(srv)
    sched = S.ContinuousScheduler(srv, quantum=cell["serving"]["quantum"])
    n_warm = S.warm_up(srv, sched, traffic)
    jax.block_until_ready(sched._caches)
    # set-up leaves large object graphs (traced programs); moving them out
    # of the collector's reach keeps its full passes short in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"bench: {name} seed {seed}: set-up {setup_s:.3f}s "
        f"({n_warm} warm calls, {_COMPILES.n} compilations "
        f"{_COMPILES.s:.1f}s)")

    win = S.Window(sched, traffic, queue_rows=mix.get("queue_rows", 2))
    tstate = {"dir": None, "span": None, "k0": None, "k1": None}
    trace_at = None
    if trace:
        t_on = max(0.0, (seconds - TRACE_S) / 2)

        def trace_at(win, now):
            if tstate["dir"] is None and now >= t_on:
                jax.block_until_ready(win.sched._tok)
                tstate["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(tstate["dir"])
                tstate["span"] = S.SPAN("bench.trace")
                tstate["span"].__enter__()
                tstate["k0"] = len(win.steps)
            elif tstate["k1"] is None and tstate["span"] is not None \
                    and now >= t_on + TRACE_S:
                _stop_trace(win, tstate)

    def _stop_trace(win, st):
        jax.block_until_ready(win.sched._tok)
        st["span"].__exit__(None, None, None)
        st["k1"] = len(win.steps)
        jax.profiler.stop_trace()

    c0, tr0 = _COMPILES.n, _COMPILES.traces
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    win.run(seconds, trace_at)
    gc.callbacks.remove(pauses)
    if trace and tstate["span"] is not None and tstate["k1"] is None:
        _stop_trace(win, tstate)
    jax.block_until_ready(sched._caches)
    gc.unfreeze()
    in_window = _COMPILES.n - c0 + _COMPILES.traces - tr0
    sub = np.asarray(win.submit_s or [0.0]) * 1e3
    dur = sorted(((e - s, k) for k, (s, e) in enumerate(win.steps)),
                 reverse=True)[:3]
    log(f"bench: window {win.t_end:.3f}s, {len(win.steps)} steps, "
        f"{len(win.reqs)} requests sent, submit() p50 "
        f"{np.percentile(sub, 50):.3f} ms max {sub.max():.3f} ms, "
        f"programs traced or compiled in "
        f"window {in_window}, gc pauses {pauses.n} ({pauses.total:.3f}s, "
        f"longest {pauses.longest:.3f}s), slowest steps "
        + ", ".join(f"#{k} {d:.3f}s" for d, k in dur))
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))

    reqs = list(win.reqs.values())
    attempted = len(reqs) + win.failed_submit
    failed = win.failed_submit + sum(
        1 for r in reqs if r.status and r.status != "completed")
    names = [m["name"] for m in w["end_to_end"]]
    result = {"correct": False, "attempted": attempted, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    if trace:
        from bench import xplane
        pb = glob.glob(os.path.join(tstate["dir"], "**", "*.xplane.pb"),
                       recursive=True)
        red = xplane.reduce(xplane.load(pb[0]),
                            kernels=tuple(cell.get("kernels", ()))) \
            if pb else {}
        shutil.rmtree(tstate["dir"], ignore_errors=True)
        record = {"reqs": reqs, "steps": win.steps, "segments": win.segments,
                  "admitted": win.admitted, "window_s": win.t_end,
                  "traced_steps": (tstate["k0"], tstate["k1"]),
                  "trace": red, "quantum": sched.quantum,
                  "max_batch": sched.n_slots, "sz": sz, "arch": arch,
                  "kv_bits": conf["kv_bits"], "peak": peak}
        metrics = {}
        for m in w["per_layer"]:
            val = spec.metric_reader(m["name"], bench or spec.BENCH).read(
                record)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
        device["busy_s"] = red.get("busy_s", 0.0)
        device["window_s"] = red.get("window_s", 0.0)
        breakdown = {"device_ops": red.get("device_ops", []),
                     "idle_gaps": red.get("idle_gaps", [])}
    else:
        metrics = e2e_metrics(win, names, setup_s)
        breakdown = None

    # what the window finished, then the program's state goes
    finished = [r for r in reqs if r.status == "completed"]
    bad = [r for r in finished if len(r.tokens) != r.max_new
           or min(r.tokens) < 0 or max(r.tokens) >= cfg.vocab]
    chk = cell["check"]
    picked = check.sample(finished, seed, chk["min_tokens"],
                          chk["max_requests"])
    del win, sched, srv
    gc.collect()
    t_ref = time.perf_counter()
    got = check.gaps(arch, params, sz, conf, picked, chk["pad_to"],
                     control=control)
    log(f"bench: reference over {got['requests']} requests, "
        f"{got['tokens']} served tokens, {time.perf_counter() - t_ref:.1f}s")
    result["correct"], compared = check.verdict(
        got["gap"], got["requests"], len(bad), chk)
    if control:
        ok, served = check.verdict(got["served_gap"], got["requests"],
                                   len(bad), chk)
        result["program"] = {"correct": ok, "compared": served}
        log(f"bench: control gap {got['gap']} (correct "
            f"{result['correct']}), served gap {got['served_gap']} "
            f"(correct {ok})")
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        compile_cache()
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START,
                       log=lambda m: print(m, file=sys.stderr, flush=True))
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    for k, v in res["compared"].items():
        print(f"compared {k} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
