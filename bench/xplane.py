"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Device planes are ``/device:TPU:<n>``. On each, the ``XLA Modules`` line
holds one event per executable run, named ``jit_<function>(<fingerprint>)``
after the program's jitted function, and the ``XLA Ops`` line one event per
HLO operation, named by its HLO text (``%<op name> = ...``). Host spans
(``jax.profiler.TraceAnnotation``) are events on the ``/host:CPU`` plane.
All events carry absolute start times on one clock.

Busy time is the union of the device's operation intervals inside the
traced window; the window is the host span ``bench.trace`` that brackets
the traced steps. Each long idle gap is named by the host span that covers
most of it, the innermost of those that cover equally much: one of the
benchmark's (``bench.*``) or of the serving program's (``serve.*``, see
``spans.py``), so a gap inside the program's ``step()`` says which part of
it the host was in.
"""
from __future__ import annotations

import re
from collections import defaultdict

_FINGERPRINT = re.compile(r"\(\d+\)$")
_CONTROL = ("while", "conditional", "call")
_HOST_SPANS = ("bench.", "serve.")


def _op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def cover_most(s: int, e: int, spans, default: str) -> str:
    """The name of the span in ``spans`` ``[(name, start, end)]`` that
    overlaps ``[s, e)`` most; of spans that overlap it equally, the
    innermost (shortest). ``default`` where none overlaps it."""
    best, key = default, (0, 0)
    for name, hs, he in spans:
        c = min(e, he) - max(s, hs)
        if c > 0 and (c, hs - he) > key:
            best, key = name, (c, hs - he)
    return best


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(pd, window_span: str = "bench.trace",
           kernels: tuple[str, ...] = ()) -> dict:
    """Per device: busy seconds and idle gaps in the window; per module:
    device seconds and runs; per kernel name prefix: device seconds and
    calls; the device ops that took most time; host spans."""
    host = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(_HOST_SPANS):
                        host.append((e.name, e.start_ns, e.start_ns
                                     + e.duration_ns))
    win = [h for h in host if h[0] == window_span]
    if not win or not devices:
        return {}
    lo, hi = win[0][1], win[0][2]
    modules = defaultdict(lambda: [0.0, 0])
    kern = {k: [0.0, 0] for k in kernels}
    ops = defaultdict(float)
    busy_s, gaps = [], []
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    if lo <= e.start_ns < hi:
                        m = modules[_FINGERPRINT.sub("", e.name)]
                        m[0] += e.duration_ns / 1e9
                        m[1] += 1
            elif line.name == "XLA Ops":
                for e in line.events:
                    if not lo <= e.start_ns < hi:
                        continue
                    ivs.append((e.start_ns, e.start_ns + e.duration_ns))
                    op = _op_name(e.name)
                    for k in kernels:
                        if op.startswith(k):
                            kern[k][0] += e.duration_ns / 1e9
                            kern[k][1] += 1
                    if not op.startswith(_CONTROL):
                        ops[op] += e.duration_ns / 1e9
        busy = _union(_clip(ivs, lo, hi))
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    steps = [h for h in host if h[0] not in (window_span, "bench.window")
             and h[2] > lo and h[1] < hi]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        # the host span that overlaps the gap most says what the host did
        named.append([cover_most(s, e, steps, "no host span"),
                      (e - s) / 1e9])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_s) / len(busy_s),
            "n_devices": len(devices),
            "modules": {k: {"s": v[0], "runs": v[1]}
                        for k, v in modules.items()},
            "kernels": {k: {"s": v[0], "calls": v[1]}
                        for k, v in kern.items()},
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": named}
