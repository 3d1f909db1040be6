"""Reduce the serving program's own spans in a profiler trace.

The scheduler names its host path in ``serve.*`` spans
(``jax.profiler.TraceAnnotation``, see ``repro.serving.scheduler``): a
round ``serve.step``, its ``serve.admit``, ``serve.segment`` and
``serve.flush`` with ``serve.flush.wait`` inside, each executable call
``serve.dispatch.<fn>``, and ``serve.submit``. They lie on the
``/host:CPU`` plane, on the clock of the device events, so each device
idle gap can be laid against what the program was doing in it. The window
is the ``bench.trace`` span, as in ``xplane.reduce``, whose busy time and
gaps these reductions share.
"""
from __future__ import annotations

from collections import defaultdict

from .xplane import _clip, _union, cover_most

PREFIX = "serve."
OUTSIDE = "outside serve"


def _host(pd, window_span: str):
    """(window (lo, hi) or None, serve spans [(name, start, end)])."""
    win, spans = None, []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == window_span and win is None:
                    win = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(PREFIX):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    return win, spans


def program_spans(pd, window_span: str = "bench.trace") -> dict:
    """Per ``serve.*`` name: total seconds ``s`` and count ``n`` of the
    spans that start inside the window."""
    win, spans = _host(pd, window_span)
    if win is None:
        return {}
    out = defaultdict(lambda: {"s": 0.0, "n": 0})
    for name, s, e in spans:
        if win[0] <= s < win[1]:
            out[name]["s"] += (e - s) / 1e9
            out[name]["n"] += 1
    return dict(out)


def idle_by_span(pd, window_span: str = "bench.trace") -> dict:
    """Device-idle seconds in the window (per device, averaged over the
    devices), by the ``serve.*`` span that covers the largest part of
    each gap; of spans covering equally much, the innermost (shortest).
    A gap no ``serve.*`` span overlaps is ``"outside serve"``."""
    win, spans = _host(pd, window_span)
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if win is None or not devices:
        return {}
    lo, hi = win
    out = defaultdict(float)
    for plane in devices:
        ivs = [(e.start_ns, e.start_ns + e.duration_ns)
               for line in plane.lines if line.name == "XLA Ops"
               for e in line.events if lo <= e.start_ns < hi]
        busy = _union(_clip(ivs, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            out[cover_most(s, e, spans, OUTSIDE)] += (e - s) / 1e9 \
                / len(devices)
    return dict(out)
