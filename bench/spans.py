"""Reduce the program's own host spans in a JAX profiler trace to per-span
statistics of the traced window, and put the device's idle time down to
them.

The served path opens eight ``jax.profiler.TraceAnnotation`` spans
(``SPANS``; docs/SERVING.md, "Tracing"). The profiler records them on the
host lines of the same trace as the device operations, on one clock.
``reduce`` works on the plain structure ``trace.load`` makes, inside the
harness's ``bench.traced_window`` span:

* ``count`` and ``mean_s``: the spans of a name that lie wholly inside the
  window, and their mean duration;
* ``busy_s``: per host line (thread) that has the name, the union of its
  intervals clipped to the window;
* ``idle_by_span``: the device idle time of the window, summed over the
  chips, each idle instant put down to the shortest program span open at
  that instant (the rule ``trace.reduce`` names single gaps by), or to
  ``NO_SPAN``.

A trace of a program without these spans reduces to empty statistics and
all idle time under ``NO_SPAN``.
"""

from __future__ import annotations

import heapq

from bench.trace import _DEVICE, _device_ops, _merge, _window

SPANS = ("serve.submit", "serve.round", "sched.wait", "serve.begin",
         "plan.call", "serve.wait", "serve.to_host", "devices.run")
NO_SPAN = "no program span"


def _host_spans(trace: dict) -> list:
    """``[[(name, start, end), ...] per host line]``, program spans only."""
    out = []
    for plane in trace["planes"]:
        if _DEVICE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out.append([(name, s, s + d) for name, s, d, _ in line["events"]
                        if name in SPANS])
    return out


def _idle(plane: dict, w0: float, w1: float) -> list:
    """The window's intervals in which no operation runs on this chip."""
    busy = _merge([[max(s, w0), min(s + d, w1)]
                   for _, s, d, _ in _device_ops(plane)
                   if min(s + d, w1) > max(s, w0)])
    idle, prev = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    return idle


def _attribute(idle: list, spans: list, out: dict) -> None:
    """Add each idle interval's time to the shortest span open over it, cut
    where spans open and close."""
    points = sorted({p for s, e in idle for p in (s, e)}
                    | {p for _, s, e in spans for p in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    open_: list = []                     # heap of (duration, name, end)
    k, j = 0, 0
    for a, b in zip(points, points[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            name, s, e = by_start[k]
            heapq.heappush(open_, (e - s, name, e))
            k += 1
        while open_ and open_[0][2] <= a:
            heapq.heappop(open_)
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        if j < len(idle) and idle[j][0] <= a:
            key = open_[0][1] if open_ else NO_SPAN
            out[key] = out.get(key, 0.0) + (b - a)


def reduce(trace: dict, *, chips: int | None = None) -> dict:
    """``{"window_s", "spans": {name: {"count", "mean_s", "busy_s": [s per
    host line]}}, "idle_by_span": {name: s}}`` for the traced window;
    ``chips`` keeps the first that many device planes, as in
    ``trace.reduce``."""
    win = _window(trace)
    if win is None:
        raise ValueError("trace has no traced window span")
    w0, w1 = win
    lines = _host_spans(trace)
    stats: dict = {}
    for name in SPANS:
        whole = [e - s for line in lines for n, s, e in line
                 if n == name and w0 <= s and e <= w1]
        busy = []
        for line in lines:
            ivs = [[max(s, w0), min(e, w1)] for n, s, e in line
                   if n == name and min(e, w1) > max(s, w0)]
            if ivs:
                busy.append(sum(e - s for s, e in _merge(ivs)) * 1e-9)
        if whole or busy:
            stats[name] = {"count": len(whole),
                           "mean_s": (sum(whole) / len(whole) * 1e-9
                                      if whole else None),
                           "busy_s": busy}
    devices = sorted((int(_DEVICE.match(p["name"]).group(1)), p)
                     for p in trace["planes"] if _DEVICE.match(p["name"]))
    flat = [sp for line in lines for sp in line]
    idle: dict = {}
    for _, plane in devices[:chips]:
        _attribute(_idle(plane, w0, w1), flat, idle)
    return {"window_s": (w1 - w0) * 1e-9,
            "spans": stats,
            "idle_by_span": {k: v * 1e-9 for k, v in
                             sorted(idle.items(), key=lambda kv: -kv[1])}}
