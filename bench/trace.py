"""Reduce a JAX profiler trace to the device's busy time, each kernel's
device time and the idle gaps, named by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
structure (``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns, detail], ...]}]}]}``); ``reduce`` works on that
structure only, so a small recorded trace kept as JSON tests it.

* Device planes are ``/device:TPU:<i>``; their operations are the events of
  the ``XLA Ops`` line. Busy time is the union of those intervals inside the
  traced window, per chip.
* The traced window is the host span the harness opens around it
  (``bench.traced_window``).
* A kernel's time is the sum of the durations of the device operations
  whose name or detail (the HLO op, its module and name stack) contains one
  of the kernel's patterns.
* Each idle gap is named by the shortest host event that covers its
  midpoint: the most specific thing the host was doing then.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.traced_window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_DETAIL_STATS = ("hlo_op", "hlo_module", "long_name", "tf_op", "name",
                 "kernel_details")


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a plain structure."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        device = bool(_DEVICE.match(plane.name))
        lines = []
        for line in plane.lines:
            evs = []
            for ev in line.events:
                dur = float(ev.duration_ns)
                if not device and dur <= 0:
                    continue
                detail = ""
                if device:
                    detail = " ".join(str(v) for k, v in ev.stats
                                      if k in _DETAIL_STATS)
                evs.append([ev.name, float(ev.start_ns), dur, detail])
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_kind(name: str) -> str:
    """``%fuzzy_lut_pallas.29 = f32[...] custom-call(...)`` → ``fuzzy_lut_pallas``:
    the HLO instruction's name without its number, so the breakdown sums
    the operations of one kind."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _window(trace: dict) -> tuple[float, float] | None:
    for plane in trace["planes"]:
        if _DEVICE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, s, d, _ in line["events"]:
                if name == WINDOW_SPAN:
                    return s, s + d
    return None


def _device_ops(plane: dict) -> list:
    lines = [l for l in plane["lines"] if l["name"] == "XLA Ops"]
    return [ev for l in lines for ev in l["events"]]


def reduce(trace: dict, kernels: dict | None = None, *, chips: int | None = None,
           top: int = 10) -> dict:
    """``{"window_s", "busy_s" (mean over chips), "busy_s_per_chip",
    "kernel_s": {kernel: s}, "kernel_calls": {kernel: n}, "device_ops":
    [[name, s]], "idle_gaps": [[host event, s]]}`` for the traced window;
    kernel and operation times and call counts are summed over the chips.
    ``kernels`` maps a kernel to its name patterns; ``chips`` keeps the
    first that many device planes (the chips the cell uses)."""
    kernels = kernels or {}
    win = _window(trace)
    if win is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w0, w1 = win
    devices = sorted((int(_DEVICE.match(p["name"]).group(1)), p)
                     for p in trace["planes"] if _DEVICE.match(p["name"]))
    devices = [p for _, p in devices][:chips]
    if not devices:
        raise ValueError("trace has no device plane")
    busy, ksum, kcalls, ops, gaps = [], {}, {}, {}, []
    for k in kernels:
        ksum[k], kcalls[k] = 0.0, 0
    for plane in devices:
        ivs = []
        for name, s, d, detail in _device_ops(plane):
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 <= s0:
                continue
            ivs.append([s0, e0])
            key = op_kind(name)
            ops[key] = ops.get(key, 0.0) + (e0 - s0)
            text = f"{name} {detail}"
            for k, pats in kernels.items():
                if any(p in text for p in pats):
                    ksum[k] += e0 - s0
                    kcalls[k] += 1
        merged = _merge(ivs)
        busy.append(sum(e - s for s, e in merged))
        prev = w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                gaps.append((s - prev, prev, s))
            prev = max(prev, e)
    host = [(s, s + d, f"{l['name']}:{name}")
            for p in trace["planes"] if not _DEVICE.match(p["name"])
            for l in p["lines"] for name, s, d, _ in l["events"]
            if name != WINDOW_SPAN]
    gaps.sort(reverse=True)
    named = []
    for length, s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        cover = [(he - hs, label) for hs, he, label in host
                 if hs <= mid <= he]
        named.append([min(cover)[1] if cover else "no host event",
                      length * 1e-9])
    n = len(devices)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "busy_s_per_chip": [b * 1e-9 for b in busy],
        "kernel_s": {k: v * 1e-9 for k, v in ksum.items()},
        "kernel_calls": dict(kcalls),
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }
