"""Host trace spans of the served path (docs/SERVING.md, "Tracing").

A profiler trace captured around a few requests served by an
``AsyncMultiModelServer`` holds the program's spans on the host lines of
the same ``.xplane.pb`` as the device operations. Each case runs the
one-chip path (``devices=None``) and the device-stream pool
(``devices=2``, on the suite's virtual CPU devices), reads the trace back
with ``ProfileData.from_file`` and checks the names, their nesting, the
count of plan calls and the ``round`` argument that links a stream
worker's chunk to the drain round that sent it.
"""

import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.amm import init_pegasus_linear
from repro.launch.serve import AsyncMultiModelServer, InferRequest

SPANS = {"serve.submit", "serve.round", "sched.wait", "serve.begin",
         "plan.call", "serve.wait", "serve.to_host", "devices.run"}


def _banks() -> list:
    rng = np.random.default_rng(0)
    return [init_pegasus_linear(
        rng.normal(size=(8, 5)).astype(np.float32), None,
        rng.normal(size=(64, 8)).astype(np.float32), group_size=2, depth=3,
        lut_bits=None)]


def _capture(tmp_path, devices):
    """Serve warm requests inside a profiler capture; returns the spans as
    ``{line index: [(name, start_ns, end_ns, args)]}`` and the count of
    chunks the server dispatched during the capture."""
    x = np.random.default_rng(1).normal(size=(32, 8)).astype(np.float32)
    # one 16-flow chunk per round: several rounds, several chunks, both
    # streams of the pool
    server = AsyncMultiModelServer({"m": _banks()}, backend="gather",
                                   devices=devices, max_batch=16, quantum=16)
    try:
        server.start()
        for n in (1, 2, 4, 8, 16):            # compile outside the capture
            server.submit(InferRequest("m", x[:n])).result(timeout=120)
        b0 = server.batches_dispatched
        jax.profiler.start_trace(str(tmp_path))
        try:
            time.sleep(0.1)                   # one idle wait of the loop
            futs = [server.submit(InferRequest("m", x[: 8 + 4 * i]))
                    for i in range(6)]
            for f in futs:
                f.result(timeout=120)
            # joining the loop closes its last round and wait
            server.stop()
        finally:
            jax.profiler.stop_trace()
        dispatched = server.batches_dispatched - b0
    finally:
        server.stop()
        server.close()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in SPANS:
                    lines.setdefault((plane.name, i), []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return lines, dispatched


def _inside(inner, spans, name) -> bool:
    """Whether span ``inner`` lies within one ``name`` span of its line."""
    return any(n == name and s <= inner[1] and inner[2] <= e
               for n, s, e, _ in spans)


@pytest.mark.parametrize("devices", [None, 2])
def test_served_path_spans(tmp_path, devices):
    lines, dispatched = _capture(tmp_path, devices)
    spans = [sp for line in lines.values() for sp in line]
    names = {sp[0] for sp in spans}
    if devices is None:
        assert names == SPANS - {"devices.run"}
    else:
        assert names == SPANS

    # every chunk is one plan call, and each request one submit
    assert dispatched > 0
    assert sum(n == "plan.call" for n, *_ in spans) == dispatched
    assert sum(n == "serve.submit" for n, *_ in spans) == 6

    rounds = {a["round"] for n, _, _, a in spans if n == "serve.round"}
    for line in lines.values():
        for sp in line:
            name = sp[0]
            if name == "serve.begin":
                assert _inside(sp, line, "serve.round")
                assert sp[3]["flows"] > 0
            if devices is None:
                if name == "plan.call":
                    assert _inside(sp, line, "serve.begin")
                if name in ("serve.wait", "serve.to_host"):
                    assert _inside(sp, line, "serve.round")
            else:
                if name in ("plan.call", "serve.to_host"):
                    assert _inside(sp, line, "devices.run")
                if name == "devices.run":
                    assert sp[3]["round"] in rounds
                    assert sp[3]["flows"] > 0
                    # the worker waits on its chunk and copies it
                    assert any(n == "serve.wait" and sp[1] <= s and e <= sp[2]
                               for n, s, e, _ in line)
    # the drain thread never runs a plan call in pool mode
    if devices is not None:
        drain = [line for line in lines.values()
                 if any(n == "serve.round" for n, *_ in line)]
        assert drain and not any(n == "plan.call"
                                 for line in drain for n, *_ in line)
