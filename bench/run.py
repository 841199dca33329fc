"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In one process: refuse any platform but a TPU (exit 2, no result), turn
on the program's persistent compile cache, make the banks and the flow
pool from the seed, register the model on an ``AsyncMultiModelServer`` as
the configuration says, warm every compile bucket the cell's traffic
reaches, then drive the traffic mix for ``--seconds`` through
``server.submit(InferRequest(...))``. With ``--trace 1`` the profiler
records a slice of the window and the cell's per-layer metrics are
reported; with ``--trace 0`` its end-to-end metrics. After the window the
program is stopped and freed, and the sampled answers are held to the
configuration's plain reference (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``, each compared number with its limit. The
last lines of standard error repeat the checks.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

from bench import check, generator, spec, trace, work  # noqa: E402

SRC = spec.ROOT / "src"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also hold the control (the reference one precision "
                         "lower, in the program's place) to the limits and "
                         "print its checks; for setting limits, not part of "
                         "a measured run")
    return ap.parse_args(argv)


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


class _CompileCounter:
    """Times at which XLA compiled something (jax.monitoring events)."""

    def __init__(self):
        self.times: list[float] = []

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def _tracer(jax, t_start: float, length: float, out: dict):
    def body():
        time.sleep(max(0.0, t_start - time.perf_counter()))
        out["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out["dir"], profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            out["t0"] = time.perf_counter()
            time.sleep(length)
            out["t1"] = time.perf_counter()
        jax.profiler.stop_trace()

    th = threading.Thread(target=body, name="bench-tracer", daemon=True)
    th.start()
    return th


def _warm(server, req_cls, name: str, x_pool: np.ndarray, sizes, copies: int):
    for n in sizes:
        for _ in range(2):
            futs = [server.submit(req_cls(name, x_pool[:n]))
                    for _ in range(copies)]
            for f in futs:
                f.result()


def run_cell(args) -> dict:
    """Everything after the look for the chip (the harness's tests drive
    it on the CPU)."""
    import jax

    devs = jax.devices()
    cell = spec.find_cell(args.workload)
    cfg, traffic, chips = cell.config, cell.traffic, cell.chips

    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import AsyncMultiModelServer, InferRequest

    cache = enable_compile_cache()
    # keep every executable, the small eager ones the server compiles too,
    # so that a run after the first in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    say("platform", devs[0].platform)
    say("device_kind", devs[0].device_kind)
    say("device_count", len(devs))
    say("compile_cache", cache)

    phases = {"start": time.perf_counter() - T_START}
    mod, ref = spec.config_module(cfg), spec.reference_module(cfg)
    s31 = generator.seed31(args.seed)
    banks, pool = mod.make(cfg, s31, args.seed)
    x_pool = mod.inputs(cfg, pool)
    jax.block_until_ready(banks)
    phases["banks"] = time.perf_counter() - T_START
    name = cfg["name"]
    server = AsyncMultiModelServer(**cfg.get("server", {}))
    plan = server.add_model(name, mod.program_model(cfg, banks),
                            backend=cfg["backend"])
    server.start()
    phases["plan"] = time.perf_counter() - T_START

    def submit(x):
        return server.submit(InferRequest(name, x))

    rec = generator.Recorder()
    _warm(server, InferRequest, name, x_pool,
          generator.warm_sizes(traffic, server.max_batch), chips)
    phases["warm"] = time.perf_counter() - T_START
    say("setup_phases_s", json.dumps(phases))
    say("compiles_in_setup", len(compiles.times))

    stats0 = server.stats()
    seconds = float(args.seconds)
    traced: dict = {}
    clients = int(traffic["clients_per_chip"]) * chips
    templates = generator.closed_templates(traffic, args.seed, len(x_pool))
    stop = threading.Event()
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    threads = generator.run_closed(submit, clients, x_pool, templates,
                                   args.seed,
                                   int(cfg["check"]["sample_requests"]),
                                   stop, rec, jax.profiler.TraceAnnotation)
    t1 = t0 + seconds
    tracer = (_tracer(jax, t0 + _trace_at(seconds), _trace_len(seconds),
                      traced) if args.trace else None)
    time.sleep(max(0.0, t1 - time.perf_counter()))
    stats1 = server.stats()
    stop.set()
    for th in threads:
        th.join(timeout=120)
    if tracer is not None:
        tracer.join()
    after = {"drain": time.perf_counter() - t1}
    say("compiles_in_window", compiles.between(t0, t1))
    say("generator_lag_ms", "none: a closed loop sends on completion, "
        "with no schedule to fall behind")

    used = devs[:chips]
    peak_mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)
    server.stop()
    server.close()
    del server, plan, submit
    gc.collect()
    after["stop"] = time.perf_counter() - t1

    with rec.lock:
        rows = list(rec.rows)
        submit_s = list(rec.submit_s)
    samples = rec.samples()
    recs = np.array([r[:5] for r in rows], dtype=np.float64).reshape(-1, 5)
    qwait = np.array([np.nan if r[5] is None else r[5] for r in rows])
    in_window = recs[:, 1] < t1
    attempted = int(in_window.sum())
    failed = int((in_window & (recs[:, 4] == 0)).sum())

    summary = None
    if args.trace:
        raw = trace.load(traced["dir"])
        kernels = {}
        for m in cell.per_layer:
            rd = spec.metric_module(m["name"])
            if hasattr(rd, "KERNEL"):
                kernels[rd.KERNEL] = rd.PATTERNS
        summary = trace.reduce(raw, kernels, chips=chips)
        shutil.rmtree(traced["dir"], ignore_errors=True)

    from bench.peaks import peaks
    ctx = types.SimpleNamespace(
        cell=cell, config=cfg, traffic=traffic, chips=chips,
        seconds=seconds, t0=t0, t1=t1, setup_s=setup_s,
        records=recs, queue_wait_ms=qwait, submit_s=np.array(submit_s),
        stats0=stats0, stats1=stats1, trace=summary,
        traced=(traced.get("t0"), traced.get("t1")),
        geometry=mod.geometry(cfg), kernel_calls=mod.kernel_calls(cfg),
        peaks=peaks(devs[0].device_kind) if devs[0].platform == "tpu"
        else None, work=work)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    after["trace_and_metrics"] = time.perf_counter() - t1

    # the comparison, once the program's state is freed
    order = sorted(samples)
    served = [(samples[r][1], len(samples[r][0])) for r in order]
    xs = (np.concatenate([x_pool[samples[r][0]] for r in order])
          if order else x_pool[:0])
    want, margins = check.reference(ref, cfg, banks, xs)
    verdict = check.compare(cfg, served, want, margins, failed=failed)
    after["reference"] = time.perf_counter() - t1
    say("requests_checked", len(served))
    say("flows_checked", verdict["flows_checked"])
    say("near_share", verdict["near_share"])
    if args.control:
        got, _ = check.reference(ref, cfg, banks, xs, precision="high")
        start, ctl = 0, []
        for _, n in served:
            ctl.append((got[start:start + n], n))
            start += n
        control = check.compare(cfg, ctl, want, margins, failed=0)
        say("control", json.dumps(control))
    else:
        control = None
    say("after_window_s", json.dumps(after))

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_mem}
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if control is not None:
        result["control"] = control
    result["checks"] = verdict["checks"]
    return result


def _trace_at(seconds: float) -> float:
    return min(1.0, 0.2 * seconds)


def _trace_len(seconds: float) -> float:
    return min(2.0, max(0.2, 0.3 * seconds))


def emit(result: dict) -> None:
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = spec.find_cell(args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found platform {devs[0].platform!r}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    emit(run_cell(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())
