"""Benchmark harness: one module per paper table/figure + roofline summary.

``python -m benchmarks.run [--quick] [--only name]``

Prints one ``name,us_per_call,derived`` CSV line per benchmark at the end
(the harness contract), with the detailed tables above them.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

# The sharding sweep (throughput.sharding_bench) needs multiple XLA devices;
# on CPU-only hosts that means simulating them. The flag must be set BEFORE
# jax initializes its backends — i.e. before the benchmark modules import —
# and is left alone when the caller exported their own XLA_FLAGS (the
# multi-device CI lane does so explicitly). Same guard as tests/conftest.py.
if "jax" not in sys.modules and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_throughput.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small sizes for CI")
    ap.add_argument("--only", default=None, help="run a single benchmark")
    ap.add_argument("--out", type=pathlib.Path, default=BENCH_JSON,
                    help="where to write the throughput trajectory JSON")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import accuracy, anomaly, flow_scalability, fusion_ablation, resources, throughput

    benches = {
        "accuracy_table5": accuracy.main,
        "resources_table6": resources.main,
        "flow_scalability_fig7": flow_scalability.main,
        "anomaly_auc_fig8": anomaly.main,
        "throughput_fig9": throughput.main,
        "fusion_ablation": fusion_ablation.main,
    }
    if args.only:
        benches = {k: v for k, v in benches.items() if args.only in k}

    csv_lines = ["name,us_per_call,derived"]
    derived_by_name = {}
    for name, fn in benches.items():
        print(f"\n=== {name} {'(quick)' if args.quick else ''} ===")
        t0 = time.perf_counter()
        try:
            derived = fn(quick=args.quick)
            derived_by_name[name] = derived
            us = (time.perf_counter() - t0) * 1e6
            summary = ""
            if isinstance(derived, list) and derived and isinstance(derived[0], dict):
                keys = [k for k in ("f1", "auc") if k in derived[0]]
                if keys:
                    vals = [r[keys[0]] for r in derived]
                    summary = f"mean_{keys[0]}={sum(vals)/len(vals):.4f}"
            elif isinstance(derived, dict) and "speedup" in derived:
                summary = f"speedup={derived['speedup']:.0f}x"
            csv_lines.append(f"{name},{us:.0f},{summary}")
        except Exception:
            traceback.print_exc()
            csv_lines.append(f"{name},-1,FAILED")

    th = derived_by_name.get("throughput_fig9")
    if isinstance(th, dict):
        # machine-readable perf trajectory: tok/s, plan-build ms, per-call ms
        # per backend — benchmarks/compare.py gates CI on regressions vs the
        # committed copy of this file.
        args.out.write_text(json.dumps(th, indent=2, sort_keys=True) + "\n")
        print(f"\nwrote {args.out}")

    print("\n" + "\n".join(csv_lines))
    if any("FAILED" in l for l in csv_lines):
        sys.exit(1)


if __name__ == "__main__":
    main()
