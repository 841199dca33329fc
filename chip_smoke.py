"""On-chip smoke test of the served Pegasus path.

Builds MLP-B at its published widths with the recipe that
``python -m repro.launch.serve --pegasus`` uses
(:func:`repro.launch.serve.mlp_b_server`), serves typed requests through
the always-on ``AsyncMultiModelServer`` on both Pallas kernel backends, and
checks every output against the XLA ``gather`` plan on the same inputs. It
fails unless the kernels really ran, compiled, on the TPU: no fallback
batch, no degraded model, no loop error, fused banks, and a
``tpu_custom_call`` in each compiled forward.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the multi-device phase

Earlier lines report the device, the compile time and the parity numbers;
the last line of a passing run is one JSON object naming the device. Exit
code 2 means JAX found no TPU (nothing runs on any other platform); any
failed check exits 1 without that line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# one request per size: buckets 8 and 16, 256, the 1024-row bucket and the
# ladder top (4096)
SIZES = (1, 9, 256, 1024, 4096)
KERNELS = ("kernel", "kernel_q8")
TOL = 1e-4         # kernel vs gather, rtol and atol (tests/test_engine.py)
Q8_AGREE = 0.75    # kernel_q8 vs gather argmax agreement (same source)


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok  {what}", flush=True)


def report(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def _parity(name: str, backend: str, outs: dict, ref: dict) -> None:
    """Hold one backend's outputs to the gather plan's, size by size."""
    agree_rows = []
    for n in SIZES:
        got, want = outs[n], ref[n]
        check(got.shape == want.shape and bool(np.isfinite(got).all()),
              f"{name} n={n}: finite output of shape {want.shape}")
        if backend == "kernel":
            err = float(np.abs(got - want).max())
            check(np.allclose(got, want, rtol=TOL, atol=TOL),
                  f"{name} n={n}: within {TOL:g} of gather "
                  f"(max abs diff {err!r})")
        else:
            agree_rows.append(got.argmax(-1) == want.argmax(-1))
            report(f"{name} n={n} argmax agreement with gather",
                   float(agree_rows[-1].mean()))
    if agree_rows:
        agree = float(np.concatenate(agree_rows).mean())
        check(agree >= Q8_AGREE, f"{name}: argmax agreement {agree!r} over "
              f"{sum(SIZES)} flows >= {Q8_AGREE}")


def _check_served(server, names: list[str], served: dict) -> dict:
    """The served path ran as built: no fallback, no loop error, fused."""
    st = server.stats()
    check(not st["health"]["degraded_models"],
          f"no degraded model (got {st['health']['degraded_models']})")
    check(not server.loop_errors,
          f"empty loop_errors (got {list(server.loop_errors)!r})")
    for name in names:
        health = st["health"]["models"][name]
        check(health["fallback_batches"] == 0 and health["probe_batches"] == 0,
              f"{name}: zero fallback and probe batches")
        check(st["serving"]["models"][name]["requests_served"] == served[name],
              f"{name}: {served[name]} requests served")
        eng = st["engine"]["models"][name]
        check(eng["fused_groups"] >= 1,
              f"{name}: {eng['fused_groups']} fused group(s) covering "
              f"{eng['fused_banks']} banks")
        report(f"{name} compiled buckets", eng["buckets"])
    return st


def _check_compiled(server, names: list[str], d_in: int) -> None:
    """Each kernel backend's compiled forward holds a Mosaic kernel."""
    import jax
    import jax.numpy as jnp

    for name in names:
        plan = server.registry.get(name)
        text = plan.lower(jax.ShapeDtypeStruct((1024, d_in), jnp.float32)
                          ).compile().as_text()
        check("tpu_custom_call" in text,
              f"{name}: tpu_custom_call in the compiled bucket-1024 forward")


def phase_one_chip(seed: int) -> None:
    import jax.numpy as jnp

    from repro.engine import build_plan
    from repro.launch.serve import InferRequest, mlp_b_server

    server, ds = mlp_b_server(KERNELS, seed=seed)
    names = [f"mlp_b/{be}" for be in KERNELS]
    banks = server.registry.model(names[0])
    report("model", f"MLP-B: {len(banks)} banks, ks="
           f"{[b.num_groups for b in banks]}, v={banks[0].group_size}, "
           f"C={banks[0].num_centroids}, out={banks[-1].out_features}")
    pool = np.concatenate([ds.train["stats"], ds.val["stats"],
                           ds.test["stats"]]).astype(np.float32)
    inputs = {n: pool[:n] for n in SIZES}
    gather = build_plan(banks, backend="gather")
    ref = {n: np.asarray(gather(x)) for n, x in inputs.items()}
    served = dict.fromkeys(names, 0)
    try:
        server.start()
        # 1) one request at a time, so each size compiles its own bucket
        t0 = time.perf_counter()
        first = {name: {n: server.submit(InferRequest(name, inputs[n]))
                        .result().output for n in SIZES} for name in names}
        report("compile_s (first request of each bucket, both backends; "
               "ends at the host copy of the result)",
               time.perf_counter() - t0)
        for name, be in zip(names, KERNELS):
            served[name] += len(SIZES)
            _parity(name, be, first[name], ref)
        # 2) every request at once: the scheduler coalesces and re-chunks
        t0 = time.perf_counter()
        futs = {(name, n): server.submit(InferRequest(name, inputs[n]))
                for name in names for n in SIZES}
        burst = {name: {n: futs[name, n].result().output for n in SIZES}
                 for name in names}
        report(f"warm burst s ({len(futs)} requests, "
               f"{len(names) * sum(SIZES)} flows)", time.perf_counter() - t0)
        for name, be in zip(names, KERNELS):
            served[name] += len(SIZES)
            _parity(name, be, burst[name], ref)
        # 3) a device array exactly one bucket long is copied, not donated
        xb = jnp.asarray(inputs[1024])
        out = server.submit(InferRequest(names[0], xb)).result().output
        served[names[0]] += 1
        check(np.array_equal(np.asarray(xb), inputs[1024])
              and np.array_equal(out, first[names[0]][1024]),
              "caller's bucket-sized jax array readable and unchanged "
              "after the donating call")
        # accuracy on the held-out flows, against the labels
        x_test, y_test = ds.test["stats"].astype(np.float32), ds.test["label"]
        report("test flows", len(y_test))
        report("accuracy gather",
               float((np.asarray(gather(x_test)).argmax(-1) == y_test).mean()))
        for name in names:
            out = server.submit(InferRequest(name, x_test)).result().output
            served[name] += 1
            report(f"accuracy {name}", float((out.argmax(-1) == y_test).mean()))
        _check_served(server, names, served)
    finally:
        server.stop()
    _check_compiled(server, names, pool.shape[1])


def phase_four_chips(seed: int) -> None:
    import jax

    from repro.engine import build_plan
    from repro.launch.serve import InferRequest, mlp_b_server

    # max_batch 1024 with a 4096-flow quantum: one scheduler round cuts
    # four chunks, one per device stream
    server, ds = mlp_b_server(("kernel",), seed=seed, devices=4,
                              max_batch=1024, quantum=4096)
    name = "mlp_b/kernel"
    banks = server.registry.model(name)
    pool = np.concatenate([ds.train["stats"], ds.val["stats"],
                           ds.test["stats"]]).astype(np.float32)
    one_chip = build_plan(banks, backend="kernel")
    sharded = build_plan(banks, backend="kernel", devices=4)
    check(sharded.compile_stats()["fused_groups"] >= 1,
          "sharded plan keeps its fused group")
    requests = [pool[:n] for n in SIZES] + [
        pool[i * 512:(i + 2) * 512] for i in range(8)]
    ref = [np.asarray(one_chip(x)) for x in requests]
    for x, want in zip(requests, ref):
        got = sharded(x)
        check(len(got.sharding.device_set) == 4
              and np.array_equal(np.asarray(got), want),
              f"sharded plan n={len(x)}: on 4 devices, bit-exact with one chip")
    try:
        server.start()
        futs = [server.submit(InferRequest(name, x)) for x in requests]
        outs = [f.result().output for f in futs]
        for x, got, want in zip(requests, outs, ref):
            check(np.array_equal(got, want),
                  f"4-stream server n={len(x)}: bit-exact with one chip")
        st = _check_served(server, [name], {name: len(requests)})
    finally:
        server.stop()
        server.close()
    dev = st["devices"]
    report("streams", [(d["device"], d["dispatched_chunks"],
                        d["dispatched_flows"]) for d in dev["per_device"]])
    check(dev["healthy_streams"] == 4, "4 healthy streams")
    check(dev["inline_dispatches"] == 0, "no inline dispatch")
    check(all(d["dispatched_flows"] > 0 for d in dev["per_device"]),
          "flows dispatched on every stream")
    text = sharded.lower(jax.ShapeDtypeStruct((1024, pool.shape[1]),
                                              np.float32)).compile().as_text()
    check("tpu_custom_call" in text,
          "tpu_custom_call in the sharded compiled forward")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-device phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic flows and the teacher")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found platform {platform!r} "
              f"({len(devices)} device(s)), not a TPU; nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    report("platform", platform)
    report("device_kind", devices[0].device_kind)
    report("device_count", len(devices))
    report("jax", jax.__version__)
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    report("compile cache", f"{cache} ({entries} entries at start)")
    try:
        if args.chips == 4:
            phase_four_chips(args.seed)
        else:
            phase_one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
