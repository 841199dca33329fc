"""The yardstick's own pieces, on the CPU at small sizes."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from bench import check, generator, spec, trace, work
from bench.configs import mlp_b, rnn_b

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def _config(name):
    return {"name": name, **json.loads(
        (spec.BENCH_DIR / "configs" / f"{name}.json").read_text())}


@pytest.mark.parametrize("name", ["mlp_b", "rnn_b", "mlp_b_4chip"])
def test_reference_agrees_with_gather_plan(name):
    """Each configuration's plain reference against the program's ``gather``
    plan on the same banks, at a small size."""
    from repro.engine import build_plan

    cfg = dict(_config(name), pool_flows=512)
    mod, ref = spec.config_module(cfg), spec.reference_module(cfg)
    banks, pool = mod.make(cfg, 1234, 98765432109)
    x = mod.inputs(cfg, pool)
    plan = build_plan(mod.program_model(cfg, banks), backend="gather",
                      audit="off")
    got = np.asarray(plan(x))
    want, margins = check.reference(ref, cfg, banks, x)
    far = margins >= cfg["check"]["near_margin"]
    assert far.mean() > 0.5
    assert np.abs(got[far] - want[far]).max() <= 1e-5
    # the bulk of the flows agree exactly or to rounding, near ones included
    assert np.median(np.abs(got - want)) <= 1e-5


def test_reference_imports_nothing_of_the_program():
    for path in list((spec.BENCH_DIR / "configs").glob("*_ref.py")) + [
            spec.BENCH_DIR / "reference.py"]:
        text = path.read_text()
        assert "repro" not in text, path


def test_work_counts_mlp_b_by_hand():
    geo = mlp_b.geometry(_config("mlp_b"))
    # banks: 16->32 (K=8), 32->32 (K=16) twice, 32->3 (K=16); depth 6
    compares = (8 + 16 + 16 + 16) * 6
    adds = 8 * 32 + 16 * 32 + 16 * 32 + 16 * 3
    bias = 32 + 32 + 32 + 3
    assert (compares, adds, bias) == (336, 1328, 99)
    assert work.ops_per_flow(geo) == compares + adds + bias == 1763
    tables = 4 * sum(k * 64 * n + 2 * k * 63
                     for k, n in ((8, 32), (16, 32), (16, 32), (16, 3)))
    ops, nbytes = work.kernel_work(geo, [[0, 1, 2, 3]], flows=4096,
                                   n_calls=2)
    assert ops == 4096 * 1763
    assert nbytes == 4096 * 4 * (16 + 3) + 2 * tables
    share, bound = work.roofline_share(ops, nbytes, 1e-3,
                                       {"flops": 197e12,
                                        "hbm_bytes_per_s": 819e9})
    assert bound == "memory"
    assert share == pytest.approx(100 * nbytes / 819e9 / 1e-3)


def test_work_counts_rnn_b():
    geo = rnn_b.geometry(_config("rnn_b"))
    assert len(geo) == 16
    assert work.ops_per_flow(geo) == (8 * 2 * 8 + 7 * 24 * 8 + 24 * 8
                                      + 8 * 2 * 24 + 7 * 24 * 24 + 24 * 3
                                      + 24 + 7 * 24 + 3)


def test_trace_reduction_of_a_recorded_trace():
    """A 6 ms slice of a trace recorded on a TPU v5 lite in ``mlp_b.bulk``
    (``--trace 1``), kept as the plain structure ``trace.load`` makes; the expected numbers were worked out from the
    events directly, not with ``trace.reduce``."""
    raw = json.loads((FIXTURES / "trace_slice.json").read_text())
    expect = json.loads((FIXTURES / "trace_slice.expected.json").read_text())
    got = trace.reduce(raw, {"fuzzy_lut_stack": ("fuzzy_lut_stack",)},
                       chips=1)
    assert got["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert got["kernel_s"]["fuzzy_lut_stack"] == pytest.approx(
        expect["kernel_s"], rel=1e-9)
    assert got["kernel_calls"]["fuzzy_lut_stack"] == expect["kernel_calls"]
    assert len(got["idle_gaps"]) <= 10 and len(got["device_ops"]) <= 10
    assert got["idle_gaps"][0][1] == pytest.approx(expect["longest_gap_s"],
                                                   rel=1e-9)


def test_trace_reduction_by_hand():
    """Two chips, overlapping operations, a kernel, a gap named by the
    host event that covers it."""
    ms = 1e6
    raw = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            [trace.WINDOW_SPAN, 0.0, 10 * ms, ""],
            ["bench.submit", 4 * ms, 4 * ms, ""]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["a", 1 * ms, 2 * ms, "jit(fuzzy_lut_stack_pallas)"],
            ["b", 2 * ms, 2 * ms, ""],
            ["c", 8 * ms, 4 * ms, ""]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ["a", 0.0, 5 * ms, "fuzzy_lut_stack_kernel"]]}]},
    ]}
    got = trace.reduce(raw, {"fuzzy_lut_stack": ("fuzzy_lut_stack",)})
    assert got["window_s"] == pytest.approx(0.010)
    # chip 0: [1,4] + [8,10] = 5 ms; chip 1: [0,5] = 5 ms
    assert got["busy_s"] == pytest.approx(0.005)
    assert got["kernel_s"]["fuzzy_lut_stack"] == pytest.approx(0.007)
    assert got["kernel_calls"]["fuzzy_lut_stack"] == 2
    assert got["idle_gaps"][0] == ["python3:bench.submit",
                                   pytest.approx(0.005)]


def test_new_traffic_mix_is_found_by_name(tmp_path):
    """A mix added as a data file, with a cell that names it, is taken up
    with no other file edited."""
    traffic = tmp_path / "traffic"
    traffic.mkdir()
    for f in (spec.BENCH_DIR / "traffic").glob("*.json"):
        (traffic / f.name).write_text(f.read_text())
    (traffic / "tiny.json").write_text(json.dumps({
        "loop": "closed", "clients_per_chip": 2, "flows_per_request": 300,
        "templates": 5}))
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "mlp_b.tiny", "config": "mlp_b",
                               "traffic": "tiny", "chips": 1,
                               "why": "test"})
    cell = spec.find_cell("mlp_b.tiny", bench, traffic_dir=traffic)
    assert cell.traffic["flows_per_request"] == 300
    tm = generator.closed_templates(cell.traffic, 5, 100)
    assert tm.shape == (5, 300) and tm.min() >= 0 and tm.max() < 100
    assert np.array_equal(tm, generator.closed_templates(cell.traffic, 5,
                                                         100))
    assert generator.warm_sizes(cell.traffic, 4096) == [300]


def test_sample_is_fixed_in_size_seeded_and_spread():
    """Each client keeps a fixed-size uniform sample of its own requests,
    the same for the same seed, however many requests it sends."""
    def draw(seed, n):
        r = generator.Reservoir(32, [generator.seed31(seed), 7])
        for i in range(n):
            r.offer(i)
        return sorted(r.items)

    a, b = draw(2**40 + 9, 5000), draw(2**40 + 9, 5000)
    assert a == b and len(a) == 32 and len(set(a)) == 32
    assert draw(3, 5000) != a
    # spread over the whole stream, not its head
    assert min(a) < 1250 and max(a) > 3750
    assert draw(1, 10) == list(range(10))


def test_seed31_takes_large_seeds():
    assert generator.seed31(2**40) != generator.seed31(0)
    assert 0 <= generator.seed31(2**70 + 3) < 2**31


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-m", "bench.run", "--workload",
                        "mlp_b.bulk", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=spec.ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
