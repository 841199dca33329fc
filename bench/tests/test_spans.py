"""The reduction of the program's host spans (``bench/spans.py``)."""

from __future__ import annotations

import json
import pathlib

import pytest

from bench import spans, trace

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
MS = 1e6


def _raw(host_lines, chip_ops):
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [[n, s * MS, d * MS, ""]
                                           for n, s, d in events]}
            for events in host_lines]},
        *({"name": f"/device:TPU:{i}", "lines": [{"name": "XLA Ops", "events": [
            [f"op{k}", s * MS, d * MS, ""] for k, (s, d) in enumerate(ops)]}]}
          for i, ops in enumerate(chip_ops))]}


def test_span_reduction_by_hand():
    """A 10 ms window; chip 0 idle over [0, 1], [3, 6] and [7, 10] ms, chip 1
    busy throughout. The drain line has two rounds (the second runs past
    the window), a group and a wait; a worker line a chunk with its plan
    call; a client line a submit that starts before the window."""
    raw = _raw(
        [[(trace.WINDOW_SPAN, 0, 10),
          ("serve.round", 0.5, 4.5), ("serve.begin", 0.5, 1.0),
          ("sched.wait", 5.0, 0.5), ("serve.round", 5.5, 6.5)],
         [("devices.run", 2.0, 2.0), ("plan.call", 2.0, 0.5),
          ("PjitFunction(copy)", 3.0, 0.1)],
         [("serve.submit", -1.0, 1.2)]],
        [[(1, 2), (6, 1)], [(0, 10)]])
    got = spans.reduce(raw)
    assert got["window_s"] == pytest.approx(0.010)
    # idle, shortest open span: [0, .2] submit, [.2, .5] none, [.5, 1]
    # begin, [3, 4] devices.run (JAX's own copy event is no program
    # span), [4, 5] round, [5, 5.5] wait, [5.5, 6] + [7, 10] second round
    want = {"serve.round": 4.5, "devices.run": 1.0, "serve.begin": 0.5,
            "sched.wait": 0.5, "no program span": 0.3, "serve.submit": 0.2}
    assert got["idle_by_span"] == pytest.approx(
        {k: v * 1e-3 for k, v in want.items()})
    assert list(got["idle_by_span"])[0] == "serve.round"
    assert spans.reduce(raw, chips=1)["idle_by_span"] == got["idle_by_span"]
    s = got["spans"]
    # only the first round lies wholly inside; the union clips the second
    assert s["serve.round"]["count"] == 1
    assert s["serve.round"]["mean_s"] == pytest.approx(0.0045)
    assert s["serve.round"]["busy_s"] == [pytest.approx(0.009)]
    assert s["serve.submit"] == {"count": 0, "mean_s": None,
                                 "busy_s": [pytest.approx(0.0002)]}
    assert s["devices.run"]["busy_s"] == [pytest.approx(0.002)]
    assert s["plan.call"]["mean_s"] == pytest.approx(0.0005)
    assert set(s) == {"serve.round", "serve.begin", "sched.wait",
                      "devices.run", "plan.call", "serve.submit"}


def test_span_reduction_of_a_recorded_trace():
    """A 6 ms slice of a trace recorded on a TPU v5 lite in ``mlp_b.bulk``
    (``--trace 1``): the program's spans on the drain and client lines and
    the chip's operations, kept as the plain structure ``trace.load`` makes.
    The expected numbers were worked out from the events at 1 ns
    resolution, not with ``spans.reduce``."""
    raw = json.loads((FIXTURES / "trace_spans.json").read_text())
    expect = json.loads((FIXTURES / "trace_spans.expected.json").read_text())
    got = spans.reduce(raw, chips=1)
    assert got["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert got["idle_by_span"] == pytest.approx(expect["idle_by_span"],
                                                rel=1e-9)
    assert set(got["spans"]) == set(expect["spans"])
    for name, want in expect["spans"].items():
        assert got["spans"][name]["count"] == want["count"], name
        assert got["spans"][name]["mean_s"] == pytest.approx(
            want["mean_s"], rel=1e-9), name
        assert got["spans"][name]["busy_s"] == pytest.approx(
            want["busy_s"], rel=1e-9), name
    # one drain thread: the rounds cover nearly all of the slice
    assert got["spans"]["serve.round"]["busy_s"][0] > 0.95 * 0.006


def test_span_union_is_per_line():
    """Overlapping spans of one name merge on their own line and add up
    across lines (two stream workers)."""
    raw = _raw(
        [[(trace.WINDOW_SPAN, 0, 10), ("devices.run", 1, 3),
          ("devices.run", 2, 3)],
         [("devices.run", 1, 2)]],
        [[(0, 10)]])
    got = spans.reduce(raw)["spans"]["devices.run"]
    assert got["busy_s"] == [pytest.approx(0.004), pytest.approx(0.002)]
    assert got["count"] == 3
    assert got["mean_s"] == pytest.approx(8 / 3 * 1e-3)


def test_trace_without_program_spans():
    """A program that opens none of the spans (the trace of the older
    recorded slice): no statistics, all idle time unattributed."""
    raw = json.loads((FIXTURES / "trace_slice.json").read_text())
    got = spans.reduce(raw, chips=1)
    assert got["spans"] == {}
    expect = json.loads((FIXTURES / "trace_slice.expected.json").read_text())
    assert got["idle_by_span"] == {"no program span": pytest.approx(
        expect["window_s"] - expect["busy_s"], rel=1e-9)}
