"""The ``cnn_l.bulk`` cell: a whole short run on the CPU is correct, the
control and a planted fault are not, and the required work that its
roofline and peak shares divide by counts each flow's 8 packet rows
through each bank and each bank's tables once per kernel call."""

from __future__ import annotations

from bench import spec, work
from bench.configs import cnn_l
from bench.tests.test_control import _drive

CELL = "cnn_l.bulk"


def test_program_correct_and_control_not():
    res = _drive(CELL, "none", control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert not res["control"]["correct"], res["control"]


def test_answer_altered_where_produced_is_caught():
    res = _drive(CELL, "altered")
    assert not res["correct"], res["checks"]
    assert res["checks"]["gap"]["value"] > res["checks"]["gap"]["limit"]


def test_required_work_counts_packet_rows_and_real_calls():
    cfg = spec.find_cell(CELL).config
    geo, calls = cnn_l.geometry(cfg), cnn_l.kernel_calls(cfg)["fuzzy_lut"]
    chunks, rows = 10, 8
    flows = 4096 * chunks
    # by hand: bank 1 (62 groups of 1 byte → 64, depth 8) and bank 2
    # (64 → 16), each with a bias, over 8 packet rows per flow
    ops1 = 62 * 8 + 62 * 64 + 64
    ops2 = 64 * 8 + 64 * 16 + 16
    io = 4 * ((62 + 64) + (64 + 16))
    tables = 4 * (62 * 256 * 64 + 2 * 62 * 255) + 4 * (
        64 * 256 * 16 + 2 * 64 * 255)
    ops, nbytes = work.kernel_work(geo, calls, flows, 2 * chunks)
    assert (ops1, ops2) == (4528, 1552)
    assert ops == flows * rows * (ops1 + ops2)
    assert nbytes == flows * rows * io + chunks * tables
    # the whole step adds the index stage (4 compares, 3 logit adds per
    # packet) and the logit bias once per flow
    assert work.ops_per_flow(geo) == rows * (ops1 + ops2 + 4 + 3) + 3
