"""The comparison fails what it must: the control (the reference one
precision lower, in the program's place) and a planted fault on the timed
path each come out not correct, and the program as it is comes out
correct. Each case drives a whole short run of the cell on the CPU (Pallas
in interpret mode) in a process of its own; the four-chip cell runs on
four virtual CPU devices. On the chip, ``python3 -m bench.run ...
--control`` reads the same numbers at the cell's own size."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import spec

CELLS = ["mlp_b.bulk", "rnn_b.bulk", "mlp_b_4chip.bulk"]


def _drive(cell: str, fault: str, control: bool = False) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if spec.find_cell(cell).chips > 1:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    cmd = [sys.executable, "-m", "bench.tests.drive", cell, "1", fault]
    p = subprocess.run(cmd + (["--control"] if control else []),
                       cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_program_correct_and_control_not(cell):
    res = _drive(cell, "none", control=True)
    assert res["correct"], res["checks"]
    assert not res["control"]["correct"], res["control"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced_is_caught(cell):
    res = _drive(cell, "altered")
    assert not res["correct"], res["checks"]
    assert res["checks"]["gap"]["value"] > res["checks"]["gap"]["limit"]
