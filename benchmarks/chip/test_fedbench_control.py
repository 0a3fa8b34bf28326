"""The control at a size a test run holds: the reference itself in
bfloat16 (the precision below the configurations' float32), put in the
program's place, fails the cell's limits, while the program passes them
on the same seed. On the CPU at a tiny size; the chip readings at each
cell's own size are in PERF.md."""
from __future__ import annotations

import json

import pytest

from fedbench import calibrate, harness, rehearsal

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_bf16_control_is_not_correct(name):
    cell = rehearsal.tiny_cell(name)
    rows = calibrate.calibrate(cell, [11], {11}, platform="cpu",
                               emit=lambda s: None)
    got = {r["kind"]: r["numbers"] for r in rows}
    assert harness.judge(got["program"], cell.limits)[0]
    assert not harness.judge(got["control_bf16"], cell.limits)[0]
