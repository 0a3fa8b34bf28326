"""A reduced-size rehearsal of every cell on the CPU: the whole run
(replay, warm-up, window, reference, check) and the last-line contract."""
from __future__ import annotations

import json

import pytest

from fedbench import harness, rehearsal

BENCHMARK = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _contract(out, names, cell):
    line = json.loads(json.dumps(out))
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert isinstance(line["correct"], bool)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) <= names
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    assert line["checks"]["schedule_mismatches"]["value"] == 0
    return line


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_prints_the_end_to_end_line(name):
    cell = rehearsal.tiny_cell(name)
    line = _contract(rehearsal.rehearse(cell), {m["name"] for m in
                                                cell.end_to_end}, cell)
    for m in ("client_tokens_per_s", "setup_s"):
        assert line["metrics"][m]["value"] > 0
    assert "breakdown" not in line
