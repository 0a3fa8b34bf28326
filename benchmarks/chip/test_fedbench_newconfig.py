"""A configuration, a traffic mix and a per-layer metric added as new
files and new BENCHMARK.json entries alone, with no edit to a file that
is there, run through the harness."""
from __future__ import annotations

import filecmp
import json
import shutil
import tempfile
from pathlib import Path

from fedbench import harness, rehearsal


def test_new_config_traffic_and_metric_need_no_edit():
    with tempfile.TemporaryDirectory() as d:
        root = Path(d)
        bench = root / "benchmarks" / "chip"
        shutil.copytree(harness.BENCH_DIR, bench,
                        ignore=shutil.ignore_patterns("__pycache__"))
        b = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
        tiny = rehearsal.TINY["charlm"]
        (bench / "configs" / "fixture-charlm.json").write_text(json.dumps(
            dict(json.loads((bench / "configs" / "lstm-char-small.json")
                            .read_text()),
                 name="fixture-charlm", model=tiny,
                 model_ref={"config": tiny}, params=0, cohort=6)))
        shutil.copy(bench / "configs" / "lstm-char-small.py",
                    bench / "configs" / "fixture-charlm.py")
        (bench / "traffic" / "fixture-sync.json").write_text(json.dumps({
            "name": "fixture-sync",
            "federated": {"mode": "sync", "concurrency": 8,
                          "aggregation_goal": 6},
            "environment": None, "population_seed": 3,
            "warm_horizon": 10, "checked_updates": 2}))
        (bench / "limits" / "fixture.sync.json").write_text(json.dumps({
            "loss_gap": 1e-3, "grad_norm_gap": 1e-3,
            "change_norm_gap": 1e-3, "schedule_mismatches": 0}))
        (bench / "metrics" / "updates_in_window.py").write_text(
            "def read(w):\n    return w.updates\n")
        b["configs"].append({"name": "fixture-charlm", "source": "x",
                             "file": "benchmarks/chip/configs/"
                                     "fixture-charlm.json",
                             "reduced": [], "why": "fixture"})
        b["workloads"].append({"name": "fixture.sync",
                               "config": "fixture-charlm",
                               "traffic": "fixture-sync", "chips": 1,
                               "why": "fixture"})
        b["per_layer"].append({"name": "updates_in_window", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine",
                               "moves": "client_tokens_per_s",
                               "workloads": ["fixture.sync"]})
        (root / "BENCHMARK.json").write_text(json.dumps(b))
        cell = harness.load_cell("fixture.sync", root, bench)
        line = rehearsal.rehearse(cell, seed=7, trace=True, bench_dir=bench)
        same = filecmp.dircmp(harness.BENCH_DIR, bench)
        assert not same.diff_files
    assert line["correct"] is True
    assert line["metrics"]["updates_in_window"]["value"] == line["attempted"]
    assert "mfu" not in line["metrics"]      # listed for other cells only
