"""The traced rehearsal (per-layer line) and the surrogate replay of the
engine's schedule, on the CPU at a tiny size."""
from __future__ import annotations

import pytest

from fedbench import harness, rehearsal
from test_fedbench_rehearsal import _contract


def test_traced_rehearsal_prints_the_per_layer_line():
    cell = rehearsal.tiny_cell("charlm-async")
    line = _contract(rehearsal.rehearse(cell, trace=True),
                     {m["name"] for m in cell.per_layer}, cell)
    # host-clock metrics read on the CPU; device-trace ones need a TPU
    for m in ("mfu", "data_synth_ms", "engine_ms", "window_compiles"):
        assert m in line["metrics"]
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert line["metrics"]["data_synth_ms"]["value"] > 0


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_surrogate_replay_equals_what_the_real_learner_receives(mode):
    """With dropouts frequent enough that cohort sizes vary."""
    name = "charlm-sync" if mode == "sync" else "charlm-async"
    cell = rehearsal.tiny_cell(name)
    cell.traffic = dict(cell.traffic, federated=dict(
        cell.traffic["federated"], concurrency=6, aggregation_goal=6,
        dropout_rate=0.3))
    P = harness._program()
    spec, cfg = harness.build_spec(cell, P)
    n = 12
    sched = harness.replay(spec, P, n)
    s = spec.replace(run=harness.replace(spec.run, max_rounds=n))
    learner = harness._learner(cell, s, cfg, P, 1)
    rec = harness.Recorder(learner, annotate=False)
    P["Experiment"](s, learner=learner).run()
    got = [(u["n"], u["mean_staleness"]) for u in rec.updates]
    assert [len(u["contrib"]) for u in rec.updates] == [n for n, _ in got]
    assert [k for k, _ in got] == [k for k, _ in sched]
    assert [s for _, s in got] == pytest.approx([s for _, s in sched])
    if mode == "sync":
        assert len({k for k, _ in sched}) > 1
    else:
        assert any(st > 0 for _, st in sched)
