"""The program's own spans in the benchmark: the breakdown's split of idle
time over them, the four readers of ``Window.program``, and a traced CPU
rehearsal through ``program_spans.py``."""
from __future__ import annotations

import json
import shutil
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

import program_spans
from fedbench import harness, rehearsal, tracing

READERS = ("host_transfer_ms", "host_transfer_mib", "batch_pack_ms",
           "useful_row_share")


def _reader(name):
    return harness._module(harness.BENCH_DIR / "metrics" / f"{name}.py",
                           f"t_reader_{name}")


def _fake_learner():
    noop = lambda *a, **kw: None                  # noqa: E731
    return SimpleNamespace(
        client_deltas=noop, client_delta=noop, apply=noop,
        eval_perplexity=noop, version=0,
        dataset=SimpleNamespace(client_batches=noop))


@pytest.mark.parametrize("annotate", [True, False])
def test_the_tracer_is_on_only_while_a_traced_window_is_open(annotate):
    from repro import spans
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    with program_spans.program_tracing():
        assert (jax.profiler.start_trace, jax.profiler.stop_trace) == \
            (start, stop)                         # the profiler is left alone
        rec = harness.Recorder(_fake_learner(), annotate=annotate)
        assert not spans.enabled()
        spans.enable()
        spans.count("client.rows_real", 7)        # before the window
        spans.disable()
        rec.in_window = True
        assert spans.enabled() is annotate
        if annotate:
            assert spans.snapshot() == {"spans": {}, "counters": {}}
        rec.in_window = False
        assert not spans.enabled()
    assert harness.Recorder is not type(rec)
    spans.reset()


def test_program_metrics_are_the_benchmark_entries_they_stand_for():
    """Each entry has a ``per_layer`` entry's keys, names a layer and
    cells the benchmark has, and equals BENCHMARK.json's entry where that
    lists the metric."""
    bench = json.loads((harness.BENCH_DIR.parents[1] /
                        "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = {c["name"] for c in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    keys = {"name", "unit", "better", "source", "layer", "moves",
            "workloads"}
    for m in program_spans.PROGRAM_METRICS:
        assert set(m) == keys
        assert m["name"] in READERS
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert m["source"] in ("host_clock", "program_counter")
        if m["name"] in listed:
            assert listed[m["name"]] == m
    layers = {m["layer"] for m in bench["per_layer"]}
    assert {m["layer"] for m in program_spans.PROGRAM_METRICS} - layers \
        == {program_spans.LAYER}


def test_a_gap_goes_to_the_program_span_inside_the_harness_span():
    """Window 0-100 ns, device busy 10-40 and 80-90; the host copies the
    deltas (50-60) and waits on the update (60-70) inside the harness's
    apply span (45-75)."""
    t = tracing.Trace(
        host=[(tracing.WINDOW_SPAN, 0.0, 100.0),
              ("learner.client_update", 0.0, 45.0),
              ("client.to_host", 40.0, 44.0),
              ("learner.apply", 45.0, 75.0),
              ("server.to_device", 50.0, 60.0),
              ("server.update", 60.0, 70.0)],
        ops={0: [("fusion.1", 10.0, 40.0), ("fusion.2", 80.0, 90.0)]})
    idle = dict(tracing.reduce(t, {}).idle_by_span)
    assert idle == pytest.approx({
        "learner.client_update": 10e-9 + 1e-9,    # 0-10, 44-45
        "client.to_host": 4e-9,
        "learner.apply": 5e-9 + 5e-9,             # 45-50, 70-75
        "server.to_device": 10e-9,
        "server.update": 10e-9,
        "engine": 5e-9 + 10e-9})                  # 75-80, 90-100


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_program_spans(name):
    read = _reader(name).read
    w = harness.Window(window_s=30.0, updates=20)
    assert read(w) is None                        # a program with no tracer
    w.program = {"spans": {}, "counters": {}}
    assert read(w) is None


@pytest.mark.parametrize("name,want", [
    ("host_transfer_ms", 1000.0 * (0.5 + 1.5 + 0.25 + 0.75) / 10),
    ("host_transfer_mib", (3 + 4 + 1 + 2) * 2 ** 20 / 2 ** 20 / 10),
    ("batch_pack_ms", 1000.0 * 0.2 / 10),
    ("useful_row_share", 100.0 * 21 / 64)])
def test_readers_of_a_fake_program_window(name, want):
    mib = 2 ** 20
    w = harness.Window(window_s=30.0, updates=10)
    w.program = {
        "spans": {
            "client.pack": {"s": 0.2, "calls": 10, "bytes": 0},
            "client.to_device": {"s": 0.5, "calls": 10, "bytes": 3 * mib},
            "client.to_host": {"s": 1.5, "calls": 10, "bytes": 4 * mib},
            "server.history_to_host": {"s": 0.25, "calls": 10,
                                       "bytes": 1 * mib},
            "server.to_device": {"s": 0.75, "calls": 10, "bytes": 2 * mib},
            "client.wait": {"s": 9.0, "calls": 10, "bytes": 0},
            "server.update": {"s": 3.0, "calls": 10, "bytes": 0}},
        "counters": {"client.rows_real": 21, "client.rows_computed": 64}}
    assert _reader(name).read(w) == pytest.approx(want)


@pytest.mark.parametrize("name", ["charlm-sync", "charlm-async"])
def test_traced_rehearsal_with_program_spans(name):
    """Run in a copy of the benchmark's root, so that its trace directory
    is its own."""
    from repro import spans
    cell = rehearsal.tiny_cell(name)
    with tempfile.TemporaryDirectory() as d:
        bench = Path(d) / "benchmarks" / "chip"
        bench.mkdir(parents=True)
        shutil.copytree(harness.BENCH_DIR / "metrics", bench / "metrics")
        out, w = program_spans.drive(
            cell, 3_000_000_321, 1.0, t_start=time.perf_counter(),
            platform="cpu", peaks=rehearsal.CPU_PEAKS, bench_dir=bench,
            log=lambda s: None)
        lines = (Path(d) / "results" / "fedbench" / name /
                 "program_spans.jsonl").read_text().splitlines()
    assert out["correct"] is True
    assert not spans.enabled()
    got = {m: out["metrics"][m]["value"] for m in READERS}
    for m in ("data_synth_ms", "engine_ms", "window_compiles"):
        assert m in out["metrics"]
    assert got["host_transfer_mib"] > 0 and got["host_transfer_ms"] > 0
    assert 0 < got["useful_row_share"] <= 100
    # the program's real rows are the benchmark's own count of real tokens
    rows = w.program["counters"]["client.rows_real"]
    assert rows * cell.config["seq_len"] == w.tokens
    recs = [json.loads(x) for x in lines]
    assert recs and all(set(r) == {"name", "start", "end", "parent",
                                   "update", "bytes"} for r in recs)
    calls = {n: s["calls"] for n, s in w.program["spans"].items()}
    assert calls["server.update"] == w.updates
    assert calls["server.eval"] == w.updates
    if name == "charlm-sync":
        assert calls["client.pack"] == calls["client.to_device"] \
            == w.updates
    else:
        assert calls["client.pack"] == sum(
            1 for r in recs if r["name"] == "client.to_host")
