"""Unit tests of the chip benchmark's yardstick, on the CPU: the trace
reduction, the copy of the data generator, the reference's weights, the
comparison and the shape of BENCHMARK.json."""
from __future__ import annotations

import importlib.util
import json
import re
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import datagen, harness, tracing
from fedbench.rehearsal import TINY

BENCH = harness.BENCH_DIR
SEED = 3_000_000_123          # above 2**31: seeds may exceed 32 bits


# ------------------------------------------------------------ trace
def _trace():
    """Window 0-100 ns; device ops at 10-30 and 25-40 (overlap) and 60-70;
    host spans: client update 0-50 with data synth 0-8 inside it, apply
    50-75, nothing (the engine) 75-100."""
    return tracing.Trace(
        host=[(tracing.WINDOW_SPAN, 0.0, 100.0),
              ("learner.client_update", 0.0, 50.0),
              ("data.synth", 0.0, 8.0),
              ("learner.apply", 50.0, 75.0)],
        ops={0: [("fusion.1", 10.0, 30.0), ("fusion.2", 25.0, 40.0),
                 ("reduce.3", 60.0, 70.0)]},
        modules={0: [("jit_client_update(7)", 10.0, 40.0),
                     ("jit_weighted_mean_deltas(2)", 60.0, 65.0),
                     ("jit__lambda(9)", 65.0, 70.0)]})


def test_trace_reduction_idle_share_and_program_time():
    r = tracing.reduce(_trace(), {"client_update": ["client_update"],
                                  "server_eval": ["weighted_mean_deltas",
                                                  "lambda"]})
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)            # 10-40 and 60-70
    assert r.layer_s["client_update"] == pytest.approx(30e-9)
    assert r.layer_s["server_eval"] == pytest.approx(10e-9)
    assert dict(r.top_ops)["fusion.1"] == pytest.approx(20e-9)


def test_trace_reduction_attributes_gaps_to_the_innermost_host_span():
    r = tracing.reduce(_trace(), {})
    idle = dict(r.idle_by_span)
    # gaps: 0-10 (data.synth 0-8 inside the client update 0-10), 40-60
    # (client update 40-50, apply 50-60), 70-100 (apply 70-75, then no
    # span, so the engine)
    assert idle == pytest.approx({"data.synth": 8e-9,
                                  "learner.client_update": 12e-9,
                                  "learner.apply": 15e-9,
                                  "engine": 25e-9})


def test_op_names_drop_the_hlo_text():
    assert tracing.op_name("%fusion.12 = f32[8]{0} fusion(%p), kind=kLoop"
                           ) == "fusion.12"
    assert tracing.op_name("copy.3") == "copy.3"


def test_trace_reduction_needs_a_window_and_device_events():
    t = _trace()
    assert tracing.reduce(tracing.Trace(host=t.host), {}) is None
    assert tracing.reduce(tracing.Trace(ops=t.ops), {}) is None


def test_trace_loader_reads_host_spans_of_a_recorded_trace():
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("learner.apply"):
                f(x).block_until_ready()
        jax.profiler.stop_trace()
        t = tracing.load(d, (tracing.WINDOW_SPAN, "learner.apply"))
    names = [n for n, _, _ in t.host]
    assert names.count(tracing.WINDOW_SPAN) == 1
    assert names.count("learner.apply") == 1
    (_, a, b), = [s for s in t.host if s[0] == "learner.apply"]
    (_, wa, wb), = [s for s in t.host if s[0] == tracing.WINDOW_SPAN]
    assert wa <= a < b <= wb
    assert t.ops == {}      # no TPU plane on the CPU


# ------------------------------------------------------------ data copy
@pytest.mark.parametrize("char_vocab", [0, 32])
def test_data_copy_matches_the_program_generator(char_vocab):
    from repro.data.synthetic import FederatedDataset, client_num_samples
    ds = FederatedDataset(vocab_size=300, seq_len=12, char_vocab=char_vocab,
                          max_word_len=8, seed=5)
    cd = datagen.ClientData(300, 12, 5, char_vocab, 8)
    for cid in (0, 17, 123_456):
        assert datagen.num_samples(cid, 5) == client_num_samples(cid, 5)
        got = ds.client_batches(cid, 4, 2)[:6]
        mine = cd.client_batches(cid, 4, 2, 6)
        assert len(mine) == min(len(got), 6)
        for a, b in zip(got, mine):
            assert a.keys() == b.keys()
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])
        rows = datagen.real_rows(cid, 5, 4, 2, 6)
        assert rows == sum(int(b["mask"][:, 0].sum()) for b in got[:6])
    ev, mine = ds.eval_batch(3, 8), cd.eval_batch(3, 8)
    for k in ev:
        np.testing.assert_array_equal(ev[k], mine[k])


# ------------------------------------------------------------ reference
def _config_module(name):
    spec = importlib.util.spec_from_file_location(
        f"t_{name}", BENCH / "configs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name,family", [("lstm-char-small", "charlm"),
                                         ("smollm-135m", "dense")])
def test_reference_weights_and_loss_match_the_program(name, family):
    from repro.configs.base import model_config_from_dict
    from repro.models import get_model
    tiny = TINY[family]
    model = get_model(model_config_from_dict(dict(tiny)))
    prog, _ = model.init(jax.random.PRNGKey(SEED), dtype=jnp.float32)
    mod = _config_module(name)
    m = dict(tiny, rms_norm_eps=1e-6)
    ref = mod.init(m, SEED)
    assert prog.keys() == ref.keys()
    for k in prog:
        np.testing.assert_array_equal(np.asarray(prog[k]), np.asarray(ref[k]))
    cd = datagen.ClientData(tiny["vocab_size"], 16, 0,
                            tiny.get("char_vocab", 0),
                            tiny.get("max_word_len", 16))
    batch = {k: jnp.asarray(v) for k, v in
             cd.client_batches(3, 4, 1, 1)[0].items()}
    a = float(model.loss(prog, batch)[0])
    b = float(mod.loss(m, ref, batch))
    assert b == pytest.approx(a, rel=1e-5)


def test_reference_config_files_state_what_the_program_resolves():
    from repro.api import ModelRef
    for path in sorted((BENCH / "configs").glob("*.json")):
        c = json.loads(path.read_text())
        cfg = ModelRef.from_dict(c["model_ref"]).resolve()
        for k, v in c["model"].items():
            assert json.loads(json.dumps(getattr(cfg, k))) == v, (path, k)
        assert cfg.param_count() == c["params"]


# ------------------------------------------------------------ comparison
def test_leaf_gap_takes_the_worst_leaf_against_the_median_floor():
    ref = {"a": 10.0, "b": 1.0, "c": 0.0}
    # c's reference norm is 0, so it is measured against the median (1.0)
    assert harness.leaf_gap({"a": 10.0, "b": 1.0, "c": 0.5}, ref) == 0.5
    assert harness.leaf_gap({"a": 11.0, "b": 1.0, "c": 0.0}, ref) == 0.1
    assert harness.leaf_gap({"a": 10.0, "b": 1.0}, ref) == float("inf")
    assert harness.leaf_gap({"a": 10.0, "b": 1.0}, ref, {"a", "b"}) == 0.0


def test_moved_leaves_drop_gradients_below_a_thousandth_of_the_median():
    assert harness.moved_leaves({"a": 1.0, "b": 2.0, "c": 1e-4}) == {"a", "b"}


def test_judge_fails_on_a_number_over_its_limit_or_not_a_number():
    ok, checks = harness.judge({"x": 0.1, "y": 5.0}, {"x": 0.2, "y": None})
    assert ok and checks["y"] == {"value": 5.0, "limit": None}
    assert not harness.judge({"x": 0.3}, {"x": 0.2})[0]
    assert not harness.judge({"x": float("nan")}, {"x": 0.2})[0]


def test_compare_reads_a_state_left_unchanged_as_one():
    answer = {"a": np.array([3.0, 4.0]), "b": np.array([1.0])}
    ref = {"losses": [5.0, 4.0, 3.0], "grad1": {"a": 2.0, "b": 1.0},
           "change": {"a": 0.3, "b": 0.2}, "answers1": [answer]}
    prog = {"losses": [5.0, 5.0, 5.0], "grad1": {"a": 0.0, "b": 0.0},
            "change": {"a": 0.0, "b": 0.0}, "answers1": [answer]}
    got = harness.compare(prog, ref)
    assert got["grad_norm_gap"] == 1.0 and got["change_norm_gap"] == 1.0
    assert got["loss_gap"] == pytest.approx(2.0 / 3.0)
    assert got["client_delta_gap"] == 0.0


def test_answer_gap_reads_a_negated_answer_as_two():
    a = {"w": np.array([3.0, 4.0])}
    assert harness.answer_gap([{"w": -a["w"]}], [a]) == pytest.approx(2.0)
    assert harness.answer_gap([a], [a, a]) == float("inf")


# ------------------------------------------------------------ the file
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_names_files_that_exist_and_metrics_that_read():
    root = harness.ROOT
    b = json.loads((root / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for c in b["configs"]:
        assert NAME.match(c["name"]) and (root / c["file"]).exists()
        assert (BENCH / "configs" / f"{c['name']}.py").exists()
        stated = json.loads((root / c["file"]).read_text())
        assert stated["reduced"] == c["reduced"]
        assert stated["source"] == c["source"]
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert (BENCH / "limits" / f"{w['name']}.json").exists()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
