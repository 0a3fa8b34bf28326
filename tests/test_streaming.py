"""Streaming telemetry subsystem (PR 6): constant-memory folds must
reproduce the materialized path — summaries **bit for bit** (exact
summation on every schedule, static and diurnal), the reservoir sample a
pure function of (seed, global session index) invariant to chunking,
lane packing and worker count — plus the ExactSum machinery itself."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.federated.runtime as rt
from repro.api import Environment, Experiment, ExperimentSpec, ModelRef, sweep
from repro.configs import FederatedConfig, RunConfig
from repro.core.estimator import CarbonEstimator, ExactSum, exact_sum
from repro.core.network import NetworkEnergyModel
from repro.core.profiles import FLEET
from repro.core.streaming import StreamedLog, StreamingAccumulator
from repro.core.telemetry import OUTCOMES, SessionBatch, TaskLog
from repro.federated.events import reservoir_keys

_ENVS = (Environment(),
         Environment(download_bps=20e6, upload_bps=5e6,
                     network=NetworkEnergyModel(e_access_nj=80.0),
                     fleet=FLEET[:3], pue=1.3,
                     carbon_intensity={"WORLD": 300.0, "US": 100.0}),
         Environment.preset("diurnal"))

_MODES = ("sync", "async", "carbon-aware")


def _spec(mode: str, conc: int, goal_frac: float, seed: int,
          max_rounds: int, env_idx: int = 0, telemetry: str = "full",
          sample: int = 100, dropout: float = 0.05) -> ExperimentSpec:
    return ExperimentSpec(
        model=ModelRef("paper-charlm"),
        federated=FederatedConfig(
            mode=mode, concurrency=conc,
            aggregation_goal=max(1, int(conc * goal_frac)),
            seed=seed, dropout_rate=dropout),
        run=RunConfig(target_perplexity=175.0, max_rounds=max_rounds,
                      telemetry=telemetry, telemetry_sample=sample),
        environment=_ENVS[env_idx % len(_ENVS)], learner="surrogate")


# ------------------------------------------------------------------ ExactSum
def test_exact_sum_matches_fsum():
    rng = np.random.default_rng(0)
    for scale in (1.0, 1e-12, 1e150):
        x = rng.standard_normal(5000) * scale
        x[::7] *= 1e9           # mixed magnitudes force cancellation error
        assert exact_sum(x) == math.fsum(x.tolist())


def test_exact_sum_chunking_and_merge_are_bit_exact():
    rng = np.random.default_rng(1)
    x = rng.standard_normal(10_000) * np.exp(rng.uniform(-40, 40, 10_000))
    whole = exact_sum(x)
    for nchunks in (2, 3, 7, 100):
        acc = ExactSum()
        for part in np.array_split(x, nchunks):
            acc.add(part)
        assert acc.value() == whole
    # merge of independent accumulators, any order
    a, b = ExactSum().add(x[:777]), ExactSum().add(x[777:])
    assert b.merge(a).value() == whole
    # permutation invariance (true exactness, not pairwise-tree luck)
    assert exact_sum(x[rng.permutation(len(x))]) == whole


def test_exact_sum_edges():
    assert exact_sum(np.zeros(5)) == 0.0
    assert ExactSum().value() == 0.0
    assert exact_sum(np.asarray([1e308, 1e308, -1e308])) == 1e308
    assert exact_sum(np.asarray([1.0, 2.0 ** -60, -1.0])) == 2.0 ** -60
    with pytest.raises(ValueError):
        exact_sum(np.asarray([1.0, np.nan]))


# -------------------------------------------------------- streaming parity
@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_streaming_matches_full_property(seed0):
    """Random specs x all three modes x static/diurnal envs: the
    streaming summary equals the materialized one bit for bit, on the
    serial AND the lane-batched path (exact summation makes even the
    diurnal schedules exact, beating the <=1e-9 contract)."""
    rng = np.random.default_rng(seed0)
    specs_f, specs_s = [], []
    for mode in _MODES:
        kw = dict(mode=mode, conc=int(rng.integers(8, 48)),
                  goal_frac=float(rng.uniform(0.3, 1.0)),
                  seed=int(rng.integers(0, 2 ** 31)),
                  max_rounds=int(rng.integers(5, 30)),
                  env_idx=int(rng.integers(len(_ENVS))),
                  dropout=float(rng.choice([0.0, 0.05, 0.3])))
        specs_f.append(_spec(telemetry="full", **kw))
        specs_s.append(_spec(telemetry="streaming", **kw))
    full = [Experiment(s).run() for s in specs_f]
    stream = [Experiment(s).run() for s in specs_s]
    lanes = sweep(specs_s, workers=1, vectorize=True)
    for sf, ss, sl in zip(full, stream, lanes):
        a, b, c = sf.summary(), ss.summary(), sl.summary()
        assert a == b, {k: (a[k], b[k]) for k in a if a[k] != b[k]}
        assert a == c, {k: (a[k], c[k]) for k in a if a[k] != c[k]}
        assert isinstance(ss.log, StreamedLog)
        assert sf.log.participation() == ss.log.participation()
        assert sf.log.mean_staleness() == ss.log.mean_staleness()
        assert sf.log.completed_sessions() == ss.log.completed_sessions()
        tb_f, tb_s = sf.log.total_bytes(), ss.log.total_bytes()
        for k in tb_f:       # exact vs pairwise sums: ulp-level agreement
            assert tb_s[k] == pytest.approx(tb_f[k], rel=1e-12)


# --------------------------------------------------- reservoir determinism
@settings(max_examples=4, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=7, max_value=200))
def test_reservoir_invariant_to_chunking_and_lanes(seed0, chunk):
    """The retained session set is a pure function of (seed, global
    index): identical across dispatch chunk sizes and serial vs
    lane_loop, for every mode — and it IS the bottom-k of
    events.reservoir_keys."""
    rng = np.random.default_rng(seed0)
    for mode in _MODES:
        kw = dict(mode=mode, conc=int(rng.integers(8, 40)),
                  goal_frac=float(rng.uniform(0.4, 1.0)),
                  seed=int(rng.integers(0, 2 ** 31)),
                  max_rounds=int(rng.integers(4, 20)),
                  env_idx=int(rng.integers(len(_ENVS))),
                  telemetry="streaming", sample=int(rng.integers(5, 60)))
        spec = _spec(**kw)
        serial = Experiment(spec).run()
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(rt, "_DISPATCH_CHUNK", chunk)
            chunked = Experiment(spec).run()
        lane = sweep([spec, _spec(mode=mode, conc=9, goal_frac=1.0,
                                  seed=3, max_rounds=5,
                                  telemetry="streaming")],
                     workers=1, vectorize=True)[0]
        idx_serial = serial.log._acc.sample_indices()
        assert np.array_equal(idx_serial, chunked.log._acc.sample_indices())
        assert np.array_equal(idx_serial, lane.log._acc.sample_indices())
        # derived bottom-k check against the key stream itself
        n = serial.log.n_sessions
        keys = reservoir_keys(spec.federated.seed, np.arange(n))
        k = min(n, spec.run.telemetry_sample)
        expect = np.sort(np.lexsort((np.arange(n), keys))[:k])
        assert np.array_equal(idx_serial, expect)
        # the sampled columns agree row-for-row across paths
        a, b = serial.log.columns(), lane.log.columns()
        for f in ("client_id", "start_t", "end_t", "outcome", "staleness"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), (mode, f)


def test_reservoir_invariant_to_workers():
    specs = [_spec("async", 30, 0.8, s, 12, telemetry="streaming",
                   sample=40) for s in (0, 9)]
    r1 = sweep(specs, workers=1, vectorize=True)
    r2 = sweep(specs, workers=2, vectorize=True)
    for a, b in zip(r1, r2):
        assert a.summary() == b.summary()
        assert np.array_equal(a.log._acc.sample_indices(),
                              b.log._acc.sample_indices())
        assert np.array_equal(a.log.columns().client_id,
                              b.log.columns().client_id)


def test_reservoir_covers_population_when_large_enough():
    """sample >= n_sessions: columns() is the whole population, equal
    session-for-session to the materialized log (decoded — the two vocab
    orderings may differ)."""
    kw = dict(mode="async", conc=20, goal_frac=0.8, seed=4, max_rounds=8,
              env_idx=2)
    full = Experiment(_spec(telemetry="full", **kw)).run()
    stream = Experiment(_spec(telemetry="streaming", sample=10 ** 6,
                              **kw)).run()
    assert not stream.log.sampled
    assert full.log.columns().to_sessions() == \
        stream.log.columns().to_sessions()
    assert stream.log.sessions == full.log.sessions


# ------------------------------------------------------- log surface edges
def test_empty_streamed_log():
    est = CarbonEstimator()
    log = StreamedLog(est, ("pixel-7",), ("US",), seed=0, sample=8)
    assert log.n_sessions == 0 and len(log) == 0
    assert not log.sampled
    assert log.participation() == {}
    assert log.mean_staleness() == 0.0
    assert log.total_bytes() == {"up": 0.0, "down": 0.0}
    assert len(log.columns()) == 0
    bd = est.estimate(log)
    assert bd.total_kg == 0.0
    log.duration_s = 3600.0
    assert est.estimate(log).server_kg > 0.0


def test_streamed_log_rejects_foreign_estimator():
    env = Environment.preset("diurnal")
    log = Experiment(_spec("async", 16, 1.0, 0, 5, env_idx=2,
                           telemetry="streaming")).run().log
    other = Environment(pue=2.0).estimator()
    with pytest.raises(ValueError):
        other.estimate(log)
    # an equal estimator re-reads the sums fine
    assert env.estimator().estimate(log).total_kg > 0.0


def test_streamed_log_log_session_and_unknown_vocab():
    est = CarbonEstimator()
    log = StreamedLog(est, ("pixel-7",), ("US",), seed=0, sample=8)
    from repro.core.telemetry import ClientSession
    s = ClientSession(client_id=1, round_idx=0, device="pixel-7",
                      country="US", download_s=1.0, compute_s=2.0,
                      upload_s=1.0, bytes_down=10.0, bytes_up=5.0,
                      start_t=0.0, end_t=4.0, outcome="completed")
    log.log_session(s)
    assert log.n_sessions == 1
    assert log.columns().to_sessions() == [s]
    bad = ClientSession(client_id=2, round_idx=0, device="galaxy-s21",
                        country="US", download_s=1.0, compute_s=1.0,
                        upload_s=1.0, bytes_down=1.0, bytes_up=1.0,
                        start_t=0.0, end_t=3.0, outcome="completed")
    with pytest.raises(ValueError):
        log.log_session(bad)


def test_breakdown_table_consistent_with_exact_totals():
    """The grouped (country, segment, outcome) table is float64 running
    sums (documented as not bit-pinned); its totals still agree with the
    exact component sums to ~1e-9 and its counts/bytes exactly."""
    res = Experiment(_spec("carbon-aware", 40, 0.8, 2, 15, env_idx=2,
                           telemetry="streaming")).run()
    log = res.log
    rows = log.breakdown_table()
    assert rows and all(r["country"] and r["outcome"] in OUTCOMES
                        for r in rows)
    comp = log.carbon_components(log._acc.estimator)
    total = (comp["client_compute_kg"] + comp["upload_kg"]
             + comp["download_kg"])
    assert sum(r["co2e_kg"] for r in rows) == pytest.approx(total, rel=1e-9)
    # the contributed/wasted split partitions the same rows
    assert comp["ok_kg"] + comp["waste_kg"] == pytest.approx(total, rel=1e-9)
    assert sum(r["count"] for r in rows) == log.n_sessions
    tb = log.total_bytes()
    assert sum(r["bytes"] for r in rows) == pytest.approx(
        tb["up"] + tb["down"], rel=1e-9)
    # diurnal env: sessions actually land in distinct schedule segments
    assert len({r["segment"] for r in rows}) > 1


def test_run_config_validates_telemetry():
    with pytest.raises(AssertionError):
        RunConfig(telemetry="columnar")
    with pytest.raises(AssertionError):
        RunConfig(telemetry_sample=0)


def test_streaming_spec_roundtrip_reproduces_summary(tmp_path):
    spec = _spec("async", 24, 0.8, 1, 10, telemetry="streaming", sample=32)
    p = tmp_path / "s.json"
    spec.save(str(p))
    spec2 = ExperimentSpec.load(str(p))
    assert spec2.run.telemetry == "streaming"
    assert Experiment(spec).run().summary() == \
        Experiment(spec2).run().summary()
