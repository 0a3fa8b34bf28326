"""Engine snapshots (PR 9): a checkpointed run killed at an arbitrary
round and resumed must reproduce the uninterrupted run **bit for bit** —
summary scalars AND session columns — on every strategy × telemetry ×
environment combination. Plus the serialization primitives underneath
(ExactSum state round-trip), the forward-compat guards (unknown snapshot
version, wrong-spec resume), and the test-only crash injector that
drives the property tests and the fault-tolerant sweep suite."""
import json
import math
import os
import pathlib
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import Environment, Experiment, ExperimentSpec, ModelRef
from repro.configs import FederatedConfig, RunConfig
from repro.core.estimator import ExactSum
from repro.core.network import NetworkEnergyModel
from repro.core.profiles import FLEET
from repro.core.snapshot import (InjectedCrash, SNAPSHOT_VERSION,
                                 _CrashInjector, load_snapshot)
from repro.core.telemetry import _ACC_DTYPES

_ENVS = (Environment(),
         Environment(download_bps=20e6, upload_bps=5e6,
                     network=NetworkEnergyModel(e_access_nj=80.0),
                     fleet=FLEET[:3], pue=1.3,
                     carbon_intensity={"WORLD": 300.0, "US": 100.0}),
         Environment.preset("diurnal"))

_MODES = ("sync", "async", "carbon-aware")


def _spec(mode: str, seed: int = 99, env_idx: int = 0,
          telemetry: str = "full", conc: int = 8,
          max_rounds: int = 20) -> ExperimentSpec:
    # target_perplexity=1.0 is unreachable: runs always go the full
    # max_rounds, so an injected crash round < max_rounds always fires
    return ExperimentSpec(
        model=ModelRef("paper-charlm"),
        federated=FederatedConfig(mode=mode, concurrency=conc,
                                  aggregation_goal=max(1, int(conc * 0.8)),
                                  seed=seed, dropout_rate=0.05),
        run=RunConfig(target_perplexity=1.0, max_rounds=max_rounds,
                      telemetry=telemetry, telemetry_sample=50),
        environment=_ENVS[env_idx % len(_ENVS)], learner="surrogate")


def _assert_same_columns(got, want):
    assert got.device_names == want.device_names
    assert got.country_names == want.country_names
    for f in _ACC_DTYPES:
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype, f
        assert np.array_equal(a, b), f


def _crash_and_resume(monkeypatch, tmp_path, spec, crash_at, every=4):
    """Run with checkpointing until the injected crash, then resume."""
    path = str(tmp_path / "snap.npz")
    monkeypatch.setenv("REPRO_CRASH_ROUND", str(crash_at))
    monkeypatch.setenv("REPRO_CRASH_KIND", "raise")
    with pytest.raises(InjectedCrash):
        Experiment(spec).run(checkpoint_path=path,
                             checkpoint_every_rounds=every)
    monkeypatch.delenv("REPRO_CRASH_ROUND")
    assert os.path.exists(path)
    return path, Experiment.resume(path)


# -------------------------------------------------- bit-for-bit resume
@pytest.mark.parametrize("telemetry", ("full", "streaming"))
@pytest.mark.parametrize("mode", _MODES)
@settings(max_examples=3, deadline=None)
@given(st.integers(min_value=6, max_value=18),
       st.integers(min_value=0, max_value=10_000))
def test_killed_and_resumed_run_is_bit_exact(mode, telemetry, crash_at,
                                             seed0):
    """The property the whole subsystem exists for: kill at a random
    round, resume from the last checkpoint, get the identical experiment
    — summaries `==` and every session column array_equal (dtype
    included) — for static and diurnal schedules alike."""
    rng = np.random.default_rng(seed0)
    spec = _spec(mode, seed=int(rng.integers(0, 2 ** 31)),
                 env_idx=int(rng.integers(len(_ENVS))), telemetry=telemetry)
    base = Experiment(spec).run()
    assert base.rounds == spec.run.max_rounds     # crash round was live
    # fresh per example: hypothesis runs many examples per test call
    with pytest.MonkeyPatch.context() as mp, \
            tempfile.TemporaryDirectory() as tmp:
        _, res = _crash_and_resume(mp, pathlib.Path(tmp), spec, crash_at)
        assert res.summary() == base.summary()
        _assert_same_columns(res.log.columns(), base.log.columns())


def test_resume_keeps_checkpointing_to_the_same_file(monkeypatch,
                                                     tmp_path):
    """By default `Experiment.resume` continues the checkpoint cadence it
    found in the snapshot, so a resumed run that crashes AGAIN loses at
    most `every` rounds — the file must advance past the crash round."""
    spec = _spec("sync")
    path, res = _crash_and_resume(monkeypatch, tmp_path, spec,
                                  crash_at=10, every=4)
    assert res.rounds == spec.run.max_rounds
    final = load_snapshot(path)
    assert final.round_idx > 10
    assert final.every == 4


def test_checkpoint_file_round_trips_spec(monkeypatch, tmp_path):
    """The spec travels inside the header: a loaded snapshot rebuilds an
    ExperimentSpec equal to the producer's, so `resume(path)` needs no
    other argument."""
    spec = _spec("async", env_idx=2, telemetry="streaming")
    path, _ = _crash_and_resume(monkeypatch, tmp_path, spec, crash_at=9)
    snap = load_snapshot(path)
    assert snap.spec().to_dict() == spec.to_dict()
    assert snap.spec_hash == spec.content_hash()


# ---------------------------------------------------- guards and errors
def test_unknown_snapshot_version_is_a_clear_error(monkeypatch, tmp_path):
    spec = _spec("sync")
    path, _ = _crash_and_resume(monkeypatch, tmp_path, spec, crash_at=8)
    with np.load(path, allow_pickle=False) as data:
        arrays = {k: data[k] for k in data.files if k != "header"}
        header = json.loads(str(data["header"][()]))
    header["version"] = 999
    np.savez(path, header=np.asarray(json.dumps(header)), **arrays)
    with pytest.raises(ValueError) as ei:
        load_snapshot(path)
    # the error names BOTH the found and the supported version
    assert "999" in str(ei.value)
    assert str(SNAPSHOT_VERSION) in str(ei.value)


def test_non_snapshot_file_is_rejected(tmp_path):
    path = str(tmp_path / "junk.npz")
    np.savez(path, x=np.arange(3))
    with pytest.raises(ValueError, match="no header"):
        load_snapshot(path)
    np.savez(path, header=np.asarray(json.dumps({"format": "other"})))
    with pytest.raises(ValueError, match="format tag"):
        load_snapshot(path)


def test_wrong_spec_resume_names_both_hashes(monkeypatch, tmp_path):
    spec = _spec("sync", seed=7)
    path, _ = _crash_and_resume(monkeypatch, tmp_path, spec, crash_at=8)
    other = _spec("sync", seed=8)
    with pytest.raises(ValueError) as ei:
        Experiment(other).run(resume_from=path)
    msg = str(ei.value)
    assert spec.content_hash() in msg       # the checkpoint's spec
    assert other.content_hash() in msg      # the resuming spec
    # and the matching spec still resumes fine
    assert Experiment(spec).run(resume_from=path).rounds \
        == spec.run.max_rounds


def test_checkpoint_knob_validation():
    spec = _spec("sync")
    with pytest.raises(ValueError, match="checkpoint_every_rounds"):
        Experiment(spec).run(checkpoint_path="/tmp/never.npz")
    real = ExperimentSpec(model=ModelRef("paper-charlm", reduced=True),
                          federated=FederatedConfig(mode="sync"),
                          run=RunConfig(max_rounds=1), learner="real")
    with pytest.raises(ValueError, match="surrogate"):
        Experiment(real).run(checkpoint_path="/tmp/never.npz",
                             checkpoint_every_rounds=1)


# -------------------------------------------------------- crash injector
def test_crash_injector_env_arming(tmp_path):
    assert _CrashInjector.from_env({}) is None
    ci = _CrashInjector.from_env({"REPRO_CRASH_ROUND": "5"})
    assert ci.at_round == 5 and ci.kind == "raise"
    ci.tick(4)                               # below the trigger: no-op
    with pytest.raises(InjectedCrash, match="round 5"):
        ci.tick(5)
    # REPRO_CRASH_SEED targets one spec of a sweep
    env = {"REPRO_CRASH_ROUND": "5", "REPRO_CRASH_SEED": "42",
           "REPRO_CRASH_KIND": "kill"}
    assert _CrashInjector.from_env(env, seed=41) is None
    armed = _CrashInjector.from_env(env, seed=42)
    assert armed is not None and armed.kind == "kill"


def test_crash_injector_once_marker_disarms_the_retry(tmp_path):
    marker = str(tmp_path / "crashed.once")
    ci = _CrashInjector(3, "raise", once_path=marker)
    with pytest.raises(InjectedCrash):
        ci.tick(3)
    assert os.path.exists(marker)            # created BEFORE crashing
    _CrashInjector(3, "raise", once_path=marker).tick(7)   # retry survives


# --------------------------------------------------- ExactSum round-trip
def test_exact_sum_state_round_trip():
    """state()/from_state() must preserve the *exact* accumulator — the
    restored object keeps folding and stays bit-identical to one that
    never stopped, including negative totals and huge exponent spread."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal(4000) * np.exp(rng.uniform(-60, 60, 4000))
    a = ExactSum().add(x[:1500])
    b = ExactSum.from_state(a.state())
    assert b.value() == a.value()
    assert b.add(x[1500:]).value() == ExactSum().add(x).value() \
        == math.fsum(x.tolist())
    neg = ExactSum().add(np.asarray([-1e300, 1.0, -2.0 ** -40]))
    assert ExactSum.from_state(neg.state()).value() == neg.value()
    empty = ExactSum()
    assert ExactSum.from_state(empty.state()).value() == 0.0
    # states are JSON-safe (that is how they travel in the header)
    assert ExactSum.from_state(
        json.loads(json.dumps(a.state()))).value() == a.value()


def test_exact_sum_state_version_guard():
    bad = dict(ExactSum().state(), version=99)
    with pytest.raises(ValueError, match="99"):
        ExactSum.from_state(bad)
