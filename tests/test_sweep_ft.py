"""Fault-tolerant sweep (PR 9): worker death, wedged workers and raised
failures must cost retries, not the whole sweep. Crash injection
(``repro.core.snapshot._CrashInjector``, armed through REPRO_CRASH_* env
vars that the worker processes inherit) kills real workers mid-run;
these tests prove detection, retry-with-backoff, pack salvage, partial
results and a truthful ``SweepReport`` — and that retried results stay
identical to a crash-free serial run."""
import importlib

import pytest

from repro.api import (Environment, Experiment, ExperimentSpec, ModelRef,
                       sweep)

# the submodule, not the same-named function re-exported by the package
sweep_mod = importlib.import_module("repro.api.sweep")
from repro.api.sweep import SweepReport
from repro.configs import FederatedConfig, RunConfig


def _spec(seed: int, mode: str = "sync", conc: int = 6,
          max_rounds: int = 8, arch: str = "paper-charlm"
          ) -> ExperimentSpec:
    return ExperimentSpec(
        model=ModelRef(arch),
        federated=FederatedConfig(mode=mode, concurrency=conc,
                                  aggregation_goal=max(1, int(conc * 0.8)),
                                  seed=seed),
        run=RunConfig(target_perplexity=1.0, max_rounds=max_rounds),
        environment=Environment(), learner="surrogate")


def _summaries(results):
    return [None if r is None else r.summary() for r in results]


@pytest.fixture
def crash_env(monkeypatch, tmp_path):
    """Arm the crash injector for exactly one spec of a sweep; returns a
    setter so each test picks round/kind/seed. The once-marker lives in
    tmp_path, so the retried attempt succeeds."""
    def arm(at_round, kind, seed, once=True):
        monkeypatch.setenv("REPRO_CRASH_ROUND", str(at_round))
        monkeypatch.setenv("REPRO_CRASH_KIND", kind)
        monkeypatch.setenv("REPRO_CRASH_SEED", str(seed))
        if once:
            monkeypatch.setenv("REPRO_CRASH_ONCE",
                               str(tmp_path / "crash.once"))
    return arm


# ----------------------------------------------------------- clean runs
def test_ft_clean_sweep_reports_all_ok():
    specs = [_spec(s) for s in (1, 2, 3)]
    baseline = [Experiment(s).run().summary() for s in specs]
    results, report = sweep(specs, workers=2, return_report=True)
    assert _summaries(results) == baseline    # process isolation is free
    assert isinstance(report, SweepReport) and report.all_ok
    assert report.counts() == {"ok": 3}
    assert all(r.attempts == 1 and r.error is None for r in report.specs)
    assert all(r.wall_s > 0 for r in report.specs)


def test_ft_empty_sweep():
    results, report = sweep([], return_report=True)
    assert results == [] and report.specs == [] and report.all_ok


# -------------------------------------------------- death and detection
def test_ft_killed_worker_is_retried_and_result_is_identical(crash_env):
    """A worker hard-exiting mid-run (os._exit — no exception, no
    result) is detected by exit code, retried, and the retried spec's
    result matches the crash-free serial baseline exactly."""
    specs = [_spec(s) for s in (10, 11, 12)]
    baseline = [Experiment(s).run().summary() for s in specs]
    crash_env(4, "kill", seed=11)
    failures = []
    results, report = sweep(
        specs, workers=2, retry_limit=2, retry_backoff_s=0.01,
        on_failure=lambda i, e, att: failures.append(
            (i, type(e).__name__, att)),
        return_report=True)
    assert _summaries(results) == baseline
    assert report.counts() == {"ok": 2, "retried": 1}
    rep = report.specs[1]
    assert rep.status == "retried" and rep.attempts == 2
    assert "_WorkerDied" in rep.error
    assert failures == [(1, "_WorkerDied", 1)]


def test_ft_hung_worker_times_out_and_is_retried(crash_env):
    specs = [_spec(s) for s in (20, 21)]
    crash_env(2, "hang", seed=21)
    results, report = sweep(
        specs, workers=2, timeout_s=2.0, retry_limit=1,
        retry_backoff_s=0.01, return_report=True)
    assert all(r is not None for r in results)
    assert report.counts() == {"ok": 1, "retried": 1}
    assert "timeout_s" in report.specs[1].error


def test_ft_exhausted_retries_leave_partial_results(crash_env):
    """retry_limit exhausted -> that spec's slot stays None, status goes
    terminal, and every OTHER spec still returns — partial results
    instead of all-or-nothing."""
    specs = [_spec(s) for s in (30, 31, 32)]
    crash_env(3, "kill", seed=31, once=False)    # crashes EVERY attempt
    results, report = sweep(specs, workers=2, retry_limit=1,
                            retry_backoff_s=0.01, return_report=True)
    assert results[1] is None
    assert results[0] is not None and results[2] is not None
    assert not report.all_ok
    rep = report.specs[1]
    assert rep.status == "failed" and rep.attempts == 2
    assert report.counts() == {"ok": 2, "failed": 1}


def test_ft_raised_failure_without_report_still_returns_partial():
    """Arming FT via on_failure alone (no report asked, no retries)
    returns the plain results list with None in the failed slot."""
    specs = [_spec(40), _spec(41, arch="no-such-arch"), _spec(42)]
    results = sweep(specs, workers=2, on_failure=lambda *a: None)
    assert results[1] is None
    assert results[0] is not None and results[2] is not None


def test_ft_on_result_fires_exactly_once_per_spec(crash_env):
    specs = [_spec(s) for s in (50, 51, 52)]
    crash_env(3, "raise", seed=52)
    seen = []
    results, _ = sweep(specs, workers=2, retry_limit=1,
                       retry_backoff_s=0.01, return_report=True,
                       on_result=lambda i, r: seen.append(i))
    assert sorted(seen) == [0, 1, 2]
    assert all(r is not None for r in results)


# ----------------------------------------------------------- pack salvage
def test_ft_pack_salvage_reruns_survivors_and_isolates_culprit():
    """A lane pack whose crash names a guilty lane: the survivors are
    re-chunked into a fresh sub-pack (outside the retry budget — the
    failure was not theirs), the culprit retries alone and fails; the
    survivors' results match serial baselines."""
    specs = [_spec(60), _spec(61, arch="no-such-arch"),
             _spec(62), _spec(63)]
    good = [0, 2, 3]
    baseline = {i: Experiment(specs[i]).run().summary() for i in good}
    results, report = sweep(specs, workers=1, vectorize=True,
                            retry_limit=1, retry_backoff_s=0.01,
                            return_report=True)
    assert results[1] is None
    assert {i: results[i].summary() for i in good} == baseline
    assert report.counts() == {"retried": 3, "failed": 1}
    assert "spec index 1" in report.specs[1].error
    assert report.specs[1].attempts == 2


# ------------------------------------------- serial fallback + annotation
def test_ft_serial_fallback_when_processes_unavailable(monkeypatch,
                                                       crash_env):
    """No worker processes (restricted env): FT falls back in-process
    with a warning; retries still work, and the failure annotation names
    the sweep spec index exactly like the pool path does."""
    def no_pool(*a, **k):
        raise OSError("no processes here")
    monkeypatch.setattr(sweep_mod, "_sweep_ft_pool", no_pool)
    crash_env(3, "raise", seed=71)
    specs = [_spec(70), _spec(71)]
    with pytest.warns(RuntimeWarning, match="in-process"):
        results, report = sweep(specs, retry_limit=1,
                                retry_backoff_s=0.01, return_report=True)
    assert all(r is not None for r in results)
    assert report.counts() == {"ok": 1, "retried": 1}
    assert "sweep spec index 1" in report.specs[1].error


def test_legacy_serial_fallback_failure_names_spec_index(monkeypatch):
    """Regression (satellite): the LEGACY pool-fallback serial rerun must
    annotate a failing spec with the same index context the pool path
    attaches — the traceback names the spec whichever path ran it."""
    def no_pool(*a, **k):
        raise OSError("no pool")
    monkeypatch.setattr(sweep_mod, "_sweep_pool", no_pool)
    specs = [_spec(80), _spec(81, arch="no-such-arch")]
    with pytest.warns(RuntimeWarning, match="in-process"):
        with pytest.raises(KeyError, match="sweep spec index 1"):
            sweep(specs, workers=2)


def test_legacy_serial_failure_names_spec_index():
    specs = [_spec(90), _spec(91, arch="no-such-arch")]
    with pytest.raises(KeyError, match="sweep spec index 1"):
        sweep(specs, workers=1)


def test_legacy_sweep_semantics_unchanged():
    """Without any FT knob the all-or-nothing contract stands: results in
    spec order, no report, first failure propagates."""
    specs = [_spec(s) for s in (100, 101)]
    results = sweep(specs, workers=1)
    assert [r.summary() for r in results] \
        == [Experiment(s).run().summary() for s in specs]


# ------------------------------------------------- one process per chip
@pytest.mark.parametrize("fault_tolerant", [False, True])
def test_real_learner_specs_run_in_the_parent(monkeypatch, fault_tolerant):
    """The parent holds the accelerator: real-learner specs never reach a
    worker process, while surrogate specs keep their pool."""
    import os
    monkeypatch.setattr(sweep_mod, "run_spec", lambda spec: os.getpid())
    pooled = []
    for name in ("_sweep_pool", "_sweep_ft_pool"):
        real_fn = getattr(sweep_mod, name)

        def spy(jobs, *a, _fn=real_fn, **kw):
            pooled.extend(i for job in jobs
                          for i in (job[1] if isinstance(job, tuple)
                                    else job.idxs))
            return _fn(jobs, *a, **kw)
        monkeypatch.setattr(sweep_mod, name, spy)
    specs = [_spec(s).replace(learner="real" if s % 2 else "surrogate")
             for s in range(6)]
    kw = {"return_report": True} if fault_tolerant else {}
    out = sweep(specs, workers=4, **kw)
    pids = out[0] if fault_tolerant else out
    real = [i for i, s in enumerate(specs) if s.learner == "real"]
    assert not set(pooled) & set(real)
    assert all(pids[i] == os.getpid() for i in real)
    assert all(pids[i] != os.getpid() for i in (0, 2, 4))   # pooled
