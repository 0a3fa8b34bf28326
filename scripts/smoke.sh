#!/usr/bin/env bash
# CI smoke: tier-1 test suite + ExperimentSpec JSON dry-runs end-to-end
# + the crash-inject/resume contract.
#
#   bash scripts/smoke.sh            # from the repo root
#
# Step 2 loads the committed spec artifacts (one sync, one async, one
# carbon-aware on the diurnal grid, one streaming-telemetry population
# point at concurrency 10^5, one faulty async point with diurnal
# hazards + correlated bursts + retry/backoff recovery, and one
# availability-churn async point with diurnal eligibility curves +
# checkpoint/resume salvage), runs each, then
# re-serializes, reloads and re-runs, asserting both runs produce the
# identical Result.summary() — the repro.api reproducibility contract,
# exercised on ALL THREE event loops (and on the intensity_schedule,
# FaultModel, AvailabilityModel and telemetry round-trips).
#
# Step 3 proves the PR 9 resume contract on the availability-churn spec:
# run it uninterrupted, run it again with checkpointing while the crash
# injector kills the run mid-way, resume from the checkpoint, and assert
# the resumed summary is bit-identical to the uninterrupted one.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== smoke 1/3: tier-1 test suite =="
python -m pytest -x -q

echo "== smoke 2/3: ExperimentSpec JSON dry-runs (with round-trip check) =="
python -m repro.api examples/specs/charlm_sync_small.json \
    --roundtrip-check --quiet
python -m repro.api examples/specs/charlm_async_small.json \
    --roundtrip-check --quiet
python -m repro.api examples/specs/charlm_carbonaware_small.json \
    --roundtrip-check --quiet
python -m repro.api examples/specs/charlm_streaming_pop.json \
    --roundtrip-check --quiet
python -m repro.api examples/specs/charlm_faulty_bursts.json \
    --roundtrip-check --quiet
python -m repro.api examples/specs/charlm_avail_churn.json \
    --roundtrip-check --quiet

echo "== smoke 3/3: crash-inject -> resume -> bit-identical summary =="
python - <<'PY'
import os
import tempfile

from repro.api import Experiment, ExperimentSpec
from repro.core.snapshot import InjectedCrash

spec = ExperimentSpec.load("examples/specs/charlm_avail_churn.json")
base = Experiment(spec).run().summary()
with tempfile.TemporaryDirectory() as td:
    path = os.path.join(td, "smoke_ckpt.npz")
    os.environ["REPRO_CRASH_ROUND"] = "60"
    os.environ["REPRO_CRASH_KIND"] = "raise"
    try:
        Experiment(spec).run(checkpoint_path=path,
                             checkpoint_every_rounds=25)
        raise SystemExit("crash injector did not fire")
    except InjectedCrash:
        pass
    finally:
        del os.environ["REPRO_CRASH_ROUND"], os.environ["REPRO_CRASH_KIND"]
    res = Experiment.resume(path, checkpoint_path=path)
resumed = res.summary()
assert resumed == base, (resumed, base)
print(f"resume contract OK: killed at round 60, resumed run matches "
      f"uninterrupted run exactly ({res.rounds} rounds, "
      f"{res.log.n_sessions} sessions)")
PY

echo "smoke OK"
