"""Shared helpers of the paper-figure scripts: parallel grid runner + CSV
emission.

The ``fig*`` and ``table_*`` scripts reproduce the paper's tables and
figures with the surrogate engine; they are not speed measurements (the
repo's one benchmark is ``benchmarks/chip``, run on the chip).

Sweeps go through ``repro.api.sweep`` — build the specs with
``make_spec``, run them all with ``run_points(points, workers=N)``, and
get back the same flat summary rows ``run_point`` produces. By default
compatible points are lane-batched (``sweep(..., vectorize=True)``, PR
4) and the packs fan out across a process pool; results are
seed-for-seed identical to per-point serial runs either way. Set
``BENCH_VECTORIZE=0`` to force the pre-lane per-spec pool and
``BENCH_WORKERS=N`` to bound the pool."""
from __future__ import annotations

import csv
import io
import itertools
import os
import sys
import time
from typing import Dict, Iterable, List, Optional, Sequence

from repro.api import (Environment, Experiment, ExperimentSpec, ModelRef,
                       Result, sweep)
from repro.configs import FederatedConfig, RunConfig, get_config

CFG = get_config("paper-charlm")
MODEL = ModelRef("paper-charlm")

# benchmark-wide worker count: BENCH_WORKERS env var, default all cores
WORKERS = int(os.environ.get("BENCH_WORKERS", "0")) or None
# lane-batch compatible sweep points by default (BENCH_VECTORIZE=0 opts out)
VECTORIZE = os.environ.get("BENCH_VECTORIZE", "1").lower() \
    not in ("0", "false", "no")


def make_spec(run: RunConfig | None = None,
              environment: Environment | None = None,
              **fed_kw) -> ExperimentSpec:
    """One sweep point as a self-contained ExperimentSpec."""
    fed_kw.setdefault("aggregation_goal",
                      max(1, int(fed_kw.get("concurrency", 100) * 0.8)))
    return ExperimentSpec(
        model=MODEL, federated=FederatedConfig(**fed_kw),
        run=run or RunConfig(target_perplexity=175.0),
        environment=environment or Environment(), learner="surrogate")


def point_row(res: Result) -> Dict[str, float]:
    """Flatten a Result into the benchmark CSV row schema."""
    fed = res.spec.federated
    out = res.summary()
    out.update(concurrency=fed.concurrency,
               mode=0.0 if fed.mode == "sync" else 1.0,
               client_lr=fed.client_lr, server_lr=fed.server_lr,
               local_epochs=fed.local_epochs, batch=fed.client_batch_size)
    out["shares_client_compute"], out["shares_upload"], \
        out["shares_download"], out["shares_server"] = (
            res.carbon.shares()[k] for k in
            ("client_compute", "upload", "download", "server"))
    return out


def run_point(run: RunConfig | None = None,
              environment: Environment | None = None,
              **fed_kw) -> Dict[str, float]:
    return point_row(Experiment(make_spec(run, environment, **fed_kw)).run())


def run_points(points: Sequence[Dict], run: RunConfig | None = None,
               environment: Environment | None = None,
               workers: Optional[int] = WORKERS,
               vectorize: bool = VECTORIZE) -> List[Dict[str, float]]:
    """Run a list of sweep points (dicts of FederatedConfig overrides; a
    point may carry its own "run"=RunConfig) — lane-batched by default,
    with packs fanned out across a process pool."""
    specs = [make_spec(p.pop("run", None) or run, environment, **p)
             for p in (dict(p) for p in points)]
    return [point_row(r)
            for r in sweep(specs, workers=workers, vectorize=vectorize)]


def session_columns(log):
    """Session columns for plotting/inspection, streaming-aware: on a
    full-telemetry TaskLog this is every session; on a ``StreamedLog``
    (``run.telemetry="streaming"``) it is the seed-deterministic
    reservoir sample, and a one-line note says so — per-session scatter
    built from it is a uniform subsample, while the summary scalars
    (carbon, bytes, participation, staleness) remain exact either way."""
    if getattr(log, "sampled", False):
        print(f"note: streaming telemetry — plotting a reservoir sample "
              f"of {len(log.columns())}/{log.n_sessions} sessions "
              "(summary scalars are exact)", file=sys.stderr)
    return log.columns()


def grid(**axes: Sequence) -> Iterable[Dict]:
    keys = list(axes)
    for vals in itertools.product(*axes.values()):
        yield dict(zip(keys, vals))


def write_csv(rows: List[Dict], path: str | None = None) -> str:
    if not rows:
        return ""
    keys = sorted({k for r in rows for k in r})
    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=keys)
    w.writeheader()
    for r in rows:
        w.writerow(r)
    text = buf.getvalue()
    if path:
        with open(path, "w") as f:
            f.write(text)
    return text


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.seconds = time.time() - self.t0
