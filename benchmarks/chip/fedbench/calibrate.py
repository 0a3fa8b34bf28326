"""Readings that the limits of a cell are set from: the program against
the reference over a dozen seeds or more, and the control and the faults
against the reference on a few of them. Not part of a benchmark run.

    python3 benchmarks/chip/fedbench/calibrate.py --workload charlm-sync \
        --seeds 101-112 --control-seeds 101-103

On a TPU; prints one JSON line per seed and reading. With
``--program-precision highest`` the program's own matmuls run at that
precision: a look at how much of its readings its default precision
causes, not a control. The control is the
reference itself in bfloat16 (the precision below the configuration's
float32) put in the program's place. Faults are planted in the reference
put in the program's place: half of the cohort left out with the mean
taken over the rest, and one client's delta (its answer) negated where
it is produced. A state left unchanged reads 1 by construction and needs no
run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from fedbench import harness  # noqa: E402


def half_cohort(i, deltas, weights, stale):
    h = max(1, len(deltas) // 2)
    return deltas[:h], weights[:h], None if stale is None else stale[:h]


def negate_one(i, deltas):
    return [{k: -v for k, v in deltas[0].items()}] + list(deltas[1:])


FAULTS = {"half_cohort": dict(alter=half_cohort),
          "negated_delta": dict(alter_answers=negate_one)}


def program_readings(cell, spec, cfg, P, seed, sizes):
    """The program's warm-up answers and first checked updates through
    the engine, and the contributions they received."""
    n = int(cell.traffic["checked_updates"])
    s = spec.replace(run=harness.replace(spec.run, max_rounds=n))
    learner = harness._learner(cell, s, cfg, P, seed)
    warm = harness.warm_sizes(learner, s, sizes, seed, P)
    rec = harness.Recorder(learner, annotate=False)
    readings = harness.Readings(learner, n, s.federated.adam_beta1)
    seen = [0]

    def on_round(ev):
        k = len(rec.updates)
        if k > seen[0]:
            readings.after_update(k, ev.perplexity)
        seen[0] = k

    P["Experiment"](s, learner=learner).run(on_round=on_round)
    values = dict(readings.values, answers1=rec.answers1,
                  warm_answers=[d for _, d in warm])
    updates = rec.updates[:n]
    warm_ids = [cid for cid, _ in warm]
    del learner, rec, readings, warm
    gc.collect()
    return values, updates, warm_ids


def calibrate(cell, seeds, control_seeds, *, platform="tpu", emit=print,
              program_precision=None):
    dev = jax.devices()[0]
    if dev.platform != platform:
        raise harness.BenchError(f"needs a {platform} device, found "
                                 f"{dev.platform!r}")
    P = harness._program()
    if platform == "tpu":
        harness.use_compile_cache(harness.ROOT, lambda s: None)
    spec, cfg = harness.build_spec(cell, P)
    sizes = sorted({n for n, _ in harness.replay(
        spec, P, int(cell.traffic["warm_horizon"]))})
    program = "program" if program_precision is None else \
        f"program_at_{program_precision}"
    out = []
    for seed in seeds:
        t = time.perf_counter()
        with jax.default_matmul_precision(program_precision):
            values, updates, warm_ids = program_readings(cell, spec, cfg, P,
                                                         seed, sizes)
        ref = harness.run_reference(cell, spec, seed, updates, warm_ids)
        rows = [(program, harness.compare(values, ref))]
        if seed in control_seeds:
            ctrl = harness.run_reference(cell, spec, seed, updates,
                                         warm_ids, dtype=jnp.bfloat16,
                                         precision="default")
            rows.append(("control_bf16", harness.compare(ctrl, ref)))
            for name, fault in FAULTS.items():
                got = harness.run_reference(cell, spec, seed, updates,
                                            warm_ids, **fault)
                rows.append((name, harness.compare(got, ref)))
        for kind, numbers in rows:
            line = {"cell": cell.name, "seed": seed, "kind": kind,
                    "numbers": numbers,
                    "seconds": round(time.perf_counter() - t, 3)}
            out.append(line)
            emit(json.dumps(line))
    return out


def _seeds(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--program-precision", default=None,
                   choices=("default", "high", "highest"),
                   help="run the program's own matmuls at this precision "
                        "(a look at what its readings come from)")
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    calibrate(cell, _seeds(args.seeds), set(_seeds(args.control_seeds)),
              emit=lambda s: print(s, flush=True),
              program_precision=args.program_precision)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
