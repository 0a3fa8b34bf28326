"""A traced run of one cell with the program's own spans on.

    python3 benchmarks/chip/program_spans.py --workload <cell> --seed <n> \
        --seconds <s>

``run.py --trace 1`` traces the window with the harness's spans around
the learner's public calls. This runs the same ``harness.drive`` with
``trace=True`` and adds what the harness does not do yet:

* the program's tracer (``repro.spans``) is reset and enabled where the
  harness opens its window (``Recorder.in_window`` set), and disabled
  where it closes it; nothing else is patched, so only the window is
  recorded, inside the harness's profiler session;
* the program's span names join the host spans that the breakdown splits
  each idle gap over (the innermost span wins);
* the tracer's snapshot becomes ``Window.program``, and the metrics of
  ``PROGRAM_METRICS`` (readers in ``metrics/``) join the per-layer line;
* the records are written to
  ``results/fedbench/<cell>/program_spans.jsonl``, next to the trace.

Prints the result line. On standard error it gives the window's
``client.rows_real`` x seq_len beside the benchmark's own count of real
tokens, which must agree. Exits 2 where the program has no tracer.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import replace  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from fedbench import harness  # noqa: E402

LAYER = "host-device transfer"
MOVES = "client_tokens_per_s"
CELLS = ["charlm-sync", "charlm-async"]
# The exact ``per_layer`` entries that BENCHMARK.json takes once the
# harness reads the program's spans itself; a metric it already lists is
# not added twice, and a test holds a listed one equal to its entry here.
PROGRAM_METRICS = [
    dict(name="host_transfer_ms", unit="ms/update", better="lower",
         source="host_clock", layer=LAYER, moves=MOVES, workloads=CELLS),
    dict(name="host_transfer_mib", unit="MiB/update", better="lower",
         source="program_counter", layer=LAYER, moves=MOVES,
         workloads=CELLS),
    dict(name="batch_pack_ms", unit="ms/update", better="lower",
         source="host_clock", layer="data synthesis", moves=MOVES,
         workloads=CELLS),
    dict(name="useful_row_share", unit="%", better="higher",
         source="program_counter", layer="client update", moves=MOVES,
         workloads=CELLS),
]


@contextlib.contextmanager
def program_tracing():
    """Patches the harness's recorder (its window hooks), span names and
    result line for the length of the block; yields a dict that holds the
    window (``"window"``) once the line is made."""
    try:
        from repro import spans
    except ImportError as e:
        raise harness.BenchError(f"the program has no tracer: {e}") from None
    recorder, names, line = harness.Recorder, harness.SPANS, \
        harness.result_line
    seen = {}

    class Recorder(recorder):
        """Records the program's spans while the traced window is open."""

        @property
        def in_window(self):
            return self._in_window

        @in_window.setter
        def in_window(self, on):
            self._in_window = on
            if on and self.annotate:
                spans.reset()
                spans.enable()
            else:
                spans.disable()

    def result_line(cell, w, *a, **kw):
        w.program = spans.snapshot()
        seen["window"] = w
        listed = {m["name"] for m in cell.per_layer}
        per_layer = cell.per_layer + [m for m in PROGRAM_METRICS
                                      if m["name"] not in listed]
        return line(replace(cell, per_layer=per_layer), w, *a, **kw)

    harness.Recorder = Recorder
    harness.SPANS = names + tuple(s for s in spans.SPANS if s not in names)
    harness.result_line = result_line
    try:
        yield seen
    finally:
        spans.disable()
        harness.Recorder, harness.SPANS, harness.result_line = \
            recorder, names, line


def drive(cell: harness.Cell, seed: int, seconds: float, *,
          bench_dir: Path = harness.BENCH_DIR, **kw) -> tuple:
    """(result line, window) of one traced run with the program's spans
    on; the records go next to the trace."""
    from repro import spans
    with program_tracing() as seen:
        out = harness.drive(cell, seed, seconds, True, bench_dir=bench_dir,
                            **kw)
    trace_dir = bench_dir.parents[1] / "results" / "fedbench" / cell.name
    trace_dir.mkdir(parents=True, exist_ok=True)
    spans.dump(trace_dir / "program_spans.jsonl")
    spans.reset()
    return out, seen["window"]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        out, w = drive(cell, args.seed, args.seconds, t_start=T_START)
    except harness.BenchError as e:
        print(f"fedbench: {e}", file=sys.stderr)
        return 2
    rows = w.program["counters"].get("client.rows_real", 0)
    print(f"program rows_real x seq_len {rows * cell.config['seq_len']} "
          f"window tokens {w.tokens:.0f}", file=sys.stderr)
    for name, s in sorted(w.program["spans"].items()):
        print(f"program span {name} calls {s['calls']} s {s['s']:.6f} "
              f"bytes {s['bytes']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
