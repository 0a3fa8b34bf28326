"""Program spans and counters at the real learner's host<->device boundaries.

Off by default. Off, ``span`` returns one shared no-op context and
``count`` returns at once: one module-level flag test per call, no
allocation and no clock read. On (``enable()``), each span

* opens ``jax.profiler.TraceAnnotation(name, update=...)``, so that under
  ``jax.profiler.start_trace`` the program's spans land in the same
  ``.xplane.pb``, on the same clock, as the device's operations;
* appends an in-memory record: name, ``perf_counter_ns`` start and end,
  the index of the enclosing span (or -1), the server update the work
  feeds, and the bytes it copies.

Per-name totals (seconds, calls, bytes) and counters are kept beside the
records; ``snapshot()`` returns them and ``dump(path)`` writes the records
as JSON lines. An operator turns the spans on around a run of their own::

    jax.profiler.start_trace(trace_dir)
    repro.spans.reset(); repro.spans.enable()
    ...                                  # Experiment(...).run()
    repro.spans.disable(); jax.profiler.stop_trace()
    totals = repro.spans.snapshot()
    repro.spans.dump(f"{trace_dir}/program_spans.jsonl")

Span and counter names are fixed (``SPANS``, ``COUNTERS``). A span whose
name ends in ``to_device`` or ``to_host`` holds a call that copies between
host and device, and counts the bytes it copies. A copy to the host
returns with the bytes there; a copy to the device may return before they
land, and the rest of it then falls in the next span that waits on the
device.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List

import jax

SPANS = ("client.pack", "client.to_device", "client.wait", "client.to_host",
         "server.to_device", "server.update", "server.history_to_host",
         "server.eval")
# client.rows_*: rows of the client update that hold data, all it
# computes, and those of padded steps past the loop's end it skips;
# client.programs: calls of a client program (one per vmapped
# cohort, one per FedBuff client); server.deltas_*: client deltas the
# server step took from the device copies the client programs made, and
# those it uploaded
COUNTERS = ("client.rows_real", "client.rows_computed", "client.rows_skipped",
            "client.programs", "server.deltas_resident",
            "server.deltas_uploaded")

_on = False
_records: List[Dict[str, Any]] = []
_stack: List[int] = []
_totals: Dict[str, List[float]] = {}         # name -> [seconds, calls, bytes]
_counts: Dict[str, int] = {}


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("_name", "_update", "_copies", "_ann", "_rec")

    def __init__(self, name: str, update: int, copies):
        self._name, self._update, self._copies = name, update, copies

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self._name,
                                                 update=self._update)
        self._ann.__enter__()
        nbytes = 0 if self._copies is None else sum(
            int(x.nbytes) for x in jax.tree_util.tree_leaves(self._copies))
        self._copies = None
        self._rec = {"name": self._name, "start": 0, "end": 0,
                     "parent": _stack[-1] if _stack else -1,
                     "update": self._update, "bytes": nbytes}
        _stack.append(len(_records))
        _records.append(self._rec)
        self._rec["start"] = time.perf_counter_ns()
        return None

    def __exit__(self, *exc):
        rec = self._rec
        rec["end"] = time.perf_counter_ns()
        _stack.pop()
        tot = _totals.setdefault(self._name, [0.0, 0, 0])
        tot[0] += (rec["end"] - rec["start"]) * 1e-9
        tot[1] += 1
        tot[2] += rec["bytes"]
        self._ann.__exit__(*exc)
        return False


def span(name: str, *, update: int = -1, copies=None):
    """Context for one span. ``update`` is the server update the work
    feeds (shared by every span of that update); ``copies`` is the pytree
    of arrays the span moves between host and device, whose bytes are
    counted only while the tracer is on."""
    if not _on:
        return _OFF
    return _Span(name, update, copies)


def count(name: str, n: int) -> None:
    if not _on:
        return
    _counts[name] = _counts.get(name, 0) + int(n)


def enabled() -> bool:
    """Whether spans and counters are being recorded; guards work done
    only to feed a counter."""
    return _on


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def reset() -> None:
    """Drops every record, total and counter (not the on/off state)."""
    _records.clear()
    _stack.clear()
    _totals.clear()
    _counts.clear()


def snapshot() -> Dict[str, Dict]:
    """Per-name totals of the spans recorded since the last ``reset``
    (``{"s", "calls", "bytes"}``) and the counters."""
    return {"spans": {n: {"s": t[0], "calls": t[1], "bytes": t[2]}
                      for n, t in _totals.items()},
            "counters": dict(_counts)}


def dump(path) -> int:
    """Writes every record as one JSON line (keys name, start, end,
    parent, update, bytes); returns the number written."""
    with open(path, "w") as f:
        for rec in _records:
            f.write(json.dumps(rec) + "\n")
    return len(_records)
