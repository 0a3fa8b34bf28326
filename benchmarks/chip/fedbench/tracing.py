"""From a profiler trace to device busy time, per-program device time and
idle gaps attributed to what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
interval lists; ``reduce`` turns those lists into numbers. The reduction
is pure, so the tests check it on a small recorded trace.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclass
class Trace:
    host: List[Interval] = field(default_factory=list)
    ops: Dict[int, List[Interval]] = field(default_factory=dict)
    modules: Dict[int, List[Interval]] = field(default_factory=dict)


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str, span_names: Sequence[str]) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``: host spans named in
    ``span_names``, and each TPU's op and program (module) events."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = Trace()
    wanted = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            tail = plane.name[len(DEVICE_PREFIX):]
            if not tail.isdigit():
                continue
            dev = int(tail)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.ops[dev] = [(op_name(e.name), e.start_ns, e.end_ns)
                                    for e in line.events]
                elif line.name == MODULES_LINE:
                    out.modules[dev] = [(e.name, e.start_ns, e.end_ns)
                                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.host += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in wanted]
    return out


def union(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    segs = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                  if b > lo and a < hi)
    out: List[List[float]] = []
    for a, b in segs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clipped(intervals: Sequence[Interval], lo: float, hi: float
             ) -> List[Interval]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in intervals
            if b > lo and a < hi]


def timeline(host: Sequence[Interval], lo: float, hi: float,
             default: str) -> List[Interval]:
    """[lo, hi] cut into segments, each named by the innermost host span
    active in it, or ``default`` where none is."""
    spans = sorted((s for s in host
                    if s[0] != WINDOW_SPAN and s[2] > lo and s[1] < hi),
                   key=lambda s: s[1])
    cuts = sorted({lo, hi} | {x for _, a, b in spans for x in (a, b)
                              if lo < x < hi})
    out: List[Interval] = []
    active: List[Interval] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[2] > mid]
        best = min(active, key=lambda s: s[2] - s[1], default=None)
        out.append((default if best is None else best[0], a, b))
    return out


def attribute(gaps: Sequence[Tuple[float, float]],
              segments: Sequence[Interval]) -> Dict[str, float]:
    """Idle time per name: each gap split over the ``timeline`` segments
    it overlaps. Both lists are in time order and do not overlap."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(segments) and segments[j][2] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][1] < b:
            name, s, e = segments[k]
            t = min(b, e) - max(a, s)
            if t > 0:
                out[name] = out.get(name, 0.0) + t
            k += 1
    return out


@dataclass
class Reduced:
    window_s: float
    busy_s: float                      # averaged over the traced chips
    layer_s: Dict[str, float]          # device seconds per layer
    top_ops: List[Tuple[str, float]]
    idle_by_span: List[Tuple[str, float]]


def reduce(trace: Trace, layers: Dict[str, Sequence[str]],
           outside: str = "engine", top: int = 10) -> Optional[Reduced]:
    """None when the trace holds no window span or no device events."""
    wins = [s for s in trace.host if s[0] == WINDOW_SPAN]
    if not wins or not trace.ops:
        return None
    _, lo, hi = wins[0]
    window = hi - lo
    busy_total, layer_ns, op_ns, idle = 0.0, {}, {}, {}
    segments = timeline(trace.host, lo, hi, outside)
    for dev, ops in trace.ops.items():
        busy = union([(a, b) for _, a, b in ops], lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for name, a, b in _clipped(ops, lo, hi):
            op_ns[name] = op_ns.get(name, 0.0) + (b - a)
        for name, a, b in _clipped(trace.modules.get(dev, []), lo, hi):
            low = name.lower()
            for layer, keys in layers.items():
                if any(k in low for k in keys):
                    layer_ns[layer] = layer_ns.get(layer, 0.0) + (b - a)
                    break
        edges = [lo] + [x for seg in busy for x in seg] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for who, t in attribute(gaps, segments).items():
            idle[who] = idle.get(who, 0.0) + t
    n = len(trace.ops)
    sec = 1e-9
    return Reduced(
        window_s=window * sec,
        busy_s=busy_total * sec / n,
        layer_s={k: v * sec / n for k, v in layer_ns.items()},
        top_ops=[(k, v * sec / n) for k, v in
                 sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        idle_by_span=[(k, v * sec / n) for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]])
