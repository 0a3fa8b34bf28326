"""One run of one cell: set-up, a measured window of federated training
through the real learner, the check against the plain reference, and the
result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``configs/<config>.json`` with the reference model
and FLOPs count beside it in ``configs/<config>.py``, its traffic mix in
``traffic/<traffic>.json``, its limits in ``limits/<cell>.json``, and each
metric's reader in ``metrics/<metric>.py``.

Seeds: ``--seed`` makes the weights (program and reference alike). The
traffic mix fixes the client population (the engine's seed and the data's
seed), so every seed trains the same clients with the same amount of data
and runs differ by the weights alone.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field, replace
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from fedbench import datagen, tracing
from fedbench.reference import FedReference, follow, leaf_norms

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPANS = ("learner.client_update", "learner.apply", "learner.eval",
         "data.synth", "harness")
TOP_LEVEL = ("learner.client_update", "learner.apply", "learner.eval",
             "harness")
LOSS_CLIP = 20.0          # the program reports exp(clip(loss, 0, 20))
CLIENT_POOL = 10 ** 6     # client ids the warm-up draws its cohorts from


class BenchError(RuntimeError):
    """The cell cannot be run as asked (device, files, configuration)."""


class _WindowClosed(Exception):
    pass


def _json(path: Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing benchmark file {path}") from None


def _module(path: Path, name: str):
    if not path.exists():
        raise BenchError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _safe(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    model_mod: Any
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = _json(root / entry["file"])
    model_mod = _module(bench_dir / "configs" / f"{wl['config']}.py",
                        f"fedbench_config_{_safe(wl['config'])}")

    def applies(metric: Dict, reported: Optional[set]) -> bool:
        if "workloads" in metric:
            return name in metric["workloads"]
        return reported is None or metric.get("moves") in reported

    e2e = [m for m in bench["end_to_end"] if applies(m, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if applies(m, names)]
    return Cell(name=name, chips=int(wl["chips"]), config=config,
                model_mod=model_mod,
                traffic=_json(bench_dir / "traffic" / f"{wl['traffic']}.json"),
                limits=_json(bench_dir / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


# ------------------------------------------------------------ the program
def _program():
    """The system under test; importing it fails where only the
    benchmark's own files are present."""
    try:
        from repro.api import Experiment, ExperimentSpec, ModelRef
        from repro.api.environment import Environment
        from repro.configs.base import FederatedConfig, RunConfig
        from repro.data.synthetic import FederatedDataset
        from repro.federated import aggregation
        from repro.federated.real import RealLearner
    except ImportError as e:
        raise BenchError(f"the system under test is not importable: {e}") \
            from None
    return dict(Experiment=Experiment, ExperimentSpec=ExperimentSpec,
                ModelRef=ModelRef, Environment=Environment,
                FederatedConfig=FederatedConfig, RunConfig=RunConfig,
                FederatedDataset=FederatedDataset, RealLearner=RealLearner,
                aggregation=aggregation)


def _canon(v):
    return json.loads(json.dumps(v))


def build_spec(cell: Cell, P: Dict):
    """(ExperimentSpec, resolved ModelConfig) of the cell; the model as
    the program resolves it must be the model the configuration file
    states."""
    c, t = cell.config, cell.traffic
    ref = P["ModelRef"].from_dict(c["model_ref"])
    cfg = ref.resolve()
    for k, v in c["model"].items():
        got = getattr(cfg, k, None)
        if _canon(got) != _canon(v):
            raise BenchError(f"{c['name']}: the program resolves {k}={got!r}"
                             f", the configuration states {v!r}")
    fed_kw = dict(t["federated"])
    fed_kw.update(client_batch_size=c["client_batch_size"],
                  client_lr=c["client_lr"], server_lr=c["server_lr"],
                  seed=t["population_seed"])
    fed = P["FederatedConfig"](**fed_kw)
    if fed.aggregation_goal > c["cohort"]:
        raise BenchError(f"{cell.name}: aggregation goal "
                         f"{fed.aggregation_goal} exceeds the cohort of "
                         f"{c['cohort']} that one chip holds")
    run = P["RunConfig"](target_perplexity=1.0, max_rounds=10 ** 6,
                         max_hours=1e9)
    spec = P["ExperimentSpec"](
        model=ref, federated=fed, run=run,
        environment=P["Environment"].from_dict(t.get("environment")),
        learner="real", seq_len=c["seq_len"],
        max_client_steps=c["max_client_steps"])
    return spec, cfg


def replay(spec, P: Dict, updates: int) -> List[tuple]:
    """The engine's schedule for the first ``updates`` server updates,
    from the surrogate learner (host only): (contributors, mean
    staleness) per update. The engine's choices do not depend on the
    learner (no stop fires at target perplexity 1.0, and its randomness
    is counter-keyed)."""
    s = spec.replace(learner="surrogate",
                     run=replace(spec.run, max_rounds=updates))
    exp = P["Experiment"](s)
    learner = exp.build_learner()
    sched, apply = [], learner.apply

    def rec(deltas, weights, *, n_contributors, mean_staleness=0.0, **kw):
        sched.append((int(n_contributors), float(mean_staleness)))
        return apply(deltas, weights, n_contributors=n_contributors,
                     mean_staleness=mean_staleness, **kw)

    learner.apply = rec
    exp.run()
    return sched


def _dataset(cell: Cell, cfg, P: Dict):
    return P["FederatedDataset"](
        vocab_size=cfg.vocab_size, seq_len=cell.config["seq_len"],
        char_vocab=cfg.char_vocab, max_word_len=cfg.max_word_len,
        seed=cell.traffic["population_seed"])


def _learner(cell: Cell, spec, cfg, P: Dict, seed: int):
    return P["RealLearner"](cfg, spec.federated, spec.run,
                            _dataset(cell, cfg, P),
                            max_client_steps=spec.max_client_steps,
                            seed=seed)


def warm_sizes(learner, spec, sizes: List[int], seed: int, P: Dict
               ) -> List[tuple]:
    """Compiles (or loads from the cache) every program of the window's
    own learner whose shape depends on the cohort size, before its first
    update: for each size of the schedule the cohort update (sync) and the
    stacking and weighted mean that ``apply`` runs. The server step, the
    eval and FedBuff's stale-base path have one shape each and compile in
    the checked updates before the window. No call here changes the
    learner's state.

    Returns (client id, delta) of a few clients of each size (the first,
    the last and one drawn from the seed), trained from the initial
    params by the very program the window runs at that size: answers that
    are checked one by one against the reference."""
    import jax.numpy as jnp
    agg = P["aggregation"]
    rng = np.random.default_rng(seed)
    kept = []
    for k in sorted({k for k in sizes if k > 0}):
        ids = [int(i) for i in rng.choice(CLIENT_POOL, size=k,
                                          replace=False)]
        if spec.federated.mode == "sync":
            deltas, weights = learner.client_deltas(ids)
            pos = {0, k - 1} | ({int(rng.integers(1, k - 1))} if k > 2
                                else set())
        else:
            d, w = learner.client_delta(ids[0])
            deltas, weights, pos = [d] * k, [w] * k, {0}
        kept += [(ids[i], deltas[i]) for i in sorted(pos)]
        stacked = {n: jnp.stack([d[n] for d in deltas]) for n in deltas[0]}
        jax.block_until_ready(agg.weighted_mean_deltas(
            stacked, jnp.asarray(np.asarray(weights, np.float32))))
    return kept


# ------------------------------------------------------------ recording
class Compiles:
    """Backend compiles reported by ``jax.monitoring`` while it is open."""

    def __init__(self):
        self.events: List[tuple] = []

    def _on(self, event, duration_secs, **kw):
        if event == COMPILE_EVENT:
            self.events.append((str(kw.get("fun_name", "")), duration_secs))

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


class Recorder:
    """Wraps the learner's public calls: host spans of the window, the
    contributors each server update received, and the phase it fell in."""

    def __init__(self, learner, annotate: bool):
        self.learner = learner
        self.annotate = annotate
        self.in_window = False
        self.spans = {s: 0.0 for s in SPANS}
        self.updates: List[Dict] = []
        self.answers1: List[Dict] = []     # client deltas of update 1
        self._open: List[tuple] = []
        for name, span in (("client_deltas", "learner.client_update"),
                           ("client_delta", "learner.client_update"),
                           ("apply", "learner.apply"),
                           ("eval_perplexity", "learner.eval")):
            setattr(learner, name, self._wrap(getattr(learner, name), span,
                                              name))
        ds = learner.dataset
        ds.client_batches = self._wrap(ds.client_batches, "data.synth", "")

    @contextlib.contextmanager
    def span(self, name: str):
        ann = jax.profiler.TraceAnnotation(name) if self.annotate \
            else contextlib.nullcontext()
        t = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                if self.in_window:
                    self.spans[name] += time.perf_counter() - t

    def _wrap(self, fn, span: str, call: str):
        def wrapped(*a, **kw):
            if call == "client_deltas":
                ver = kw.get("version", a[1] if len(a) > 1 else None)
                v = self.learner.version if ver is None else ver
                self._open += [(int(c), int(v)) for c in a[0]]
            elif call == "client_delta":
                ver = kw.get("version", a[1] if len(a) > 1 else None)
                v = self.learner.version if ver is None else ver
                self._open.append((int(a[0]), int(v)))
            elif call == "apply":
                st = kw.get("staleness")
                self.updates.append(dict(
                    contrib=self._open, window=self.in_window,
                    staleness=None if st is None else [int(s) for s in st],
                    n=int(kw.get("n_contributors", 0)),
                    mean_staleness=float(kw.get("mean_staleness", 0.0))))
                self._open = []
            with self.span(span):
                out = fn(*a, **kw)
            if not self.updates and call == "client_deltas":
                self.answers1 += list(out[0])
            elif not self.updates and call == "client_delta":
                self.answers1.append(out[0])
            return out
        return wrapped


class Readings:
    """The program's readings over its first ``n`` server updates: the
    eval loss after each, the leaf norms of FedAdam's first gradient
    (worked out from its first moment after one step), and the leaf
    norms of the parameters' change after the ``n``-th."""

    def __init__(self, learner, n: int, beta1: float):
        self.learner, self.n, self.beta1 = learner, n, beta1
        self.p0 = {k: np.asarray(v, np.float64)
                   for k, v in jax.device_get(learner.params).items()}
        self.values: Dict[str, Any] = {"losses": []}

    def after_update(self, k: int, perplexity: float) -> None:
        if k > self.n:
            return
        self.values["losses"].append(math.log(perplexity))
        if k == 1:
            m = jax.device_get(self.learner.opt_state["m"])
            self.values["grad1"] = {n: v / (1.0 - self.beta1)
                                    for n, v in leaf_norms(m).items()}
        if k == self.n:
            p = jax.device_get(self.learner.params)
            self.values["change"] = {
                n: float(np.linalg.norm(
                    (np.asarray(v, np.float64) - self.p0[n]).ravel()))
                for n, v in p.items()}
            self.p0 = None


# ------------------------------------------------------------ comparison
def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Optional[set] = None) -> float:
    """Worst leaf of |prog norm - ref norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref.values())
    worst = 0.0
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        if k not in prog:
            return math.inf
        worst = max(worst, abs(prog[k] - r) / max(r, med, 1e-30))
    return worst


def moved_leaves(grad1: Dict[str, float]) -> set:
    """Leaves whose first gradient is more than a thousandth of the
    median leaf's: the others move under Adam by round-off alone."""
    med = statistics.median(grad1.values())
    return {k for k, v in grad1.items() if v >= 1e-3 * med}


def answer_gap(prog: List[Dict], ref: List[Dict]) -> float:
    """Worst client of |program delta - reference delta| / |reference
    delta|, over each client's whole delta: the answers of the first
    update and those the warm-up kept of each cohort size, checked one by
    one."""
    if len(prog) != len(ref) or not ref:
        return math.inf
    worst = 0.0
    for a, b in zip(prog, ref):
        diff = norm = 0.0
        for k, v in b.items():
            if k not in a:
                return math.inf
            bv = np.asarray(v, np.float64)
            diff += float(np.sum(np.square(np.asarray(a[k], np.float64)
                                           - bv)))
            norm += float(np.sum(np.square(bv)))
        worst = max(worst, math.sqrt(diff / max(norm, 1e-300)))
    return worst


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers that decide ``correct``, program readings against the
    reference's, after the first checked server updates."""
    loss_gap = max(abs(p - min(max(r, 0.0), LOSS_CLIP)) /
                   max(abs(min(max(r, 0.0), LOSS_CLIP)), 1e-30)
                   for p, r in zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = math.inf
    return {"loss_gap": loss_gap,
            "client_delta_gap": answer_gap(
                prog.get("answers1", []) + prog.get("warm_answers", []),
                ref["answers1"] + ref.get("warm_answers", [])),
            "grad_norm_gap": leaf_gap(prog.get("grad1", {}), ref["grad1"]),
            "change_norm_gap": leaf_gap(prog.get("change", {}), ref["change"],
                                        moved_leaves(ref["grad1"]))}


def judge(numbers: Dict[str, float], limits: Dict) -> tuple:
    """(correct, checks) where checks maps each number to its value and
    limit; a number whose limit is null is reported, not compared."""
    ok, checks = True, {}
    for name, value in numbers.items():
        lim = limits.get(name)
        checks[name] = {"value": value, "limit": lim}
        if lim is not None and not (value <= lim):
            ok = False
    return ok, checks


def run_reference(cell: Cell, spec, seed: int, checked: List[Dict],
                  warm_ids: List[int] = (), *, dtype=None, precision: str = "highest", alter=None,
                  alter_answers=None) -> Dict:
    import jax.numpy as jnp
    c, fed = cell.config, spec.federated
    m = c["model"]
    data = datagen.ClientData(m["vocab_size"], c["seq_len"],
                              cell.traffic["population_seed"],
                              m.get("char_vocab", 0),
                              m.get("max_word_len", 16))
    fed_d = dict(client_lr=fed.client_lr, server_lr=fed.server_lr,
                 client_batch_size=fed.client_batch_size,
                 local_epochs=fed.local_epochs,
                 staleness_exponent=fed.staleness_exponent,
                 adam_beta1=fed.adam_beta1, adam_beta2=fed.adam_beta2,
                 adam_eps=fed.adam_eps)
    model = dict(m, **{k: c[k] for k in ("rms_norm_eps",) if k in c})
    ref = FedReference(cell.model_mod, model, fed_d, data, seed,
                       dtype=dtype or jnp.float32, precision=precision,
                       max_steps=spec.max_client_steps)
    out = follow(ref, checked, spec.run.eval_clients, alter=alter,
                 alter_answers=alter_answers)
    out["warm_answers"] = [ref.client_delta(cid, 0)[0] for cid in warm_ids]
    del ref
    gc.collect()
    return out


# ------------------------------------------------------------ the window
@dataclass
class Window:
    """What the metric readers see."""
    setup_s: float = 0.0
    window_s: float = 0.0
    updates: int = 0
    tokens: float = 0.0
    useful_flops: float = 0.0
    peak: Optional[Dict] = None
    chips: int = 1
    memory_peak_bytes: int = 0
    compiles: int = 0
    compiled: List[str] = field(default_factory=list)
    spans: Dict[str, float] = field(default_factory=dict)
    reduced: Optional[tracing.Reduced] = None

    @property
    def engine_s(self) -> float:
        return self.window_s - sum(self.spans.get(s, 0.0) for s in TOP_LEVEL)


def _peaks() -> Dict:
    return _json(Path(__file__).resolve().parent / "peaks.json")


def _layers() -> Dict:
    return _json(Path(__file__).resolve().parent / "layers.json")["layers"]


def use_compile_cache(root: Path, log) -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a
    fixed path inside the checkout, placed through the program's own
    helper; every program is cached, not only those that take a second
    to compile."""
    import os
    from repro.compile_cache import enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    log(f"[bench] compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def drive(cell: Cell, seed: int, seconds: float, trace: bool, *,
          t_start: float, platform: str = "tpu",
          peaks: Optional[Dict] = None, bench_dir: Path = BENCH_DIR,
          log=None) -> Dict:
    """Set-up, window, check: the result line as a dict."""
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != platform:
        raise BenchError(f"needs a {platform} device; JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind})")
    if len(devs) < cell.chips:
        raise BenchError(f"cell {cell.name} needs {cell.chips} chips; JAX "
                         f"found {len(devs)}")
    peaks = peaks if peaks is not None else _peaks()
    if dev.device_kind not in peaks:
        raise BenchError(f"no peak figures for device kind "
                         f"{dev.device_kind!r} in peaks.json")
    P = _program()
    if platform == "tpu":
        use_compile_cache(bench_dir.parents[1], log)
    spec, cfg = build_spec(cell, P)
    t = cell.traffic
    n_checked = int(t["checked_updates"])
    sched = replay(spec, P, int(t["warm_horizon"]))
    sizes = sorted({n for n, _ in sched})
    log(f"[bench] {cell.name}: schedule of {len(sched)} updates, cohort "
        f"sizes {sizes}")
    w = Window(peak=peaks[dev.device_kind], chips=cell.chips)
    tokens_per_row = cell.config["seq_len"]
    flops_per_token = cell.model_mod.train_flops_per_token(
        cell.config["model"], cell.config["seq_len"])
    fed = spec.federated
    trace_dir = bench_dir.parents[1] / "results" / "fedbench" / cell.name
    with Compiles() as compiles:
        learner = _learner(cell, spec, cfg, P, seed)
        warm = warm_sizes(learner, spec, sizes, seed, P)
        log(f"[bench] warm-up: {len(compiles.events)} compiles, "
            f"{time.perf_counter() - t_start:.1f} s since start")
        rec = Recorder(learner, annotate=trace)
        readings = Readings(learner, n_checked, fed.adam_beta1)
        state = {}

        def on_round(ev):
            with rec.span("harness"):
                k = len(rec.updates)              # server updates so far
                fresh = k > state.get("seen", 0)
                state["seen"] = k
                if fresh:
                    readings.after_update(k, ev.perplexity)
                jax.block_until_ready(learner.params)
            if fresh and k == n_checked:
                if trace:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    jax.profiler.start_trace(str(trace_dir))
                    state["ann"] = jax.profiler.TraceAnnotation(
                        tracing.WINDOW_SPAN)
                    state["ann"].__enter__()
                rec.in_window = True
                state.update(t0=time.perf_counter(), u0=k,
                             c0=len(compiles.events))
            elif "t0" in state and \
                    time.perf_counter() - state["t0"] >= seconds:
                state.update(t1=time.perf_counter(), u1=k,
                             c1=len(compiles.events))
                rec.in_window = False
                raise _WindowClosed

        exp = P["Experiment"](spec, learner=learner)
        try:
            exp.run(on_round=on_round)
            raise BenchError("the engine stopped before the window closed")
        except _WindowClosed:
            pass
        if trace:
            state["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    w.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    w.setup_s = state["t0"] - t_start
    w.window_s = state["t1"] - state["t0"]
    w.updates = state["u1"] - state["u0"]
    w.compiled = [n for n, _ in compiles.events[state["c0"]:state["c1"]]]
    w.compiles = len(w.compiled)
    w.spans = dict(rec.spans)
    log(f"[bench] window {w.window_s:.3f} s, {w.updates} updates, "
        f"{w.compiles} compiles {w.compiled}, setup {w.setup_s:.3f} s, "
        f"peak {w.memory_peak_bytes} B")
    in_win = [u for u in rec.updates if u["window"]]
    rows = sum(datagen.real_rows(cid, t["population_seed"],
                                 fed.client_batch_size, fed.local_epochs,
                                 spec.max_client_steps)
               for u in in_win for cid, _ in u["contrib"])
    w.tokens = float(rows * tokens_per_row)
    w.useful_flops = w.tokens * flops_per_token
    if trace:
        w.reduced = tracing.reduce(
            tracing.load(str(trace_dir), (tracing.WINDOW_SPAN,) + SPANS),
            _layers())
    checked = rec.updates[:n_checked]
    got = [(u["n"], u["mean_staleness"]) for u in rec.updates]
    mismatches = sum(1 for a, b in zip(got, sched)
                     if a[0] != b[0] or abs(a[1] - b[1]) > 1e-9)
    prog = dict(readings.values, answers1=rec.answers1,
                warm_answers=[d for _, d in warm])
    warm_ids = [cid for cid, _ in warm]
    del exp, learner, rec, readings, warm
    gc.collect()
    ref = run_reference(cell, spec, seed, checked, warm_ids)
    numbers = compare(prog, ref)
    numbers["schedule_mismatches"] = float(mismatches)
    correct, checks = judge(numbers, cell.limits)
    return result_line(cell, w, dev, devs, correct, checks, trace, bench_dir)


def result_line(cell: Cell, w: Window, dev, devs, correct: bool,
                checks: Dict, trace: bool, bench_dir: Path = BENCH_DIR
                ) -> Dict:
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = _module(bench_dir / "metrics" / f"{m['name']}.py",
                         f"fedbench_metric_{_safe(m['name'])}")
        value = reader.read(w)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": w.memory_peak_bytes}
    out = {"correct": bool(correct), "attempted": w.updates,
           "failed": 0, "metrics": metrics, "device": device}
    if trace and w.reduced is not None:
        device["busy_s"] = w.reduced.busy_s
        device["window_s"] = w.reduced.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in w.reduced.top_ops],
            "idle_gaps": [[n, s] for n, s in w.reduced.idle_by_span]}
    out["checks"] = checks
    return out
