"""Bring-up smoke run of the real federated learner on one TPU chip.

    python chip_smoke.py

Builds its specs with ``repro.launch.train``'s own argument parser and
spec builder and runs them with ``Experiment(spec).run()``, on full-width
``paper-charlm`` (15,560,704 params, seq_len 64, random weights from seed
0, synthetic client data), in one process:

* sync    FedAvg, cohort 40, goal 32, 3 server rounds;
* async   FedBuff, concurrency 40, goal 8, 3 server versions;
* int8    one sync round through the int8 uplink codec, which runs the
          Pallas kernel on the chip, and that kernel against the jnp
          reference on a real client delta;
* agree   eval loss at the initial params on the chip and on the host CPU.

Every phase prints bring-up observations: compile seconds, distinct cohort
shapes the client update compiled, wall seconds per round (ended with
``block_until_ready``), peak device memory and perplexity. Any failed check
raises, so the exit code is not 0 and no result line is printed. The last
line of a good run is one JSON object naming the device. Without a TPU the
script exits non-zero before it builds a model.
"""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PLATFORM = "tpu"
# launch/train.py command lines: the model at full width, then each phase
TRAIN_ARGS = ["--arch", "paper-charlm", "--seq-len", "64"]
SYNC = ["--mode", "sync", "--concurrency", "40", "--aggregation-goal", "32",
        "--rounds", "3"]
ASYNC = ["--mode", "async", "--concurrency", "40", "--aggregation-goal", "8",
         "--rounds", "3"]
INT8 = ["--mode", "sync", "--concurrency", "40", "--aggregation-goal", "32",
        "--rounds", "1", "--compression", "int8"]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# int8 kernel vs jnp reference, both on the chip: the scales are one f32
# division each, so they agree to a few ulps; q may move by one where
# x/scale lies within an ulp of a rounding tie, and only there.
SCALE_RTOL = 1e-6
Q_MAX_DIFF = 1
Q_MISMATCH_SHARE = 1e-4
# eval loss, chip vs host. The TPU's default f32 matmul is one bf16 pass
# (8-bit mantissas, unit roundoff 2^-9 ~ 2e-3 per operand), so at default
# precision the loss is held to 2e-2 relative; at "highest" precision the
# matmuls are f32-accurate and only summation order and transcendental
# approximations differ, so 1e-4 relative.
LOSS_RTOL_DEFAULT = 2e-2
LOSS_RTOL_HIGHEST = 1e-4


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


class Compiles:
    """Backend compiles reported by ``jax.monitoring`` while it is open."""

    def __init__(self):
        self.events = []

    def _on(self, event, duration_secs, **kw):
        if event == COMPILE_EVENT:
            self.events.append((str(kw.get("fun_name", "")), duration_secs))

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def summary(self, start: int) -> str:
        ev = self.events[start:]
        shapes = sum(1 for name, _ in ev if name == "jit(client_update)")
        secs = sum(s for _, s in ev)
        return (f"compile {secs:.2f} s over {len(ev)} programs; "
                f"client_update cohort shapes compiled: {shapes}")


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def on_device(params, dev) -> bool:
    return all(leaf.devices() == {dev} for leaf in jax.tree.leaves(params))


def train(tag: str, args, dev, compiles: Compiles):
    """One `launch.train`-built spec through `Experiment(spec).run()`;
    returns the Experiment (its learner holds the trained params)."""
    from repro.api import Experiment
    from repro.launch.train import build_parser, spec_from_args
    spec = spec_from_args(build_parser().parse_args(TRAIN_ARGS + args))
    mark = len(compiles.events)
    exp = Experiment(spec)
    learner = exp.build_learner()
    check(on_device(learner.params, dev), f"{tag}: params on {dev}")
    ppl0 = learner.eval_perplexity()
    check(math.isfinite(ppl0), f"{tag}: initial perplexity finite")
    walls, ppls = [], []
    t = [time.perf_counter()]

    def on_round(ev):
        jax.block_until_ready(exp.learner.params)
        now = time.perf_counter()
        walls.append(now - t[0])
        ppls.append(ev.perplexity)
        t[0] = now
        print(f"[{tag}] round {ev.round_idx}: wall {walls[-1]:.3f} s, "
              f"ppl {ev.perplexity:.3f}", flush=True)

    res = exp.run(on_round=on_round)
    check(res.rounds == spec.run.max_rounds and len(ppls) == res.rounds,
          f"{tag}: ran {spec.run.max_rounds} rounds")
    check(all(math.isfinite(p) for p in ppls), f"{tag}: perplexity finite")
    check(on_device(exp.learner.params, dev), f"{tag}: params stay on {dev}")
    print(f"[{tag}] {compiles.summary(mark)}")
    print(f"[{tag}] peak device memory {peak_bytes(dev)}; "
          f"ppl {ppl0:.3f} -> {ppls[-1]:.3f}", flush=True)
    return exp


def kernel_vs_reference(learner, dev) -> None:
    """The codec's Pallas kernel against `quantize_ref` on one real,
    flattened client delta (every param leaf)."""
    from repro.federated import aggregation
    from repro.kernels.int8_quant import ops, ref
    delta, _ = learner.client_delta(0)
    text = jax.jit(aggregation.compress_roundtrip).lower(
        {k: jnp.asarray(v) for k, v in delta.items()}).as_text()
    check(("tpu_custom_call" in text) == (dev.platform == "tpu"),
          "int8 codec runs the Pallas kernel exactly on a TPU")
    flat = jax.device_put(
        np.concatenate([np.ravel(v) for v in delta.values()]), dev)
    q1, s1 = ops.quantize(flat)
    q0, s0 = jax.jit(ref.quantize_ref)(flat)
    nb = q0.shape[0]
    q1, s1 = np.asarray(q1), np.asarray(s1)
    q0, s0 = np.asarray(q0), np.asarray(s0)
    check((q1[nb:] == 0).all(), "int8: padding blocks quantize to 0")
    dq = np.abs(q1[:nb].astype(np.int32) - q0.astype(np.int32))
    srel = np.abs(s1[:nb] - s0) / s0
    share = float(np.mean(dq > 0))
    print(f"[int8] kernel vs ref on {flat.size} elements ({nb} blocks): "
          f"max |dq| {int(dq.max())}, mismatched share {share:.3g}, "
          f"max scale rel diff {float(srel.max()):.3g}")
    check(int(dq.max()) <= Q_MAX_DIFF, f"int8: |dq| <= {Q_MAX_DIFF}")
    check(share <= Q_MISMATCH_SHARE, f"int8: q mismatches <= "
          f"{Q_MISMATCH_SHARE}")
    check(float(srel.max()) <= SCALE_RTOL, f"int8: scale rtol {SCALE_RTOL}")


def host_agreement(learner, dev) -> None:
    """Eval loss at the learner's initial params, chip vs host CPU."""
    cpu = jax.devices("cpu")[0]
    batch = learner.dataset.eval_batch(learner.run.eval_clients,
                                       batch_size=32)
    loss = jax.jit(lambda p, b: learner.model.loss(p, b)[0])
    on_host = float(loss(jax.device_put(learner.params, cpu),
                         jax.device_put(batch, cpu)))
    for prec, rtol in (("default", LOSS_RTOL_DEFAULT),
                       ("highest", LOSS_RTOL_HIGHEST)):
        with jax.default_matmul_precision(prec):
            got = float(loss(learner.params, jax.device_put(batch, dev)))
        rel = abs(got - on_host) / abs(on_host)
        print(f"[agree] eval loss {dev.platform} {got:.7f} vs cpu "
              f"{on_host:.7f} at {prec} precision: rel diff {rel:.3g} "
              f"(tolerance {rtol})")
        check(rel <= rtol, f"agree: {prec} precision within {rtol}")


def main() -> int:
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != PLATFORM:
        print(f"chip_smoke: needs a {PLATFORM} device; JAX found "
              f"platform {dev.platform!r} ({dev.device_kind})",
              file=sys.stderr)
        return 1
    print(f"[device] {dev.platform} {dev.device_kind}, {len(devs)} "
          f"device(s)")
    from repro.compile_cache import enable_compile_cache
    print(f"[device] compile cache {enable_compile_cache()}")
    with Compiles() as compiles:
        exp = train("sync", SYNC, dev, compiles)
        kernel_vs_reference(exp.learner, dev)   # an uncompressed delta
        del exp
        train("async", ASYNC, dev, compiles)
        train("int8", INT8, dev, compiles)
        from repro.api import Experiment
        from repro.launch.train import build_parser, spec_from_args
        spec = spec_from_args(build_parser().parse_args(TRAIN_ARGS))
        host_agreement(Experiment(spec).build_learner(), dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
