"""smollm-135m-published against its plain reference on the CPU: the
weights drawn from the published init, the loss, every leaf's gradient at
the published depth, the FLOPs count, the cell's limits at a tiny size;
and the reader of ``client_update_mfu``."""
from __future__ import annotations

import importlib.util
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import calibrate, datagen, harness, rehearsal, tracing
from fedbench.rehearsal import TINY

BENCH = harness.BENCH_DIR
NAME = "smollm-135m-published"
SEED = 3_000_000_123
PUBLISHED = dict(initializer_range=0.02, rms_norm_eps=1e-5)


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _module(BENCH / "configs" / f"{NAME}.py", "t_smollm_published")


def _program(m):
    from repro.configs.base import model_config_from_dict
    from repro.models import get_model
    return get_model(model_config_from_dict(dict(m)))


def _batch(m, seq, client=5):
    cd = datagen.ClientData(m["vocab_size"], seq, 0)
    return {k: jnp.asarray(v)
            for k, v in cd.client_batches(client, 8, 1, 1)[0].items()}


def test_published_init_weights_and_loss_match_the_program():
    m = dict(TINY["dense"], **PUBLISHED)
    model = _program(m)
    prog, _ = model.init(jax.random.PRNGKey(SEED), dtype=jnp.float32)
    ref = REF.init(m, SEED)
    assert prog.keys() == ref.keys()
    for k in prog:
        np.testing.assert_array_equal(np.asarray(prog[k]), np.asarray(ref[k]))
    # every normally drawn leaf is N(0, 0.02^2); the norms are one
    for k, v in ref.items():
        want = 1.0 if k.endswith("norm") else 0.02
        got = float(jnp.std(v)) if want != 1.0 else float(jnp.mean(v))
        assert got == pytest.approx(want, rel=0.1), k
    batch = _batch(m, 16)
    a = float(jax.jit(lambda p, b: model.loss(p, b)[0])(prog, batch))
    b = float(jax.jit(lambda p, b: REF.loss(m, p, b))(ref, batch))
    assert b == pytest.approx(a, rel=1e-5)


DEEP = dict(TINY["dense"], num_layers=30, d_model=128, num_heads=2,
            num_kv_heads=1, d_ff=384, vocab_size=2048, **PUBLISHED)


@pytest.fixture(scope="module")
def deep_grads():
    """(init, program gradient, reference gradient), each jitted once for
    both seeds."""
    model = _program(DEEP)
    init = jax.jit(lambda k: model.init(k, dtype=jnp.float32)[0])
    prog = jax.jit(jax.grad(lambda p, b: model.loss(p, b)[0]))
    ref = jax.jit(jax.grad(lambda p, b: REF.loss(DEEP, p, b)))
    return init, prog, ref


@pytest.mark.parametrize("seed", [201, 202])
def test_gradient_at_published_depth_matches_the_reference(seed,
                                                           deep_grads):
    """30 blocks at a reduced width (d 128, 2 query heads and 1 key/value
    head of the published size 64), float32: every leaf's gradient norm
    agrees with the reference's. Under the program's default init the
    gradients grow to 1e4-1e6 and disagree by up to 8x in a leaf."""
    init, prog_grad, ref_grad = deep_grads
    params = init(jax.random.PRNGKey(seed))
    batch = _batch(DEEP, 64)
    with jax.default_matmul_precision("highest"):
        prog, ref = prog_grad(params, batch), ref_grad(params, batch)
    gp = {k: float(jnp.linalg.norm(v)) for k, v in prog.items()}
    gr = {k: float(jnp.linalg.norm(v)) for k, v in ref.items()}
    assert np.isfinite(sum(gp.values()))
    for k in gr:
        assert gp[k] == pytest.approx(gr[k], rel=1e-5), k


def test_flops_per_token_matches_xla_cost_analysis():
    """As test_fedbench_flops: 2 of the 30 identical blocks, since the
    count is linear in depth."""
    m = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())["model"]
    m = dict(m, num_layers=2)
    S, B = 8, 2
    params = jax.eval_shape(lambda: REF.init(m, 0))
    i32 = jnp.int32
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
             "labels": jax.ShapeDtypeStruct((B, S), i32),
             "mask": jax.ShapeDtypeStruct((B, S - 1), jnp.float32)}
    step = jax.jit(jax.grad(lambda p, b: REF.loss(m, p, b, unroll=True)))
    ca = step.lower(params, batch).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    mine = REF.train_flops_per_token(m, S) * S * B
    assert ca["flops"] == pytest.approx(mine, rel=0.03)


def test_limits_separate_the_program_from_the_control_at_the_published_init():
    """``smollm-sync``'s limits at a tiny size with the published init: the
    program passes them; the reference in bfloat16 and each planted fault
    (half of the cohort, one negated answer) put in its place do not. The
    rehearsal of every cell swaps in ``TINY["dense"]`` at the program's
    default init, where the float32 reference alone departs from its
    float64 self by up to the whole of a client's delta over 8 local
    steps, so no ``client_delta_gap`` under 1 holds there."""
    cell = rehearsal.tiny_cell("smollm-sync")
    m = dict(cell.config["model"], **PUBLISHED)
    cell.config = dict(cell.config, model=m, model_ref={"config": m})
    rows = calibrate.calibrate(cell, [11], {11}, platform="cpu",
                               emit=lambda s: None)
    got = {r["kind"]: r["numbers"] for r in rows}
    assert harness.judge(got["program"], cell.limits)[0], got["program"]
    for kind in ("control_bf16", "half_cohort", "negated_delta"):
        assert not harness.judge(got[kind], cell.limits)[0], kind


def _window(layer_s, useful_flops=0.25 * 197e12 * 2.0, chips=1):
    reduced = None if layer_s is None else tracing.Reduced(
        window_s=30.0, busy_s=10.0, layer_s=layer_s, top_ops=[],
        idle_by_span=[])
    return harness.Window(window_s=30.0, updates=10,
                          useful_flops=useful_flops, chips=chips,
                          peak={"bf16_flops_per_s": 197e12},
                          reduced=reduced)


def test_client_update_mfu_reads_flops_over_the_client_programs_time():
    read = _module(BENCH / "metrics" / "client_update_mfu.py",
                   "t_client_update_mfu").read
    # a quarter of the peak for 2 s of client-update programs
    w = _window({"client_update": 2.0, "server_eval": 1.0})
    assert read(w) == pytest.approx(25.0)
    assert read(_window({"client_update": 2.0}, chips=4)) == \
        pytest.approx(6.25)
    assert read(_window(None)) is None                 # untraced
    assert read(_window({"server_eval": 1.0})) is None  # no client program
    assert read(_window({"client_update": 2.0}, useful_flops=0.0)) is None
