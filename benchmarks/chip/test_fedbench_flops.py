"""Each configuration's FLOPs per token against XLA's count of one
forward + backward step of its plain reference with every loop unrolled
(no ``lax.scan``, whose body XLA's cost model counts once), at the
published widths; smollm-135m keeps 2 of its 30 identical blocks, since
the count is linear in depth."""
from __future__ import annotations

import importlib.util
import json

import jax
import jax.numpy as jnp
import pytest

from fedbench import harness

BENCH = harness.BENCH_DIR


@pytest.mark.parametrize("name,depth", [("lstm-char-small", None),
                                        ("smollm-135m", 2)])
def test_flops_per_token_matches_xla_cost_analysis(name, depth):
    spec = importlib.util.spec_from_file_location(
        f"flops_{name.replace('-', '_')}", BENCH / "configs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]
    if depth:
        m = dict(m, num_layers=depth)
    S, B = 8, 2
    params = jax.eval_shape(lambda: mod.init(m, 0))
    i32 = jnp.int32
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
             "labels": jax.ShapeDtypeStruct((B, S), i32),
             "mask": jax.ShapeDtypeStruct((B, S - 1), jnp.float32)}
    if m.get("char_vocab"):
        batch["chars"] = jax.ShapeDtypeStruct((B, S, m["max_word_len"]), i32)
    step = jax.jit(jax.grad(lambda p, b: mod.loss(m, p, b, unroll=True)))
    ca = step.lower(params, batch).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    mine = mod.train_flops_per_token(m, S) * S * B
    # XLA also counts elementwise work (activations, softmax, the
    # optimizer-free gradient sums); the matmuls are all but 2% of it
    assert ca["flops"] == pytest.approx(mine, rel=0.03)
