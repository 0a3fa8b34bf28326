"""Per-architecture smoke tests (REDUCED variants, CPU) + decode/forward
consistency. Full configs are exercised only through ``param_count``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, reduced
from repro.models import get_model, param_count

RNG = jax.random.PRNGKey(0)


def _reduced(name):
    cfg0 = get_config(name)
    layers = 3 if cfg0.family == "hybrid" else 2
    return reduced(cfg0, layers=layers)


def _batch(cfg, B=2, S=16):
    b = {"tokens": jax.random.randint(RNG, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)}
    if cfg.family in ("vlm", "audio"):
        b["frontend"] = jax.random.normal(
            RNG, (B, cfg.num_frontend_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "charlm":
        b["chars"] = jax.random.randint(RNG, (B, S, cfg.max_word_len), 0,
                                        cfg.char_vocab)
    return b


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_smoke_forward_and_train_step(name):
    """One forward + one SGD step on the reduced config: shapes + finiteness."""
    cfg = _reduced(name)
    model = get_model(cfg)
    params, axes = model.init(RNG)
    assert set(axes) == set(params)
    for k, v in params.items():
        assert len(axes[k]) == v.ndim, k
    batch = _batch(cfg)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    new = {k: params[k] - 0.01 * grads[k] for k in params}
    loss2, _ = jax.jit(model.loss)(new, batch)
    assert np.isfinite(float(loss2))
    assert new["embed" if "embed" in new else list(new)[0]].shape == \
        params["embed" if "embed" in params else list(params)[0]].shape


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_smoke_decode_path(name):
    cfg = _reduced(name)
    model = get_model(cfg)
    params, _ = model.init(RNG)
    batch = _batch(cfg, B=2, S=12)
    if cfg.family == "charlm":
        lg, cache = model.prefill(params, batch["tokens"], chars=batch["chars"])
        step_in = batch["chars"][:, -1]
    elif cfg.family in ("vlm", "audio"):
        lg, cache = model.prefill(params, batch["tokens"], batch["frontend"])
        step_in = batch["tokens"][:, -1]
    else:
        lg, cache = model.prefill(params, batch["tokens"])
        step_in = batch["tokens"][:, -1]
    assert lg.shape == (2, cfg.vocab_size)
    lg2, cache2 = jax.jit(model.decode_step)(params, cache, step_in)
    assert lg2.shape == (2, cfg.vocab_size)
    assert np.isfinite(np.asarray(lg2, np.float32)).all()
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("name", ["smollm-135m", "rwkv6-7b",
                                  "recurrentgemma-2b", "mixtral-8x22b"])
def test_decode_matches_full_forward(name):
    """Prefill(t[:-1]) + decode(t[-1]) logits == full-forward last logits."""
    cfg = _reduced(name)
    model = get_model(cfg)
    if getattr(model, "is_moe", False):
        # dropless routing on both paths so the equivalence is exact
        model.capacity_factor = float(cfg.moe.num_experts)
    params, _ = model.init(RNG)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                              cfg.vocab_size)
    # full forward logits at the last position
    if cfg.family in ("dense", "moe", "vlm"):
        x = model._embed(params, toks)
        x, _, _ = model._stack(params, x)
        full = model.logits(params, x[:, -1:, :])[:, 0]
    elif cfg.family == "ssm":
        x = params["embed"][toks]
        states, _ = model._zero_states(2, x.dtype)
        x, _ = model._stack(params, x, states)
        import repro.models.common as cm
        x = cm.rms_norm(x[:, -1:], params["final_norm"])
        full = jnp.einsum("bsd,dv->bsv", x, params["unembed"])[:, 0]
    else:  # hybrid
        x = params["embed"][toks]
        states, _ = model._zero_rec_states(2, x.dtype)
        x, _, _ = model._stack(params, x, states)
        import repro.models.common as cm
        x = cm.rms_norm(x[:, -1:], params["final_norm"])
        full = jnp.einsum("bsd,dv->bsv", x, model._unembed(params))[:, 0]

    _, cache = model.prefill(params, toks[:, :-1], pad_to=16)
    dec, _ = model.decode_step(params, cache, toks[:, -1])
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_param_counts_in_expected_band():
    expect = {
        "mistral-nemo-12b": (11e9, 13.5e9),
        "mixtral-8x22b": (135e9, 145e9),
        "smollm-135m": (0.12e9, 0.17e9),
        "rwkv6-7b": (7e9, 8.2e9),
        "granite-moe-1b-a400m": (1.2e9, 1.5e9),
        "recurrentgemma-2b": (2.4e9, 2.9e9),
    }
    for name, (lo, hi) in expect.items():
        n = param_count(get_config(name))
        assert lo <= n <= hi, (name, n)
    # MoE active params
    mix = get_config("mixtral-8x22b")
    assert param_count(mix, active_only=True) < 0.35 * param_count(mix)
