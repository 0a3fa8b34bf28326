"""A model's published init and norm epsilon (``ModelConfig``
``initializer_range`` and ``rms_norm_eps``): they round-trip, the epsilon
reaches every RMSNorm of ``DecoderLM``, a model without them keeps its
own init, and the training forward names its parts for the trace."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ModelRef
from repro.configs import get_config, reduced
from repro.configs.base import model_config_from_dict, model_config_to_dict
from repro.models import common as cm
from repro.models import get_model


def _tiny(**kw):
    return dataclasses.replace(
        reduced(get_config("smollm-135m"), d_model=64, heads=4, kv_heads=2,
                d_ff=128, vocab=256), **kw)


def test_registry_smollm_takes_its_published_values():
    cfg = get_config("smollm-135m")
    assert (cfg.initializer_range, cfg.rms_norm_eps) == (0.02, 1e-5)
    other = get_config("stablelm-1.6b")
    assert (other.initializer_range, other.rms_norm_eps) == (None, 1e-6)


def test_config_round_trips_the_two_fields():
    cfg = _tiny(initializer_range=0.03, rms_norm_eps=1e-5)
    d = model_config_to_dict(cfg)
    assert (d["initializer_range"], d["rms_norm_eps"]) == (0.03, 1e-5)
    assert model_config_from_dict(d) == cfg
    ref = ModelRef.from_config(cfg)
    assert ModelRef.from_dict(ref.to_dict()).resolve() == cfg
    old = {k: v for k, v in d.items()
           if k not in ("initializer_range", "rms_norm_eps")}
    back = model_config_from_dict(old)            # a dict from before them
    assert (back.initializer_range, back.rms_norm_eps) == (None, 1e-6)


def test_rms_norm_eps_reaches_every_norm_of_the_decoder(monkeypatch):
    seen = []
    norm = cm.rms_norm

    def spy(x, gamma, eps=1e-6):
        seen.append(eps)
        return norm(x, gamma, eps)

    monkeypatch.setattr(cm, "rms_norm", spy)
    cfg = _tiny(rms_norm_eps=0.125)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 8), jnp.int32)
    model.loss(params, {"tokens": toks, "labels": toks})
    _, cache = model.prefill(params, toks, pad_to=9)
    model.decode_step(params, cache, toks[:, 0])
    model.flash_decode = model.kv_quant = True
    cache, _ = model.init_cache(2, 9)
    model.decode_step(params, cache, toks[:, 0])
    # each layer body is traced once per path: 2 block norms a path, plus
    # the final norm of loss, prefill's logits and both decode steps
    assert len(seen) == 4 * 2 + 4
    assert set(seen) == {0.125}


def test_init_std_draws_every_normal_weight_and_only_those():
    cfg = _tiny(initializer_range=0.5)
    params, _ = get_model(cfg).init(jax.random.PRNGKey(1))
    base, _ = get_model(_tiny(initializer_range=None)).init(
        jax.random.PRNGKey(1))
    for k, v in params.items():
        if k.endswith("norm"):
            np.testing.assert_array_equal(v, base[k])
        else:
            # the same draws, scaled by 0.5 in place of the default scale
            ratio = np.asarray(v) / np.asarray(base[k])
            assert np.allclose(ratio, ratio.flat[0], rtol=1e-5), k
            assert float(jnp.std(v)) == pytest.approx(0.5, rel=0.1), k


def test_training_forward_names_attention_ffn_and_lm_head():
    cfg = _tiny()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 8), jnp.int32)
    hlo = jax.jit(lambda p, b: model.loss(p, b)[0]).lower(
        params, {"tokens": toks, "labels": toks}).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("attention", "ffn", "lm_head"):
        assert any(f"/{scope}/" in n for n in names), scope
