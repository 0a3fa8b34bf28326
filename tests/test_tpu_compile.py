"""Compile the main path's device programs for one described TPU v5e chip,
at real width, with no chip attached: the int8 codec kernels on one
full paper-charlm delta, and the vmapped client step of a 32-client
cohort. The compiler refuses here what the chip would refuse (illegal
block shapes, programs that do not fit its 16 GB). All such tests live in
this one file: the topology is described inside a fixture, so only the
worker that runs this file loads the TPU compiler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.synthetic import FederatedDataset
from repro.federated.client import make_client_update, stack_batches
from repro.kernels.int8_quant import kernel as K
from repro.models import get_model

PARAMS = 15_560_704          # paper-charlm, models/registry.param_count
HBM_BYTES = 16 * 2**30       # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                   # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_int8_kernels_compile_at_full_width(one_chip):
    x = _sds((PARAMS,), jnp.float32, one_chip)
    quant = jax.jit(lambda v: K.quantize_pallas(v, block=256))
    q, s = jax.eval_shape(quant, x)
    assert -(-PARAMS // 256) == 60_784
    assert q.shape == (60_928, 256) and s.shape == (60_928,)
    assert "tpu_custom_call" in quant.lower(x).compile().as_text()
    deq = jax.jit(K.dequant_accumulate_pallas).lower(
        _sds(q.shape, jnp.float32, one_chip), _sds(q.shape, q.dtype, one_chip),
        _sds(s.shape, s.dtype, one_chip), _sds((), jnp.float32, one_chip))
    assert "tpu_custom_call" in deq.compile().as_text()


def test_cohort_client_step_fits_one_chip(one_chip):
    """RealLearner's vmapped update, K=32 clients x 8 steps x batch 8 at
    seq_len 64: compiles for v5e and fits its HBM."""
    K_, steps, batch = 32, 8, 8
    cfg = get_config("paper-charlm")
    model = get_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    assert sum(int(np.prod(p.shape)) for p in params.values()) == PARAMS
    ds = FederatedDataset(vocab_size=cfg.vocab_size, seq_len=64,
                          char_vocab=cfg.char_vocab,
                          max_word_len=cfg.max_word_len)
    one, mask = stack_batches(ds.client_batches(0, batch, 1), steps)
    update = jax.jit(jax.vmap(make_client_update(model.loss, 0.3),
                              in_axes=(None, 0, 0)))
    compiled = update.lower(
        {k: _sds(p.shape, p.dtype, one_chip) for k, p in params.items()},
        {k: _sds((K_,) + v.shape, v.dtype, one_chip) for k, v in one.items()},
        _sds((K_,) + mask.shape, mask.dtype, one_chip)).compile()
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.output_size_in_bytes
             + m.argument_size_in_bytes)
    assert m.output_size_in_bytes >= K_ * PARAMS * 4     # the K f32 deltas
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB"


def test_smollm_cohort_of_four_fits_one_chip(one_chip):
    """The smollm-sync cell's largest cohort program: SmolLM-135M at its
    published widths, depth and init, K=4 clients x 8 steps x batch 8 at
    seq_len 64, beside the params and FedAdam's two moments."""
    K_, steps, batch, seq = 4, 8, 8, 64
    cfg = get_config("smollm-135m")
    assert cfg.initializer_range == 0.02 and cfg.rms_norm_eps == 1e-5
    model = get_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    n = sum(int(np.prod(p.shape)) for p in params.values())
    assert n == 134_515_008
    update = jax.jit(jax.vmap(make_client_update(model.loss, 0.1),
                              in_axes=(None, 0, 0)))
    tok = _sds((K_, steps, batch, seq), jnp.int32, one_chip)
    compiled = update.lower(
        {k: _sds(p.shape, p.dtype, one_chip) for k, p in params.items()},
        {"tokens": tok, "labels": tok,
         "mask": _sds((K_, steps, batch, seq - 1), jnp.float32, one_chip)},
        _sds((K_, steps), jnp.float32, one_chip)).compile()
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.output_size_in_bytes
             + m.argument_size_in_bytes)
    assert m.output_size_in_bytes >= K_ * n * 4           # the K f32 deltas
    assert total + 2 * n * 4 < HBM_BYTES, f"{total / 2**30:.2f} GiB"
