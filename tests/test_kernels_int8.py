"""int8 quant kernel: shape/dtype sweeps vs the pure-jnp oracle +
hypothesis property tests on the codec's error bound."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.int8_quant import ops, ref
from repro.kernels.int8_quant.kernel import (dequant_accumulate_pallas,
                                             quantize_pallas)

SHAPES = [(64,), (1000,), (128, 128), (3, 7, 11), (2048, 33)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("block", [128, 256])
def test_pallas_quantize_matches_ref(shape, dtype, block):
    x = jax.random.normal(jax.random.PRNGKey(7), shape, dtype)
    q1, s1 = quantize_pallas(x, block=block, interpret=True)
    q0, s0 = ref.quantize_ref(x, block)
    nb = q0.shape[0]
    np.testing.assert_array_equal(np.asarray(q1)[:nb], np.asarray(q0))
    np.testing.assert_allclose(np.asarray(s1)[:nb], np.asarray(s0), rtol=1e-6)
    # padding rows must be exactly zero-scale-one
    assert (np.asarray(q1)[nb:] == 0).all()


@pytest.mark.parametrize("shape", [(512,), (64, 48)])
def test_pallas_dequant_accumulate(shape):
    x = jax.random.normal(jax.random.PRNGKey(3), shape)
    acc = jax.random.normal(jax.random.PRNGKey(4), shape)
    q, s = quantize_pallas(x, block=128, interpret=True)
    got = ops.dequant_accumulate(acc, q, s, 0.25, block=128, interpret=True)
    want = ref.dequant_accumulate_ref(
        acc, *ref.quantize_ref(x, 128), 0.25, block=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 2000), st.integers(0, 2**31 - 1),
       st.floats(1e-3, 1e3))
def test_roundtrip_error_bound(n, seed, scale):
    """|x - dq(q(x))| <= block_amax / 254 + eps, per element."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (n,))) * scale
    y = np.asarray(ref.quant_dequant_ref(jnp.asarray(x), 256))
    xb = np.pad(x, (0, (-n) % 256)).reshape(-1, 256)
    amax = np.abs(xb).max(axis=1)
    bound = np.repeat(amax / 254.0 + 1e-6, 256)[:n] * (1 + 1e-3)
    assert (np.abs(x - y) <= bound + 1e-7).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000))
def test_accumulate_linearity(seed):
    """acc' = acc + w*dq is exactly linear in w."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (300,))
    acc = jnp.zeros((300,))
    q, s = ref.quantize_ref(x, 256)
    a1 = ref.dequant_accumulate_ref(acc, q, s, 1.0)
    a2 = ref.dequant_accumulate_ref(acc, q, s, 2.0)
    np.testing.assert_allclose(np.asarray(a2), 2 * np.asarray(a1), rtol=1e-6)


def test_wire_bytes():
    assert ops.wire_bytes(256) == 256 + 4
    assert ops.wire_bytes(257) == 257 + 8
    # 4x smaller than f32 for big tensors (modulo scale overhead)
    n = 1_000_000
    assert ops.wire_bytes(n) < 4 * n / 3.8
