"""The local-SGD loop ends at the last real step (``federated/client.py``):
every client's delta equals a fixed-length ``lax.scan`` over all padded
steps, one compiled program serves every trip count, and a cohort runs to
its longest client's last real step. CPU, tiny charlm (the LSTM-Char
family of ``lstm-char-small``) and dense ``DecoderLM`` configs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import spans
from repro.configs import FederatedConfig, RunConfig, get_config, reduced
from repro.data import FederatedDataset
from repro.federated.client import (make_client_update, make_cohort_update,
                                    stack_batches, trip_count)
from repro.federated.real import RealLearner
from repro.models import get_model

STEPS, BATCH, LR = 8, 4, 0.1


def _charlm():
    cfg = reduced(get_config("paper-charlm"), layers=1, d_model=32, d_ff=32,
                  vocab=128)
    return dataclasses.replace(cfg, lstm_hidden=32, max_context=8)


def _dense():
    # SmolLM-135M's family at its published init (initializer_range 0.02)
    return reduced(get_config("smollm-135m"), layers=2, d_model=64, heads=4,
                   kv_heads=2, d_ff=128, vocab=256)


CONFIGS = {"charlm": _charlm, "dense": _dense}


def _dataset(cfg) -> FederatedDataset:
    return FederatedDataset(vocab_size=cfg.vocab_size, seq_len=8,
                            char_vocab=cfg.char_vocab,
                            max_word_len=cfg.max_word_len)


def _scan_reference(loss_fn, client_lr, max_grad_norm=10.0):
    """The loop as a fixed-length scan over every stacked step, padded
    ones included, each masked to a no-op."""
    grad_fn = jax.grad(lambda p, b: loss_fn(p, b)[0])

    def one_step(params, batch_and_mask):
        batch, m = batch_and_mask
        g = grad_fn(params, batch)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                          for v in g.values()))
        scale = jnp.minimum(1.0, max_grad_norm / (gn + 1e-9)) * m
        new = {k: params[k] - (client_lr * scale) * g[k].astype(params[k].dtype)
               for k in params}
        loss = loss_fn(params, batch)[0]
        return new, loss * m

    def client_update(params, batches, step_mask):
        final, losses = lax.scan(
            one_step, params, (batches, step_mask.astype(jnp.float32)))
        delta = {k: final[k] - params[k] for k in params}
        n_steps = jnp.maximum(jnp.sum(step_mask), 1.0)
        return delta, jnp.sum(losses) / n_steps

    return client_update


def _client(ds: FederatedDataset, cid: int, steps: int):
    """Client ``cid``'s first ``steps`` batches, padded to STEPS."""
    batches = ds.client_batches(cid, BATCH, 1)
    assert len(batches) >= steps
    return stack_batches(batches[:steps], STEPS)


def _cohort(ds: FederatedDataset, steps):
    # clients 4, 5 and 9 hold 50, 14 and 36 batches of 4
    packed = [_client(ds, cid, n) for cid, n in zip((4, 5, 9), steps)]
    return ({k: np.stack([b[k] for b, _ in packed]) for k in packed[0][0]},
            np.stack([m for _, m in packed]))


def _assert_equal(got, want):
    """Bit for bit: the loop runs the scan's own steps, and a padded step
    leaves the params as they are."""
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def setup(request):
    cfg = CONFIGS[request.param]()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return model, params, _dataset(cfg)


@pytest.mark.parametrize("steps", [(1, 3, 8), (8, 8, 8)],
                         ids=["ragged", "full"])
def test_cohort_delta_equals_the_fixed_length_scan(setup, steps):
    model, params, ds = setup
    batches, mask = _cohort(ds, steps)
    assert int(trip_count(mask)) == max(steps)
    delta, loss = make_cohort_update(model.loss, LR)(params, batches, mask)
    # the reference under the same vmap: a vmapped LSTM's matmuls round
    # differently from one client's alone (CPU, about 1e-5 of a delta)
    ref = jax.jit(jax.vmap(_scan_reference(model.loss, LR),
                           in_axes=(None, 0, 0)))
    want, want_loss = ref(params, batches, mask)
    for i, n in enumerate(steps):            # client by client
        _assert_equal({k: v[i] for k, v in delta.items()},
                      {k: v[i] for k, v in want.items()})
        assert any(float(jnp.abs(v[i]).max()) > 0 for v in delta.values()), n
    np.testing.assert_allclose(np.asarray(loss), np.asarray(want_loss),
                               rtol=1e-6)


@pytest.mark.parametrize("steps", [1, 3, 8])
def test_single_client_delta_equals_the_fixed_length_scan(setup, steps):
    model, params, ds = setup
    batches, mask = _client(ds, 4, steps)
    delta, loss = make_client_update(model.loss, LR)(params, batches, mask)
    want, want_loss = jax.jit(_scan_reference(model.loss, LR))(
        params, batches, mask)
    _assert_equal(delta, want)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(want_loss),
                               rtol=1e-6)


def test_plain_vmap_of_the_client_update_runs_each_client_to_its_end(setup):
    """Under a plain ``jax.vmap`` the bound is batched, and each client's
    delta is still its own scan's."""
    model, params, ds = setup
    batches, mask = _cohort(ds, (2, 5, 1))
    plain = jax.jit(jax.vmap(make_client_update(model.loss, LR),
                             in_axes=(None, 0, 0)))
    delta, _ = plain(params, batches, mask)
    want, _ = jax.jit(jax.vmap(_scan_reference(model.loss, LR),
                               in_axes=(None, 0, 0)))(params, batches, mask)
    _assert_equal(delta, want)


def test_one_compiled_program_serves_every_trip_count(setup):
    model, params, ds = setup
    cohort = make_cohort_update(model.loss, LR)
    single = make_client_update(model.loss, LR)
    for n in (1, 4, 8):
        batches, mask = _cohort(ds, (n, n, 1))
        assert int(trip_count(mask)) == n
        jax.block_until_ready(cohort(params, batches, mask))
        one, m1 = _client(ds, 5, n)
        jax.block_until_ready(single(params, one, m1))
    assert cohort._cache_size() == 1
    assert single._cache_size() == 1


def test_a_cohort_runs_to_its_longest_clients_last_real_step():
    """A cohort of 1 and 2 real steps runs 2 iterations for both clients:
    the loss is evaluated twice an iteration (the gradient's forward and
    the loss's own), and the learner counts 2 x 2 steps of rows."""
    cfg = _charlm()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    ds = _dataset(cfg)
    calls = []

    def loss(p, b):
        jax.debug.callback(lambda: calls.append(1))
        return model.loss(p, b)

    batches, mask = _cohort(ds, (1, 2, 2))
    batches = {k: v[:2] for k, v in batches.items()}
    mask = mask[:2]
    jax.block_until_ready(make_cohort_update(loss, LR)(params, batches, mask))
    assert len(calls) == 2 * 2

    fed = FederatedConfig(mode="sync", concurrency=2, aggregation_goal=2,
                          client_lr=LR, client_batch_size=BATCH)
    lr = RealLearner(cfg, fed, RunConfig(max_rounds=1), ds,
                     max_client_steps=STEPS)
    # clients 3 and 16 hold 1 and 2 batches of 4
    assert [len(ds.client_batches(c, BATCH, 1)) for c in (3, 16)] == [1, 2]
    spans.reset()
    spans.enable()
    try:
        lr.client_deltas([3, 16])
        got = spans.snapshot()["counters"]
    finally:
        spans.disable()
        spans.reset()
    assert got["client.rows_computed"] == 2 * 2 * BATCH
    assert got["client.rows_skipped"] == 2 * (STEPS - 2) * BATCH
    assert got["client.rows_real"] <= 3 * BATCH
