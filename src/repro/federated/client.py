"""Client-side local training (paper §3.3): plain SGD, E local epochs.

A client's batches are stacked into one fixed shape of ``n_steps`` steps
with a per-step mask (``stack_batches``: real steps first, padding after),
so ragged client datasets share one compiled program. The shape is fixed;
the trip count is not: the local-SGD loop ends at the last step that holds
data (``trip_count``), a traced bound, so every trip count runs the same
compiled program. ``make_client_update`` builds it for one client,
``make_cohort_update`` for a cohort under ``vmap``, whose clients share one
unbatched bound: the last real step of its longest client. Both return the
model DELTA and the mean loss of the real steps.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def trip_count(step_mask):
    """One past the last step whose mask is set in any client: the
    (..., n_steps) mask's loop bound, 0 where no step is set. A NumPy
    mask gives a NumPy integer, computed on the host."""
    steps = step_mask.shape[-1]
    real = (step_mask.reshape(-1, steps) > 0).any(axis=0)
    return (real * np.arange(1, steps + 1)).max()


def _local_sgd(loss_fn: Callable, client_lr: float,
               max_grad_norm: float) -> Callable:
    """run(params, batches, step_mask, n) -> (delta, mean_loss): steps
    0..n-1 of the stacked batches. A step whose mask is 0 multiplies its
    update and its loss by 0, so an n past a client's last real step
    leaves its delta as it is."""
    grad_fn = jax.grad(lambda p, b: loss_fn(p, b)[0])

    def one_step(params, batch, m):
        g = grad_fn(params, batch)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                          for v in g.values()))
        scale = jnp.minimum(1.0, max_grad_norm / (gn + 1e-9)) * m
        new = {k: params[k] - (client_lr * scale) * g[k].astype(params[k].dtype)
               for k in params}
        loss = loss_fn(params, batch)[0]
        return new, loss * m

    def run(params, batches, step_mask, n):
        mask = step_mask.astype(jnp.float32)

        def body(i, carry):
            p, loss_sum = carry
            batch = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False),
                batches)
            new, loss = one_step(p, batch, mask[i])
            return new, loss_sum + loss

        final, loss_sum = lax.fori_loop(
            0, n, body, (params, jnp.zeros((), jnp.float32)))
        delta = {k: final[k] - params[k] for k in params}
        n_steps = jnp.maximum(jnp.sum(step_mask), 1.0)
        return delta, loss_sum / n_steps

    return run


def make_client_update(loss_fn: Callable, client_lr: float,
                       max_grad_norm: float = 10.0) -> Callable:
    """loss_fn(params, batch) -> (loss, metrics).

    Returns jitted f(params, batches, step_mask) -> (delta, mean_loss)
    where batches is a dict of (n_steps, B, ...) stacked arrays and
    step_mask (n_steps,) zeroes out padding steps. The loop runs to the
    client's last real step. Under a plain ``jax.vmap`` that bound is
    batched: the loop then runs to the cohort's longest client, and
    selects each client's carry at every step.
    """
    run = _local_sgd(loss_fn, client_lr, max_grad_norm)

    def client_update(params, batches, step_mask):
        return run(params, batches, step_mask, trip_count(step_mask))

    return jax.jit(client_update)


def make_cohort_update(loss_fn: Callable, client_lr: float,
                       max_grad_norm: float = 10.0) -> Callable:
    """As ``make_client_update`` for a cohort: f(params, batches, step_mask)
    with batches (K, n_steps, B, ...) and step_mask (K, n_steps), every
    client from the same params. The loop bound, the last real step of the
    cohort's longest client, is taken outside the vmap, so the loop runs
    that many steps unbatched."""
    run = jax.vmap(_local_sgd(loss_fn, client_lr, max_grad_norm),
                   in_axes=(None, 0, 0, None))

    def client_update(params, batches, step_mask):
        return run(params, batches, step_mask, trip_count(step_mask))

    return jax.jit(client_update)


def stack_batches(batches, n_steps: int):
    """Pad a list of batch dicts to n_steps and build the step mask."""
    assert batches, "client has no data"
    batches = batches[:n_steps]
    mask = np.zeros((n_steps,), np.float32)
    mask[: len(batches)] = 1.0
    out = {}
    for k in batches[0]:
        arrs = [b[k] for b in batches]
        while len(arrs) < n_steps:
            arrs.append(np.zeros_like(arrs[0]))
        out[k] = np.stack(arrs)
    return out, mask
