"""Real JAX learner: actual federated training of any model-zoo config.

Holds server params + FedAdam state, compiles the client local-SGD step
once (ragged client datasets are padded into a fixed scan length), and —
for FedBuff — keeps a ring of recent param versions so stale clients
really do train against the model they were sent (true staleness, not an
approximation). Deltas optionally round-trip the int8 wire codec.

Every host<->device copy sits in a span (``repro.spans``; off unless
enabled), apart from the waits on the device, so a trace can tell the
copies from the device work. The spans change nothing the program does:
they wrap the calls it makes untraced, and a copy to the device that has
not landed when its call returns falls in the next span that waits.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs.base import FederatedConfig, ModelConfig, RunConfig
from repro.data.synthetic import FederatedDataset
from repro.federated import aggregation
from repro.federated.client import make_client_update, stack_batches
from repro.models import get_model
from repro.optim import server_optimizer


class RealLearner:
    real = True

    def __init__(self, model_cfg: ModelConfig, fed: FederatedConfig,
                 run: RunConfig, dataset: FederatedDataset,
                 max_client_steps: int = 8, seed: int = 0):
        self.cfg = model_cfg
        self.fed = fed
        self.run = run
        self.dataset = dataset
        self.max_steps = max_client_steps
        self.model = get_model(model_cfg)
        rng = jax.random.PRNGKey(seed)
        self.params, self.axes = self.model.init(rng, dtype=jnp.float32)
        self.opt = server_optimizer(fed.server_optimizer, fed.server_lr,
                                    b1=fed.adam_beta1, b2=fed.adam_beta2,
                                    eps=fed.adam_eps)
        self.opt_state = self.opt.init(self.params)
        self._client_update = make_client_update(self.model.loss, fed.client_lr)
        self._vmapped_update = jax.jit(jax.vmap(
            self._client_update, in_axes=(None, 0, 0)))
        self.version = 0
        self._history: List[Tuple[int, Dict[str, np.ndarray]]] = []
        self._push_history()
        self._eval_batch = None

        def server_step(params, opt_state, mean_delta):
            # FedAdam: server "gradient" is the negative aggregated delta
            grads = {k: -v for k, v in mean_delta.items()}
            return self.opt.update(grads, opt_state, params)

        self._server_step = jax.jit(server_step)

    # -------------------------------------------------------------- history
    def _push_history(self):
        with spans.span("server.history_to_host", update=self.version,
                        copies=self.params):
            host = jax.device_get(self.params)
        self._history.append((self.version, host))
        cap = max(2, self.fed.staleness_cap)
        if len(self._history) > cap:
            self._history.pop(0)

    def params_at(self, version: int):
        for v, p in reversed(self._history):
            if v <= version:
                return p
        return self._history[0][1]

    # -------------------------------------------------------------- transfers
    def _train(self, update_fn, version: Optional[int], data, mask,
               update: int):
        """Calls a client program on host batches and returns its delta(s)
        on the host. The call copies its host arguments to the device (the
        batches and mask, and a stale base from the host ring) and
        dispatches the program, so one span holds the copies and the
        dispatch."""
        stale = version is not None and version != self.version
        base = self.params_at(version) if stale else self.params
        with spans.span("client.to_device", update=update,
                        copies=(base, data, mask) if stale else (data, mask)):
            deltas, _ = update_fn(base, data, mask)
        if self.fed.compression == "int8":
            deltas = aggregation.compress_roundtrip(
                deltas, block=self.fed.quant_block)
        # the copies to the host start as the program ends, as a bare
        # device_get starts them, so the wait holds the device time alone
        for x in jax.tree_util.tree_leaves(deltas):
            x.copy_to_host_async()
        with spans.span("client.wait", update=update):
            jax.block_until_ready(deltas)
        with spans.span("client.to_host", update=update, copies=deltas):
            return jax.device_get(deltas)

    # -------------------------------------------------------------- learner
    def client_deltas(self, client_ids, version: Optional[int] = None):
        """Vmapped cohort update (true cross-device simulation): all clients
        train in parallel from the same server params — one compiled call
        per round instead of len(cohort) sequential ones."""
        feeds = self.version + 1
        batches = [self.dataset.client_batches(
            cid, self.fed.client_batch_size, self.fed.local_epochs)
            for cid in client_ids]
        with spans.span("client.pack", update=feeds):
            packed = [stack_batches(b, self.max_steps) for b in batches]
            cohort = {k: np.stack([st[k] for st, _ in packed])
                      for k in packed[0][0]}
            cmask = np.stack([m for _, m in packed])
        _count_rows(cohort["mask"])
        n_ex = [float(min(len(b), self.max_steps) * self.fed.client_batch_size)
                for b in batches]
        out = self._train(self._vmapped_update, version, cohort, cmask, feeds)
        return [{k: v[i] for k, v in out.items()}
                for i in range(len(client_ids))], n_ex

    def client_delta(self, client_id: int, version: Optional[int] = None):
        """Run real local training; returns (delta dict, example weight)."""
        feeds = self.version + 1
        batches = self.dataset.client_batches(
            client_id, self.fed.client_batch_size, self.fed.local_epochs)
        with spans.span("client.pack", update=feeds):
            stacked, mask = stack_batches(batches, self.max_steps)
        _count_rows(stacked["mask"])
        n_ex = min(len(batches), self.max_steps) * self.fed.client_batch_size
        return (self._train(self._client_update, version, stacked, mask,
                            feeds), float(n_ex))

    def apply(self, deltas: List[Dict[str, np.ndarray]], weights: List[float],
              *, n_contributors: int = 0, mean_staleness: float = 0.0,
              staleness: Optional[List[int]] = None) -> None:
        assert deltas, "apply() with empty buffer"
        feeds = self.version + 1
        w = np.asarray(weights, np.float32)
        if staleness is not None:  # FedBuff staleness scaling
            w = w * aggregation.fedbuff_weights(staleness,
                                                self.fed.staleness_exponent)
        with spans.span("server.to_device", update=feeds, copies=deltas):
            stacked = {k: jnp.stack([d[k] for d in deltas])
                       for k in deltas[0]}
        with spans.span("server.update", update=feeds):
            mean_delta = aggregation.weighted_mean_deltas(stacked,
                                                          jnp.asarray(w))
            self.params, self.opt_state = self._server_step(
                self.params, self.opt_state, mean_delta)
        self.version += 1
        self._push_history()

    def eval_perplexity(self) -> float:
        with spans.span("server.eval", update=self.version):
            if self._eval_batch is None:
                self._eval_batch = self.dataset.eval_batch(
                    self.run.eval_clients, batch_size=32)
                self._eval_fn = jax.jit(
                    lambda p, b: self.model.loss(p, b)[0])
            loss = self._eval_fn(self.params, self._eval_batch)
            return float(np.exp(np.clip(np.asarray(loss), 0, 20)))


def _count_rows(mask: np.ndarray) -> None:
    """Rows the client update computes (steps x batch per client) and the
    rows among them that hold data: a data row's mask is 1 at its first
    position, a padding row's is 0."""
    if spans.enabled():
        spans.count("client.rows_real", int(mask[..., 0].sum()))
        spans.count("client.rows_computed", mask[..., 0].size)
