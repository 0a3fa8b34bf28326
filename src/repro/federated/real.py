"""Real JAX learner: actual federated training of any model-zoo config.

Holds server params + FedAdam state, compiles the client local-SGD step
once (ragged client datasets are padded into a fixed scan length), and —
for FedBuff — keeps a ring of recent param versions so stale clients
really do train against the model they were sent (true staleness, not an
approximation). Deltas optionally round-trip the int8 wire codec.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
        self._history.append((self.version, jax.device_get(self.params)))
        cap = max(2, self.fed.staleness_cap)
        if len(self._history) > cap:
            self._history.pop(0)

    def params_at(self, version: int):
        for v, p in reversed(self._history):
            if v <= version:
                return p
        return self._history[0][1]

    # -------------------------------------------------------------- learner
    def client_deltas(self, client_ids, version: Optional[int] = None):
        """Vmapped cohort update (true cross-device simulation): all clients
        train in parallel from the same server params — one compiled call
        per round instead of len(cohort) sequential ones."""
        base = self.params if version is None or version == self.version \
            else self.params_at(version)
        stacked_all, masks, n_ex = [], [], []
        for cid in client_ids:
            batches = self.dataset.client_batches(
                cid, self.fed.client_batch_size, self.fed.local_epochs)
            st, m = stack_batches(batches, self.max_steps)
            stacked_all.append(st)
            masks.append(m)
            n_ex.append(min(len(batches), self.max_steps)
                        * self.fed.client_batch_size)
        cohort = {k: np.stack([s[k] for s in stacked_all])
                  for k in stacked_all[0]}
        cmask = np.stack(masks)
        deltas, _ = self._vmapped_update(base, cohort, cmask)
        if self.fed.compression == "int8":
            deltas = aggregation.compress_roundtrip(
                deltas, block=self.fed.quant_block)
        out = jax.device_get(deltas)
        return [{k: v[i] for k, v in out.items()}
                for i in range(len(client_ids))], [float(n) for n in n_ex]

    def client_delta(self, client_id: int, version: Optional[int] = None):
        """Run real local training; returns (delta dict, example weight)."""
        base = self.params if version is None or version == self.version \
            else self.params_at(version)
        batches = self.dataset.client_batches(
            client_id, self.fed.client_batch_size, self.fed.local_epochs)
        stacked, mask = stack_batches(batches, self.max_steps)
        delta, _ = self._client_update(base, stacked, mask)
        if self.fed.compression == "int8":
            delta = aggregation.compress_roundtrip(delta,
                                                   block=self.fed.quant_block)
        n_ex = min(len(batches), self.max_steps) * self.fed.client_batch_size
        return jax.device_get(delta), float(n_ex)

    def apply(self, deltas: List[Dict[str, np.ndarray]], weights: List[float],
              *, n_contributors: int = 0, mean_staleness: float = 0.0,
              staleness: Optional[List[int]] = None) -> None:
        assert deltas, "apply() with empty buffer"
        w = np.asarray(weights, np.float32)
        if staleness is not None:  # FedBuff staleness scaling
            w = w * aggregation.fedbuff_weights(staleness,
                                                self.fed.staleness_exponent)
        stacked = {k: jnp.stack([d[k] for d in deltas]) for k in deltas[0]}
        mean_delta = aggregation.weighted_mean_deltas(stacked, jnp.asarray(w))
        self.params, self.opt_state = self._server_step(
            self.params, self.opt_state, mean_delta)
        self.version += 1
        self._push_history()

    def eval_perplexity(self) -> float:
        if self._eval_batch is None:
            self._eval_batch = self.dataset.eval_batch(
                self.run.eval_clients, batch_size=32)
            self._eval_fn = jax.jit(lambda p, b: self.model.loss(p, b)[0])
        loss = self._eval_fn(self.params, self._eval_batch)
        return float(np.exp(np.clip(np.asarray(loss), 0, 20)))
