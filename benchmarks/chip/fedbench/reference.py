"""Plain federated reference: per-client local SGD, FedAvg / FedBuff
weighting and the FedAdam server step, written from their descriptions.

One client at a time, one local step per jitted call (no ``vmap``, no
cohort program), float32 at ``highest`` matmul precision unless the
caller asks for the control's lower precision. Client data comes from the
benchmark's own copy of the generator; weights come from the
configuration's own reference ``init``. Nothing here imports the program.

Semantics followed, each as the system under test states it:

* local SGD: ``p -= client_lr * min(1, 10 / (|g| + 1e-9)) * g`` with
  ``|g|`` the global gradient norm, over the client's real steps (the
  system's masked padding steps leave the parameters as they are);
* FedAvg weight of a client: its trained steps times the batch size (the
  system counts the padded rows of a short last batch; FedAvg's n_k would
  count only real rows: a departure noted in PERF.md);
* FedBuff: weight times ``(1 + staleness) ** -staleness_exponent``, where
  staleness is the server version minus the version the client trained
  from;
* FedAdam: Adam on the negative weighted-mean delta, float32 moments,
  bias-corrected, ``eps`` added outside the square root.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fedbench.datagen import ClientData

MAX_GRAD_NORM = 10.0


class FedReference:
    def __init__(self, model_mod, model: Dict, fed: Dict, data: ClientData,
                 seed: int, *, dtype=jnp.float32, precision: str = "highest",
                 max_steps: int = 8):
        self.fed = fed
        self.data = data
        self.dtype = dtype
        self.precision = precision
        self.max_steps = max_steps
        with jax.default_matmul_precision(precision):
            p0 = jax.jit(lambda: model_mod.init(model, seed))()
        self.params = {k: v.astype(dtype) for k, v in p0.items()}
        self.history = {0: self.params}
        self.version = 0
        self.m1 = {k: jnp.zeros(v.shape, jnp.float32)
                   for k, v in self.params.items()}
        self.v1 = dict(self.m1)
        lr = fed["client_lr"]

        def step(p, batch):
            g = jax.grad(lambda q: model_mod.loss(model, q, batch))(p)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                              for x in g.values()))
            scale = jnp.minimum(1.0, MAX_GRAD_NORM / (gn + 1e-9))
            return {k: (p[k] - (lr * scale) * g[k]).astype(p[k].dtype)
                    for k in p}

        self._step = jax.jit(step)
        self._loss = jax.jit(lambda p, b: model_mod.loss(model, p, b))
        self._eval = None

    def _batch(self, b: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        return {k: jnp.asarray(v) for k, v in b.items()}

    def client_delta(self, client_id: int, base_version: int):
        """(delta, FedAvg weight) of one client trained from the server
        params of ``base_version``."""
        base = self.history[base_version]
        p = base
        batches = self.data.client_batches(
            client_id, self.fed["client_batch_size"],
            self.fed["local_epochs"], self.max_steps)
        with jax.default_matmul_precision(self.precision):
            for b in batches:
                p = self._step(p, self._batch(b))
        delta = {k: (p[k].astype(jnp.float32) - base[k].astype(jnp.float32))
                 for k in p}
        return delta, float(len(batches) * self.fed["client_batch_size"])

    def apply(self, deltas: Sequence[Dict], weights: Sequence[float],
              staleness: Optional[Sequence[int]] = None) -> Dict:
        """One server update; returns the gradient FedAdam was given."""
        w = np.asarray(weights, np.float64)
        if staleness is not None:
            w = w * (1.0 + np.asarray(staleness, np.float64)) ** (
                -self.fed["staleness_exponent"])
        w = w / w.sum()
        grad = {k: -sum(float(wi) * d[k] for wi, d in zip(w, deltas))
                for k in deltas[0]}
        b1, b2, eps = (self.fed["adam_beta1"], self.fed["adam_beta2"],
                       self.fed["adam_eps"])
        t = self.version + 1
        new = {}
        for k, g in grad.items():
            self.m1[k] = b1 * self.m1[k] + (1 - b1) * g
            self.v1[k] = b2 * self.v1[k] + (1 - b2) * g * g
            upd = (self.m1[k] / (1 - b1 ** t)) / (
                jnp.sqrt(self.v1[k] / (1 - b2 ** t)) + eps)
            new[k] = (self.params[k].astype(jnp.float32)
                      - self.fed["server_lr"] * upd).astype(self.dtype)
        self.params = new
        self.version = t
        self.history[t] = new
        return grad

    def eval_loss(self, n_clients: int, batch: int = 32) -> float:
        if self._eval is None:
            self._eval = self._batch(self.data.eval_batch(n_clients, batch))
        with jax.default_matmul_precision(self.precision):
            return float(self._loss(self.params, self._eval))


def leaf_norms(tree: Dict) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def follow(ref: FedReference, updates: List[Dict], eval_clients: int,
           *, alter: Optional[Callable] = None,
           alter_answers: Optional[Callable] = None) -> Dict:
    """Run the reference through the recorded first server updates.

    ``updates``: per update, ``contrib`` [(client id, base version)] and
    ``staleness`` (None for FedAvg). ``alter_answers(i, deltas)`` plants a
    fault where the clients' deltas are produced, ``alter(i, deltas,
    weights, stale)`` one where they are aggregated: faults in the
    reference put in the program's place, for the calibration of limits.
    Returns the eval loss after each update, the clients' deltas (the
    answers) of the first update, the leaf norms of FedAdam's first
    gradient, and the leaf norms of the parameters' change after the last
    update."""
    p0 = {k: np.asarray(v, np.float64) for k, v in ref.params.items()}
    losses, grad1, answers1 = [], None, []
    for i, u in enumerate(updates):
        deltas, weights = [], []
        for cid, ver in u["contrib"]:
            d, w = ref.client_delta(cid, ver)
            deltas.append(d)
            weights.append(w)
        if alter_answers is not None:
            deltas = alter_answers(i, deltas)
        if i == 0:
            answers1 = list(deltas)
        stale = None if u["staleness"] is None else \
            [ref.version - ver for _, ver in u["contrib"]]
        if alter is not None:
            deltas, weights, stale = alter(i, deltas, weights, stale)
        g = ref.apply(deltas, weights, stale)
        if i == 0:
            grad1 = leaf_norms(g)
        losses.append(ref.eval_loss(eval_clients))
    change = {k: float(np.linalg.norm(
        (np.asarray(v, np.float64) - p0[k]).ravel()))
        for k, v in ref.params.items()}
    return {"losses": losses, "answers1": answers1, "grad1": grad1,
            "change": change}
