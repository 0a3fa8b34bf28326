"""Real JAX learner: actual federated training of any model-zoo config.

Holds server params + FedAdam state, compiles the client local-SGD step
once (ragged client datasets are padded into one fixed shape of
``max_client_steps`` steps; the loop stops at the cohort's last real
step, so the shape stays fixed and the trip count does not), and —
for FedBuff — keeps a ring of recent param versions so stale clients
really do train against the model they were sent (true staleness, not an
approximation). Deltas optionally round-trip the int8 wire codec.

The client deltas come back to the caller as read-only host rows. Once
a caller has passed one of them back to ``apply``, the learner keeps the
device copies its client programs make until the next server step, and
``apply`` aggregates a row the learner returned (each leaf the very array
it handed out) from that copy. It uploads the deltas that are not its own
(a row the caller altered or copied) and those it holds no copy of. A
caller that only reads the deltas, as a warm-up that stacks them itself
does, has no copy held in device memory beside its own.

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
from repro.federated.client import (make_client_update, make_cohort_update,
                                    stack_batches, trip_count)
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
        self._vmapped_update = make_cohort_update(self.model.loss,
                                                  fed.client_lr)
        self.version = 0
        self._history: List[Tuple[int, Dict[str, np.ndarray]]] = []
        self._push_history()
        self._eval_batch = None

        def server_step(params, opt_state, mean_delta):
            # FedAdam: server "gradient" is the negative aggregated delta
            grads = {k: -v for k, v in mean_delta.items()}
            return self.opt.update(grads, opt_state, params)

        self._server_step = jax.jit(server_step)
        # id of a returned row's first leaf -> (row, its device tree or None
        # where no copy is kept, row index in the tree or None where the tree
        # is that one client's alone)
        self._lead = next(iter(self.params))
        self._rows: Dict[int, Tuple[Dict[str, np.ndarray], Optional[Dict],
                                    Optional[int]]] = {}
        self._rows_come_back = False   # apply has been passed one of them

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
        on the device and as read-only host arrays. The call copies its
        host arguments to the device (the batches and mask, and a stale
        base from the host ring) and dispatches the program, so one span
        holds the copies and the dispatch."""
        stale = version is not None and version != self.version
        base = self.params_at(version) if stale else self.params
        with spans.span("client.to_device", update=update,
                        copies=(base, data, mask) if stale else (data, mask)):
            deltas, _ = update_fn(base, data, mask)
        spans.count("client.programs", 1)
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
            host = jax.device_get(deltas)
        for x in host.values():
            x.flags.writeable = False
        return deltas, host

    def _keep(self, rows: List[Dict[str, np.ndarray]], tree: Dict,
              batched: bool) -> None:
        """Records the host rows returned, with their device tree once a
        caller has been seen to pass rows back."""
        tree = tree if self._rows_come_back else None
        for i, row in enumerate(rows):
            self._rows[id(row[self._lead])] = (row, tree,
                                               i if batched else None)

    def _own(self, delta: Dict[str, np.ndarray]):
        """(row, device tree, index) where each leaf of ``delta`` is the
        array the learner returned in that row, else None."""
        kept = self._rows.get(id(delta.get(self._lead)))
        if kept is None or delta.keys() != kept[0].keys() or any(
                delta[k] is not v for k, v in kept[0].items()):
            return None
        return kept

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
        _count_rows(cohort["mask"], cmask)
        n_ex = [float(min(len(b), self.max_steps) * self.fed.client_batch_size)
                for b in batches]
        # a cohort kept from a call that no apply followed would stay live
        # beside this one's program
        self._rows.clear()
        tree, out = self._train(self._vmapped_update, version, cohort, cmask,
                                feeds)
        rows = [{k: v[i] for k, v in out.items()}
                for i in range(len(client_ids))]
        self._keep(rows, tree, batched=True)
        return rows, n_ex

    def client_delta(self, client_id: int, version: Optional[int] = None):
        """Run real local training; returns (delta dict, example weight)."""
        feeds = self.version + 1
        batches = self.dataset.client_batches(
            client_id, self.fed.client_batch_size, self.fed.local_epochs)
        with spans.span("client.pack", update=feeds):
            stacked, mask = stack_batches(batches, self.max_steps)
        _count_rows(stacked["mask"], mask)
        n_ex = min(len(batches), self.max_steps) * self.fed.client_batch_size
        tree, out = self._train(self._client_update, version, stacked, mask,
                                feeds)
        self._keep([out], tree, batched=False)
        return out, float(n_ex)

    def apply(self, deltas: List[Dict[str, np.ndarray]], weights: List[float],
              *, n_contributors: int = 0, mean_staleness: float = 0.0,
              staleness: Optional[List[int]] = None) -> None:
        """One server step from exactly the deltas passed. A delta whose
        every leaf is the array the learner returned for it is taken from
        the device copy its client program made, where one is kept; any
        other is uploaded. The first such delta passed turns the keeping
        of copies on. Every kept copy is dropped once the step is
        dispatched."""
        assert deltas, "apply() with empty buffer"
        feeds = self.version + 1
        w = np.asarray(weights, np.float32)
        if staleness is not None:  # FedBuff staleness scaling
            w = w * aggregation.fedbuff_weights(staleness,
                                                self.fed.staleness_exponent)
        stacked = self._server_input(deltas, feeds)
        with spans.span("server.update", update=feeds):
            mean_delta = aggregation.weighted_mean_deltas(stacked,
                                                          jnp.asarray(w))
            self.params, self.opt_state = self._server_step(
                self.params, self.opt_state, mean_delta)
        self._rows.clear()
        self.version += 1
        self._push_history()

    def _server_input(self, deltas: List[Dict[str, np.ndarray]],
                      update: int) -> Dict:
        """The (n, ...) device tree of the passed deltas, in order: a kept
        cohort's own tree where it is passed whole and in order, else the
        kept single clients' trees, the kept cohorts' rows and the uploaded
        deltas laid end to end and taken in the order passed."""
        own = [self._own(d) for d in deltas]
        self._rows_come_back |= any(own)
        kept = [o if o is not None and o[1] is not None else None
                for o in own]
        upload = [d for d, o in zip(deltas, kept) if o is None]
        spans.count("server.deltas_resident", len(deltas) - len(upload))
        spans.count("server.deltas_uploaded", len(upload))
        singles, blocks, at = [], [], {}      # at: id(tree) -> first row
        n = 0
        for o in kept:
            if o is not None and o[2] is None and id(o[1]) not in at:
                at[id(o[1])] = n
                singles.append(o[1])
                n += 1
        for o in kept:
            if o is not None and o[2] is not None and id(o[1]) not in at:
                at[id(o[1])] = n
                blocks.append(o[1])
                n += o[1][self._lead].shape[0]
        if upload:
            with spans.span("server.to_device", update=update,
                            copies=upload):
                blocks.append({k: jnp.stack([d[k] for d in upload])
                               for k in upload[0]})
        order, up = [], n
        for o in kept:
            if o is None:
                order.append(up)
                up += 1
            else:
                order.append(at[id(o[1])] + (o[2] or 0))
        if order == list(range(up)):
            if len(blocks) == 1 and not singles:
                return blocks[0]
            order = None
        return _gather_rows(blocks, singles,
                            None if order is None else np.asarray(order))

    def eval_perplexity(self) -> float:
        with spans.span("server.eval", update=self.version):
            if self._eval_batch is None:
                self._eval_batch = self.dataset.eval_batch(
                    self.run.eval_clients, batch_size=32)
                self._eval_fn = jax.jit(
                    lambda p, b: self.model.loss(p, b)[0])
            loss = self._eval_fn(self.params, self._eval_batch)
            return float(np.exp(np.clip(np.asarray(loss), 0, 20)))


@jax.jit
def _gather_rows(blocks: List[Dict], singles: List[Dict], order):
    """Per leaf: the rows of ``singles`` (one row each) and of ``blocks``
    (trees with a leading row axis) laid end to end, then taken in
    ``order`` (None keeps them as laid)."""
    def leaf(k):
        parts = [b[k] for b in blocks]
        if singles:
            parts.insert(0, jnp.stack([s[k] for s in singles]))
        x = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        return x if order is None else jnp.take(x, order, axis=0)
    return {k: leaf(k) for k in (singles or blocks)[0]}


def _count_rows(mask: np.ndarray, step_mask: np.ndarray) -> None:
    """Rows the client update computes (clients x the steps its loop runs
    x batch), the rows of padded steps past the loop's end that it skips,
    and the rows it computes that hold data: a data row's mask is 1 at its
    first position, a padding row's is 0."""
    if spans.enabled():
        steps = step_mask.shape[-1]
        clients, batch = step_mask.size // steps, mask.shape[-2]
        n = int(trip_count(step_mask))
        spans.count("client.rows_real", int(mask[..., 0].sum()))
        spans.count("client.rows_computed", clients * n * batch)
        spans.count("client.rows_skipped", clients * (steps - n) * batch)
