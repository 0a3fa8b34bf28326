"""Server-side aggregation (the PAPAYA Aggregator) + FedAdam update.

Sync (FedAvg): example-weighted mean of client deltas.
Async (FedBuff): staleness-scaled mean over the buffer, weight
(1+staleness)^-alpha (Nguyen et al. 2022).

Wire compression (paper §6 lever): deltas optionally round-trip through the
blockwise-int8 codec (kernels/int8_quant) before entering the buffer,
exactly like a production uplink would — so its quality effect (if any) is
part of the training loop, not just an accounting trick.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.int8_quant import ops as q8


def compress_roundtrip(delta: Dict[str, jnp.ndarray], block: int = 256
                       ) -> Dict[str, jnp.ndarray]:
    """Simulate the int8 uplink: quantize + dequantize each leaf (the
    Pallas kernel on a TPU, the jnp reference elsewhere)."""
    return {k: q8.quant_dequant(v, block=block) for k, v in delta.items()}


@jax.jit
def weighted_mean_deltas(deltas: Dict[str, jnp.ndarray],
                         weights: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """deltas: dict of (N, ...) stacked client deltas; weights: (N,)."""
    w = weights / jnp.maximum(jnp.sum(weights), 1e-12)

    def avg(v):
        wb = w.reshape((-1,) + (1,) * (v.ndim - 1))
        return jnp.sum(v * wb, axis=0)

    return {k: avg(v) for k, v in deltas.items()}


def fedbuff_weights(staleness: Sequence[int], alpha: float) -> np.ndarray:
    s = np.asarray(staleness, np.float64)
    return (1.0 + s) ** (-alpha)
