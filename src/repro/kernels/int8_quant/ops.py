"""Public int8-codec ops: the Pallas kernels on a TPU, the pure-jnp oracle
elsewhere. ``interpret=True`` runs the Pallas kernels in interpret mode on
any backend (kernel tests off the chip); nothing else selects it."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.int8_quant import kernel as K
from repro.kernels.int8_quant import ref as R


def _pallas(interpret: bool) -> bool:
    return interpret or jax.default_backend() == "tpu"


def quantize(x: jnp.ndarray, block: int = 256, *, interpret: bool = False
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if _pallas(interpret):
        return K.quantize_pallas(x, block=block, interpret=interpret)
    return R.quantize_ref(x, block)


def dequantize(q, s, shape, block: int = 256):
    return R.dequantize_ref(q, s, shape, block)


def quant_dequant(x: jnp.ndarray, block: int = 256, *,
                  interpret: bool = False) -> jnp.ndarray:
    if _pallas(interpret):
        q, s = K.quantize_pallas(x, block=block, interpret=interpret)
        return R.dequantize_ref(q, s, x.shape, block).astype(x.dtype)
    return R.quant_dequant_ref(x, block)


def dequant_accumulate(acc: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
                       weight, block: int = 256, *,
                       interpret: bool = False) -> jnp.ndarray:
    if _pallas(interpret):
        nb = q.shape[0]
        flat = acc.astype(jnp.float32).reshape(-1)
        pad = nb * block - flat.shape[0]
        acc2d = jnp.pad(flat, (0, pad)).reshape(nb, block)
        out = K.dequant_accumulate_pallas(acc2d, q, s, weight,
                                          interpret=interpret)
        return out.reshape(-1)[: flat.shape[0]].reshape(acc.shape).astype(acc.dtype)
    return R.dequant_accumulate_ref(acc, q, s, weight, block)


def wire_bytes(x_size: int, block: int = 256) -> int:
    """Bytes on the wire for an int8-compressed tensor of x_size elements."""
    nb = -(-x_size // block)
    return x_size + 4 * nb  # int8 payload + f32 scale per block
