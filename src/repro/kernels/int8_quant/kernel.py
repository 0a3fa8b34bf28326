"""Pallas TPU kernels for blockwise int8 quantize / fused dequant-accumulate.

Each quantization block (``block`` lanes, a multiple of 128) is one row of
an (nb, block) matrix. A grid step streams a tile of rows HBM->VMEM,
reduces |max| along the lanes, and writes int8 + scales back. The row count
of a tile is a multiple of 32, the sublane tiling of int8, so the int8
output is stored as whole (32, 128) tiles. Scales travel as an (nb, 1)
column: a rank-1 block of a few rows is not a legal TPU block shape, a
(rows, 1) block of a 2-D array is. The dequant-accumulate kernel fuses the
FedBuff buffer update (acc += w * q*scale) into a single pass so the server
never materializes the dequantized f32 update in HBM; its weight is a
scalar in SMEM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT8_SUBLANES = 32        # int8 vreg tile is (32, 128)
MAX_ROWS_PER_TILE = 512   # 512 x 256 f32 = 512 KiB in, 128 KiB int8 out


def rows_per_tile(nb: int) -> int:
    """Quant blocks per grid step: whole int8 sublane tiles, at most
    MAX_ROWS_PER_TILE (small tensors take a single grid step)."""
    return min(MAX_ROWS_PER_TILE, -(-nb // INT8_SUBLANES) * INT8_SUBLANES)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]                                       # (R, block) f32
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)    # (R, 1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_pallas(x: jnp.ndarray, block: int = 256, interpret: bool = False
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: any shape; returns (q (nb, block) int8, scales (nb,) f32).
    nb is padded up to a whole number of tiles (``rows_per_tile``); padding
    rows quantize to q == 0 with scale 1."""
    flat = x.astype(jnp.float32).reshape(-1)
    rows = rows_per_tile(-(-flat.shape[0] // block))
    flat = jnp.pad(flat, (0, (-flat.shape[0]) % (block * rows)))
    xb = flat.reshape(-1, block)
    nb = xb.shape[0]
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb)
    return q, s.reshape(nb)


def _deq_acc_kernel(q_ref, s_ref, w_ref, acc_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)                   # (R, block)
    out_ref[...] = acc_ref[...] + w_ref[0, 0] * (q * s_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_accumulate_pallas(acc2d: jnp.ndarray, q: jnp.ndarray,
                              s: jnp.ndarray, weight, interpret: bool = False
                              ) -> jnp.ndarray:
    """acc2d: (nb, block) f32 accumulator laid out like q; s: (nb,)."""
    nb, block = q.shape
    rows = rows_per_tile(nb)
    pad = (-nb) % rows
    q = jnp.pad(q, ((0, pad), (0, 0)))
    s = jnp.pad(s.astype(jnp.float32), (0, pad)).reshape(-1, 1)
    acc2d = jnp.pad(acc2d, ((0, pad), (0, 0)))
    w = jnp.asarray(weight, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        _deq_acc_kernel,
        grid=((nb + pad) // rows,),
        in_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb + pad, block), jnp.float32),
        interpret=interpret,
    )(q, s, w, acc2d)
    return out[:nb]
