"""Pallas TPU flash attention with causal + sliding-window masking.

TPU adaptation: classic FlashAttention online-softmax, tiled
for VMEM — q tile (BLOCK_Q, D) and kv tiles (BLOCK_KV, D) with D padded to
lane width 128 and block sizes multiples of the MXU dim. Grid is
(batch*kv_head*group, n_q, n_kv); the LAST grid axis is sequential on TPU,
so the running (m, l, acc) state lives in VMEM scratch across kv steps and
the output tile is written once on the final kv block. Sliding-window
banding prunes work via `pl.when` on the block-level mask (a kv block
strictly outside the band contributes nothing and skips its matmuls).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 block_q: int, block_kv: int, window: int, causal: bool,
                 scale: float, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_kv
    # block-level band check (python-level constants + program ids)
    diag_ok = jnp.asarray(True)
    if causal:
        diag_ok &= k_start <= q_start + block_q - 1
    if window:
        diag_ok &= k_start + block_kv - 1 > q_start - window

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                      # (bq, D)
        k = k_ref[0].astype(jnp.float32)                      # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = jnp.ones((block_q, block_kv), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 256, block_kv: int = 256,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B,S,Hq,D); k/v: (B,S,Hkv,D). GQA via head grouping."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    assert S % block_q == 0 and S % block_kv == 0
    n_q, n_kv = S // block_q, S // block_kv
    scale = 1.0 / math.sqrt(D)

    # (B*Hq, S, D) for q/o; (B*Hkv, S, D) for k/v; q head bh -> kv head bh//g
    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_kv=block_kv, window=window,
        causal=causal, scale=scale, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max m
            pltpu.VMEM((block_q,), jnp.float32),      # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
