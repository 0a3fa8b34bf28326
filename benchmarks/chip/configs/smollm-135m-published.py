"""smollm-135m-published: the plain reference and the FLOPs count.

SmolLM-135M (HuggingFaceTB; Llama layout): token embedding tied to the
output head, 30 pre-norm blocks of RMSNorm -> grouped-query attention
(9 query heads, 3 key/value heads, head size 64, rotary positions, causal
softmax) -> residual, RMSNorm -> SwiGLU feed-forward (1536) -> residual;
final RMSNorm. Loss: mean next-token negative log-likelihood over the
masked positions.

Numerics as the published config.json states them: every Linear and
Embedding weight drawn from N(0, ``initializer_range``**2) with 0.02, norms
one, RMSNorm epsilon ``rms_norm_eps`` 1e-5 (HF Llama's ``_init_weights``).
A model dict without those keys (the benchmark's tiny rehearsal models)
takes the system's defaults: fixed scales for the embedding and the
attention output, 1/sqrt(second-to-last dimension) for every other
weight, epsilon 1e-6.

Written from that description in plain ``jax.numpy``, with one full
softmax per head (no blocking); the benchmark runs it in float32 at
``highest`` matmul precision. Parameter names, shapes, order and random
draws follow the system's initialisation so that the same seed gives the
same weights.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax


def _shapes(m: Dict):
    d, V, L, f = m["d_model"], m["vocab_size"], m["num_layers"], m["d_ff"]
    H, Hkv = m["num_heads"], m["num_kv_heads"]
    hd = d // H
    std = m.get("initializer_range")
    return [("embed", (V, d), "normal",
             1.0 / math.sqrt(d) if std is None else std),
            ("final_norm", (d,), "ones", None),
            ("blocks/attn_norm", (L, d), "ones", None),
            ("blocks/wq", (L, d, H, hd), "normal", std),
            ("blocks/wk", (L, d, Hkv, hd), "normal", std),
            ("blocks/wv", (L, d, Hkv, hd), "normal", std),
            ("blocks/wo", (L, H, hd, d), "normal",
             1.0 / math.sqrt(H * hd) if std is None else std),
            ("blocks/ffn_norm", (L, d), "ones", None),
            ("blocks/w_gate", (L, d, f), "normal", std),
            ("blocks/w_up", (L, d, f), "normal", std),
            ("blocks/w_down", (L, f, d), "normal", std)]


def init(m: Dict, seed: int) -> Dict[str, jnp.ndarray]:
    """float32 weights from the seed: N(0, 1) times the stated scale (the
    std where one is given), else 1/sqrt(second-to-last dimension); norms
    one."""
    key = jax.random.PRNGKey(seed)
    params = {}
    for name, shape, kind, scale in _shapes(m):
        if kind == "ones":
            params[name] = jnp.ones(shape, jnp.float32)
            continue
        key, sub = jax.random.split(key)
        if scale is None:
            scale = 1.0 / math.sqrt(shape[-2])
        params[name] = jax.random.normal(sub, shape, jnp.float32) * scale
    return params


def _rms(x, g, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * g.astype(jnp.float32)).astype(dt)


def _rope(x, theta):
    """Rotary positions on (B, S, H, hd), halves rotated as pairs."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _block(m, p, x, eps):
    B, S, d = x.shape
    H, Hkv = m["num_heads"], m["num_kv_heads"]
    hd = d // H
    h = _rms(x, p["attn_norm"], eps)
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, p["wq"]), m["rope_theta"])
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, p["wk"]), m["rope_theta"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    k = jnp.repeat(k, H // Hkv, axis=2)       # query head j reads kv j // g
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1),
                   v.astype(jnp.float32)).astype(x.dtype)
    x = x + jnp.einsum("bshk,hkd->bsd", a, p["wo"])
    h = _rms(x, p["ffn_norm"], eps)
    gate = h @ p["w_gate"]
    return x + (gate * jax.nn.sigmoid(gate) * (h @ p["w_up"])) @ p["w_down"]


def loss(m: Dict, params: Dict, batch: Dict, unroll: bool = False):
    """Mean masked next-token NLL of ``batch`` (tokens, labels (B, S),
    mask (B, S-1)). Blocks run one after another under ``lax.scan`` over
    the stacked layers, or with ``unroll`` in a Python loop (XLA's cost
    analysis counts a loop body once; an unrolled 30-block step takes the
    TPU compiler minutes)."""
    eps = m.get("rms_norm_eps", 1e-6)
    x = params["embed"][batch["tokens"]]
    blocks = {k.split("/", 1)[1]: v for k, v in params.items()
              if k.startswith("blocks/")}
    if unroll:
        for layer in range(m["num_layers"]):
            x = _block(m, {k: v[layer] for k, v in blocks.items()}, x, eps)
    else:
        x, _ = lax.scan(lambda h, p: (_block(m, p, h, eps), None), x,
                        blocks)
    x = _rms(x, params["final_norm"], eps)
    logits = (x[:, :-1].astype(jnp.float32)
              @ params["embed"].T.astype(jnp.float32))
    gold = jnp.take_along_axis(logits, batch["labels"][:, 1:, None],
                               axis=-1)[..., 0]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - gold
    mask = batch["mask"].astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def train_flops_per_token(m: Dict, seq_len: int) -> float:
    """Useful forward + backward FLOPs per token: 3 x the forward's matmul
    FLOPs (2 per multiply-add), attention over the causal pairs only. The
    embedding lookup is a gather and counts nothing; the tied output head
    runs on the seq_len - 1 positions that are predicted."""
    d, f, V, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    H, Hkv = m["num_heads"], m["num_kv_heads"]
    hd = d // H
    proj = 2 * d * hd * (2 * H + 2 * Hkv) + 2 * 3 * d * f
    attn_per_row = 4 * H * hd * seq_len * (seq_len + 1) / 2
    per_row = (L * (seq_len * proj + attn_per_row)
               + (seq_len - 1) * 2 * d * V)
    return 3.0 * per_row / seq_len
