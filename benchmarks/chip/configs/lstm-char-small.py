"""lstm-char-small: the plain reference and the FLOPs count.

Character-aware CNN-LSTM word LM (Kim et al. 2016, arXiv 1508.06615), as
the Green FL paper (arXiv 2303.14604, section 3.2) trains it on phones:
char embedding -> valid 1-D convolutions of several widths with tanh and
max over positions -> one highway layer -> projection -> 2 LSTM layers ->
ReLU MLP -> softmax over the word vocabulary. Loss: mean next-word
negative log-likelihood over the masked positions.

Written from that description in plain ``jax.numpy``; the parameter names,
shapes, order and random draws follow the system's initialisation so that
the same seed gives the same weights. Departures from the paper, kept
because the system under test has them: the highway layer has one gate
pair (Kim et al. allow several), the forget gate gets a +1 bias inside the
sigmoid, and the decoder has a ReLU MLP before the softmax.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax


def _shapes(m: Dict):
    ce, V = m["char_emb"], m["vocab_size"]
    cnn_out = sum(n for _, n in m["cnn_filters"])
    L, d, Hd, f = m["num_layers"], m["d_model"], m["lstm_hidden"], m["d_ff"]
    out = [("char_embed", (m["char_vocab"], ce), "normal", 0.1)]
    for w, n in m["cnn_filters"]:
        out.append((f"cnn/w{w}", (w, ce, n), "normal", None))
        out.append((f"cnn/b{w}", (n,), "zeros", None))
    out += [("highway/wt", (cnn_out, cnn_out), "normal", None),
            ("highway/bt", (cnn_out,), "zeros", None),
            ("highway/wh", (cnn_out, cnn_out), "normal", None),
            ("highway/bh", (cnn_out,), "zeros", None),
            ("proj_in", (cnn_out, d), "normal", None),
            ("lstm/wx", (L, d, 4 * Hd), "normal", None),
            ("lstm/wh", (L, Hd, 4 * Hd), "normal", None),
            ("lstm/bias", (L, 4 * Hd), "zeros", None),
            ("mlp/w1", (Hd, f), "normal", None),
            ("mlp/b1", (f,), "zeros", None),
            ("unembed", (f, V), "normal", None)]
    return out


def init(m: Dict, seed: int) -> Dict[str, jnp.ndarray]:
    """float32 weights from the seed: N(0, 1) times 0.1 for the char
    embedding and 1/sqrt(fan-in) elsewhere; biases zero."""
    key = jax.random.PRNGKey(seed)
    params = {}
    for name, shape, kind, scale in _shapes(m):
        if kind == "zeros":
            params[name] = jnp.zeros(shape, jnp.float32)
            continue
        key, sub = jax.random.split(key)
        if scale is None:
            scale = 1.0 / math.sqrt(shape[-2] if len(shape) >= 2
                                    else shape[-1])
        params[name] = jax.random.normal(sub, shape, jnp.float32) * scale
    return params


def _lstm(x, wx, wh, b, unroll: bool):
    """x: (B, S, d) -> (B, S, Hd); zero initial state."""
    B, Hd = x.shape[0], wh.shape[0]
    xg = x @ wx + b

    def step(carry, g_x):
        h, c = carry
        g = g_x + h @ wh
        i, f, gg, o = jnp.split(g, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(gg)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    carry = (jnp.zeros((B, Hd), x.dtype), jnp.zeros((B, Hd), x.dtype))
    if unroll:
        hs = []
        for t in range(x.shape[1]):
            carry, h = step(carry, xg[:, t])
            hs.append(h)
        return jnp.stack(hs, axis=1)
    _, hs = lax.scan(step, carry, jnp.swapaxes(xg, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def loss(m: Dict, params: Dict, batch: Dict, unroll: bool = False):
    """Mean masked next-word NLL of ``batch`` (chars (B, S, W), labels
    (B, S), mask (B, S-1))."""
    x = params["char_embed"][batch["chars"]]               # (B, S, W, ce)
    W = x.shape[-2]
    feats = []
    for w, _ in m["cnn_filters"]:
        ker, conv = params[f"cnn/w{w}"], 0.0
        for i in range(w):                                 # valid conv
            conv = conv + x[..., i:W - w + 1 + i, :] @ ker[i]
        feats.append(jnp.max(jnp.tanh(conv + params[f"cnn/b{w}"]), axis=-2))
    f = jnp.concatenate(feats, axis=-1)
    t = jax.nn.sigmoid(f @ params["highway/wt"] + params["highway/bt"])
    h = jax.nn.relu(f @ params["highway/wh"] + params["highway/bh"])
    x = (t * h + (1.0 - t) * f) @ params["proj_in"]
    for layer in range(m["num_layers"]):
        x = _lstm(x, params["lstm/wx"][layer], params["lstm/wh"][layer],
                  params["lstm/bias"][layer], unroll)
    h = jax.nn.relu(x @ params["mlp/w1"] + params["mlp/b1"])
    logits = (h[:, :-1] @ params["unembed"]).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, batch["labels"][:, 1:, None],
                               axis=-1)[..., 0]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - gold
    mask = batch["mask"].astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def train_flops_per_token(m: Dict, seq_len: int) -> float:
    """Useful forward + backward FLOPs per token: 3 x the forward's
    matmul and convolution FLOPs (2 per multiply-add). The embedding
    lookup is a gather and counts nothing; elementwise work is left out.
    The decoder runs on the seq_len - 1 positions that are predicted."""
    ce, W = m["char_emb"], m["max_word_len"]
    cnn_out = sum(n for _, n in m["cnn_filters"])
    d, Hd, f, V = m["d_model"], m["lstm_hidden"], m["d_ff"], m["vocab_size"]
    conv = sum(2 * (W - w + 1) * w * ce * n for w, n in m["cnn_filters"])
    per_word = (conv + 2 * 2 * cnn_out * cnn_out + 2 * cnn_out * d
                + m["num_layers"] * 2 * (d + Hd) * 4 * Hd + 2 * Hd * f)
    per_row = seq_len * per_word + (seq_len - 1) * 2 * f * V
    return 3.0 * per_row / seq_len
