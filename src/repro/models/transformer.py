"""Decoder-only transformer covering the dense / MoE / VLM families.

Dense:  mistral-nemo-12b, smollm-135m, stablelm-3b, stablelm-1.6b
MoE:    mixtral-8x22b (SWA), granite-moe-1b-a400m
VLM:    internvl2-2b (precomputed patch embeddings prepended — frontend stub)

Pre-norm RMSNorm blocks, RoPE GQA attention (full or sliding-window),
SwiGLU FFN or capacity-based top-k MoE. Layer stack runs under lax.scan.
The training forward names its parts (``attention``, ``ffn``, ``lm_head``)
with ``jax.named_scope``, so a device trace's op metadata can split them.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import sharding as _sh
from repro.configs.base import ModelConfig
from repro.models import common as cm


class DecoderLM:
    def __init__(self, cfg: ModelConfig, *, decode_window: int = 0,
                 remat: bool = False, serve_replicated_ffn: bool = False):
        """decode_window > 0 enables the sliding-window ring-buffer decode
        variant (used for long_500k on otherwise full-attention archs).
        remat recomputes each layer in the backward pass (train shapes)."""
        self.cfg = cfg
        self.decode_window = decode_window or cfg.sliding_window
        self.is_moe = cfg.moe is not None
        self.remat = remat
        # GShard-style expert capacity for train/prefill (documented
        # deviation from Mixtral's dropless routing — DESIGN.md §4);
        # decode runs dropless (capacity = tokens x top_k).
        self.capacity_factor = 1.25
        # §Perf H1.3: replicate (tiny) decode activations across the data
        # axis for the FFN/unembed segment so 2D-resident weights are
        # matmul'd locally (partial-sum all-reduce) instead of gathered.
        self.serve_replicated_ffn = serve_replicated_ffn
        # §Perf H1.4: explicit shard_map flash-decoding (cache sharded along
        # its length over "model"; (B,H)-sized combine collectives).
        self.flash_decode = False
        # §Perf H1.6 (experimental): int8 KV cache (per-token symmetric
        # scales) — 2.2x less cache HBM; requires flash_decode.
        self.kv_quant = False

    # ---------------------------------------------------------------- init
    def init(self, rng, dtype=jnp.float32) -> Tuple[cm.Params, cm.Axes]:
        """Every normally drawn weight takes N(0, std**2) where the config
        sets ``initializer_range``; otherwise the fixed scales of ``embed``
        and ``wo`` and ``ParamBuilder``'s default for the rest."""
        cfg = self.cfg
        b = cm.ParamBuilder(rng, dtype)
        d, hd = cfg.d_model, cfg.resolved_head_dim
        H, Hkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
        std = cfg.initializer_range
        b.param("embed", (cfg.vocab_size, d), ("vocab", "embed"),
                scale=1.0 / math.sqrt(d) if std is None else std)
        if not cfg.tie_embeddings:
            b.param("unembed", (d, cfg.vocab_size), ("embed", "vocab"),
                    scale=std)
        b.param("final_norm", (d,), ("embed",), init="ones")
        # stacked per-layer params
        b.param("blocks/attn_norm", (L, d), ("layers", "embed"), init="ones")
        b.param("blocks/wq", (L, d, H, hd), ("layers", "embed", "heads", "head_dim"),
                scale=std)
        b.param("blocks/wk", (L, d, Hkv, hd), ("layers", "embed", "kv_heads", "head_dim"),
                scale=std)
        b.param("blocks/wv", (L, d, Hkv, hd), ("layers", "embed", "kv_heads", "head_dim"),
                scale=std)
        b.param("blocks/wo", (L, H, hd, d), ("layers", "heads", "head_dim", "embed"),
                scale=1.0 / math.sqrt(H * hd) if std is None else std)
        b.param("blocks/ffn_norm", (L, d), ("layers", "embed"), init="ones")
        if self.is_moe:
            E, f = cfg.moe.num_experts, cfg.d_ff
            b.param("blocks/router", (L, d, E), ("layers", "embed", "experts"),
                    scale=std)
            b.param("blocks/w_gate", (L, E, d, f), ("layers", "experts", "embed", "ffn"),
                    scale=std)
            b.param("blocks/w_up", (L, E, d, f), ("layers", "experts", "embed", "ffn"),
                    scale=std)
            b.param("blocks/w_down", (L, E, f, d), ("layers", "experts", "ffn", "embed"),
                    scale=std)
        else:
            f = cfg.d_ff
            b.param("blocks/w_gate", (L, d, f), ("layers", "embed", "ffn"), scale=std)
            b.param("blocks/w_up", (L, d, f), ("layers", "embed", "ffn"), scale=std)
            b.param("blocks/w_down", (L, f, d), ("layers", "ffn", "embed"), scale=std)
        return b.build()

    # ------------------------------------------------------------- forward
    def _layer(self, lp: Dict[str, jnp.ndarray], x: jnp.ndarray,
               positions_offset: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """One block on (B, S, d). Returns (x_out, k, v) (k/v for cache)."""
        cfg = self.cfg
        B, S, d = x.shape
        eps = cfg.rms_norm_eps
        with jax.named_scope("attention"):
            h = cm.rms_norm(x, lp["attn_norm"], eps)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
            pos = positions_offset + jnp.arange(S)
            cos, sin = cm.rope_angles(pos, cfg.resolved_head_dim,
                                      cfg.rope_theta)
            q = cm.apply_rope(q, cos, sin)
            k = cm.apply_rope(k, cos, sin)
            attn = cm.flash_attention(q, k, v, causal=True,
                                      window=cfg.sliding_window,
                                      block_q=min(512, S),
                                      block_kv=min(512, S))
            x = x + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])

        with jax.named_scope("ffn"):
            h = cm.rms_norm(x, lp["ffn_norm"], eps)
            if self.is_moe:
                out, aux = cm.moe_block(
                    h.reshape(B * S, d), lp["router"], lp["w_gate"],
                    lp["w_up"], lp["w_down"], top_k=cfg.moe.top_k,
                    capacity_factor=self.capacity_factor)
                return x + out.reshape(B, S, d), (k, v), aux
            x = x + cm.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x, (k, v), jnp.zeros((), jnp.float32)

    def _stack(self, params: cm.Params, x: jnp.ndarray,
               positions_offset: int = 0, collect_kv: bool = True):
        """Scan the layer stack; returns (x, stacked (k, v), aux_sum).
        collect_kv=False (train path) drops the per-layer KV scan outputs —
        they are only needed to build a prefill cache and would otherwise
        dominate activation memory under autodiff."""
        blocks = {k.split("/", 1)[1]: v for k, v in params.items()
                  if k.startswith("blocks/")}

        def body(x, lp):
            x, kv, aux = self._layer(lp, x, positions_offset)
            x = _sh.constrain_batch(x)
            return x, ((kv if collect_kv else None), aux)

        if self.remat:
            body = jax.checkpoint(body)
        x, (kvs, auxs) = lax.scan(body, x, blocks)
        return x, kvs, jnp.sum(auxs)

    def _embed(self, params, tokens, frontend=None):
        x = _sh.constrain_batch(params["embed"][tokens])
        if self.cfg.num_frontend_tokens and frontend is not None:
            x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
        return x

    def logits(self, params, x):
        with jax.named_scope("lm_head"):
            x = cm.rms_norm(x, params["final_norm"], self.cfg.rms_norm_eps)
            w = params["embed"].T if self.cfg.tie_embeddings \
                else params["unembed"]
            return jnp.einsum("bsd,dv->bsv", x, w)

    # ----------------------------------------------------------- train api
    def loss(self, params: cm.Params, batch: Dict[str, jnp.ndarray]
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        tokens = batch["tokens"]
        x = self._embed(params, tokens, batch.get("frontend"))
        x, _, aux = self._stack(params, x, collect_kv=False)
        nf = self.cfg.num_frontend_tokens if "frontend" in batch else 0
        with jax.named_scope("lm_head"):
            x = cm.rms_norm(x[:, nf:], params["final_norm"],
                            self.cfg.rms_norm_eps)
            w = params["embed"].T if self.cfg.tie_embeddings \
                else params["unembed"]
            loss = cm.lm_loss(x, w, batch["labels"], batch.get("mask", None))
        total = loss
        if self.is_moe:
            total = loss + self.cfg.moe.router_aux_weight * aux
        return total, {"xent": loss, "aux": aux}

    # ----------------------------------------------------------- serve api
    def init_cache(self, B: int, cache_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        C = min(cache_len, self.decode_window) if self.decode_window else cache_len
        shape = (cfg.num_layers, B, C, cfg.num_kv_heads, cfg.resolved_head_dim)
        axes = ("layers", "batch", "cache", "kv_heads", "head_dim")
        if self.kv_quant:
            cache = {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.ones(shape[:-1], jnp.float32),
                "v_scale": jnp.ones(shape[:-1], jnp.float32),
                "pos": jnp.zeros((), jnp.int32),
            }
            cache_axes = {"k": axes, "v": axes, "k_scale": axes[:-1],
                          "v_scale": axes[:-1], "pos": ()}
            return cache, cache_axes
        cache = {
            "k": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
        cache_axes = {"k": axes, "v": axes, "pos": ()}
        return cache, cache_axes

    def prefill(self, params, tokens, frontend=None, pad_to: int = 0):
        """Run the prompt; return (last-position logits, cache).
        pad_to > prompt length reserves cache slots for decode_step."""
        x = self._embed(params, tokens, frontend)
        x, (ks, vs), _ = self._stack(params, x)
        lg = self.logits(params, x[:, -1:, :])[:, 0]
        C = x.shape[1]
        if self.decode_window and C > self.decode_window:
            ks = ks[:, :, -self.decode_window:]
            vs = vs[:, :, -self.decode_window:]
            C = self.decode_window
        if pad_to > C:
            pad = [(0, 0), (0, 0), (0, pad_to - C), (0, 0), (0, 0)]
            ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
        cache = {"k": ks, "v": vs, "pos": jnp.asarray(x.shape[1], jnp.int32)}
        return lg, cache

    def decode_step(self, params, cache, tokens: jnp.ndarray):
        """tokens: (B,) int32. One autoregressive step."""
        cfg = self.cfg
        x = params["embed"][tokens][:, None, :]          # (B, 1, d)
        pos = cache["pos"]
        C = cache["k"].shape[2]
        # ring buffer for SWA variants; append (cache pre-sized) otherwise
        write_idx = pos % C if self.decode_window else jnp.minimum(pos, C - 1)
        blocks = {k.split("/", 1)[1]: v for k, v in params.items()
                  if k.startswith("blocks/")}
        if self.kv_quant:
            return self._decode_step_q8(params, cache, tokens, blocks)

        def body(x, per_layer):
            lp, kc, vc = per_layer
            h = cm.rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
            cos, sin = cm.rope_angles(pos[None], cfg.resolved_head_dim,
                                      cfg.rope_theta)
            q = cm.apply_rope(q, cos[None], sin[None])
            k = cm.apply_rope(k, cos[None], sin[None])
            valid = jnp.minimum(pos + 1, C)
            if self.flash_decode:
                attn, kc, vc = cm.flash_decode_attention(
                    q[:, 0], kc, vc, k[:, 0], v[:, 0], write_idx, valid)
            else:
                kc = lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), write_idx, axis=1)
                vc = lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), write_idx, axis=1)
                kc = _sh.constrain_batch(kc)
                vc = _sh.constrain_batch(vc)
                attn = cm.decode_attention(q[:, 0], kc, vc, valid)
            x = x + jnp.einsum("bhk,hkd->bd", attn, lp["wo"])[:, None, :]
            h = cm.rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
            if self.serve_replicated_ffn:
                h = _sh.constrain_replicated(h)
            if self.is_moe:
                out, _ = cm.moe_block(h[:, 0], lp["router"], lp["w_gate"],
                                      lp["w_up"], lp["w_down"],
                                      top_k=cfg.moe.top_k,
                                      capacity_factor=float(cfg.moe.num_experts))
                x = x + out[:, None, :]
            else:
                x = x + cm.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            return x, (kc, vc)

        x, (ks, vs) = lax.scan(body, x, (blocks, cache["k"], cache["v"]))
        lg = self.logits(params, x)[:, 0]
        new_cache = {"k": ks, "v": vs, "pos": pos + 1}
        return lg, new_cache

    def _decode_step_q8(self, params, cache, tokens, blocks):
        """int8-KV flash-decode step (§Perf H1.6)."""
        cfg = self.cfg
        x = params["embed"][tokens][:, None, :]
        pos = cache["pos"]
        C = cache["k"].shape[2]
        write_idx = pos % C if self.decode_window else jnp.minimum(pos, C - 1)

        def body(x, per_layer):
            lp, kc, vc, ks_, vs_ = per_layer
            h = cm.rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
            cos, sin = cm.rope_angles(pos[None], cfg.resolved_head_dim,
                                      cfg.rope_theta)
            q = cm.apply_rope(q, cos[None], sin[None])
            k = cm.apply_rope(k, cos[None], sin[None])
            valid = jnp.minimum(pos + 1, C)
            attn, kc, vc, ks_, vs_ = cm.flash_decode_attention_q8(
                q[:, 0], kc, vc, ks_, vs_, k[:, 0], v[:, 0], write_idx, valid)
            x = x + jnp.einsum("bhk,hkd->bd", attn, lp["wo"])[:, None, :]
            h = cm.rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
            if self.serve_replicated_ffn:
                h = _sh.constrain_replicated(h)
            if self.is_moe:
                out, _ = cm.moe_block(h[:, 0], lp["router"], lp["w_gate"],
                                      lp["w_up"], lp["w_down"],
                                      top_k=cfg.moe.top_k,
                                      capacity_factor=float(cfg.moe.num_experts))
                x = x + out[:, None, :]
            else:
                x = x + cm.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            return x, (kc, vc, ks_, vs_)

        x, (ks, vs, kss, vss) = lax.scan(
            body, x, (blocks, cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"]))
        lg = self.logits(params, x)[:, 0]
        return lg, {"k": ks, "v": vs, "k_scale": kss, "v_scale": vss,
                    "pos": pos + 1}
