"""Model construction + analytic parameter accounting.

The parameter counts feed the Green-FL energy model (client FLOPs ->
duration -> energy) through ``ModelConfig.param_count``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import (AUDIO, CHARLM, DENSE, HYBRID, MOE, SSM, VLM,
                                ModelConfig)


def get_model(cfg: ModelConfig, *, decode_window: int = 0,
              remat: bool = False):
    from repro.models.charlm import CharLM
    from repro.models.encdec import EncDecLM
    from repro.models.griffin import Griffin
    from repro.models.rwkv import RWKV6
    from repro.models.transformer import DecoderLM

    fam = cfg.family
    if fam in (DENSE, MOE, VLM):
        return DecoderLM(cfg, decode_window=decode_window, remat=remat)
    if fam == SSM:
        return RWKV6(cfg, remat=remat)
    if fam == HYBRID:
        return Griffin(cfg, remat=remat)
    if fam == AUDIO:
        return EncDecLM(cfg, remat=remat)
    if fam == CHARLM:
        return CharLM(cfg, remat=remat)
    raise ValueError(fam)


@functools.lru_cache(maxsize=64)
def param_shapes_and_axes(cfg: ModelConfig):
    """Exact param ShapeDtypeStructs + logical axes, with no allocation."""
    model = get_model(cfg)
    axes_box = {}

    def initf(r):
        params, axes = model.init(r, dtype=jnp.bfloat16)
        axes_box.update(axes)
        return params

    shapes = jax.eval_shape(initf, jax.ShapeDtypeStruct((2,), jnp.uint32))
    return shapes, dict(axes_box)


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes, axes = param_shapes_and_axes(cfg)
    total = 0.0
    for k, s in shapes.items():
        n = 1
        for d in s.shape:
            n *= d
        if active_only and cfg.moe is not None and "experts" in axes[k]:
            n *= cfg.moe.top_k / cfg.moe.num_experts
        total += n
    return int(total)
