"""Logical-axis -> mesh sharding rules (GSPMD via NamedSharding).

Every model param/cache leaf carries a tuple of logical axis names (one per
dim). ``spec_for`` maps those to a PartitionSpec given the mesh, with
per-dim divisibility checks so illegal shardings silently fall back to
replication (e.g. smollm's 9 heads on a 16-way model axis).

Default production rules (single pod, mesh ("data", "model")):
  heads/kv_heads/ffn/experts/vocab/rnn -> "model"   (tensor / expert parallel)
  embed                                -> "data"    (FSDP: params+opt sharded)
  batch                                -> ("pod","data")  [+ "pod" when present]
  layers / head_dim / cache / None     -> replicated

MoE expert-parallel note: experts shard over "model" when divisible
(granite 32e/16); otherwise the FFN dim carries the model axis (mixtral 8e).
Both are expressed by listing "experts" BEFORE "ffn" in the rule table and
letting divisibility resolve the winner per arch.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[str, Tuple[str, ...], None]

# logical axis -> preferred mesh axes, in priority order
DEFAULT_RULES: Dict[str, Sequence[MeshAxes]] = {
    "batch": (("pod", "data"), "data"),
    "experts": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "ffn_out": (),
    "vocab": ("model",),
    "rnn": ("model",),
    "embed": (("pod", "data"), "data"),     # FSDP
    "embed_out": (),
    "cache": (),
    "layers": (),
    "head_dim": (),
    "head_dim2": (),
}

# Serving (decode) rules: weights stay RESIDENT — 2D-sharded over
# ("model","data") where divisible so a 141B MoE fits 256 chips without
# per-step FSDP all-gathers; activations (tiny at decode: B x d) move
# instead. KV caches shard over batch; FSDP ("embed") is disabled.
SERVE_RULES: Dict[str, Sequence[MeshAxes]] = {
    "batch": (("pod", "data"), "data"),
    "experts": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": (("model", "data"), "model", "data"),
    "ffn_out": (),
    "vocab": (("model", "data"), "model", "data"),
    "rnn": ("model", "data"),
    "embed": (),
    "embed_out": (),
    "head_dim": ("data",),
    "cache": (),
    "layers": (),
    "head_dim2": (),
}


def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _mesh_has(mesh: Mesh, axes: MeshAxes) -> bool:
    if axes is None:
        return True
    if isinstance(axes, str):
        axes = (axes,)
    return all(a in mesh.shape for a in axes)


def spec_for(logical: Sequence[Optional[str]], shape: Sequence[int],
             mesh: Mesh, rules: Optional[Dict[str, Sequence[MeshAxes]]] = None
             ) -> P:
    """Resolve one leaf's PartitionSpec. Replicates any dim whose preferred
    mesh axes are absent, already used, or don't divide the dim size."""
    rules = rules or DEFAULT_RULES
    used: set = set()
    out = []
    for name, dim in zip(logical, shape):
        placed = None
        for cand in (rules.get(name, ()) if name else ()):
            if cand is None:
                continue
            cand_t = (cand,) if isinstance(cand, str) else tuple(cand)
            if not _mesh_has(mesh, cand_t):
                continue
            if any(a in used for a in cand_t):
                continue
            if dim % _axis_size(mesh, cand_t) != 0:
                continue
            placed = cand_t if len(cand_t) > 1 else cand_t[0]
            used.update(cand_t)
            break
        out.append(placed)
    # drop trailing Nones for tidiness
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def batch_spec(mesh: Mesh, ndim: int, *, batch_dim: int = 0,
               shape: Optional[Sequence[int]] = None) -> P:
    """Shard the batch dim over ("pod","data") where divisible."""
    axes: MeshAxes = ("pod", "data") if "pod" in mesh.shape else ("data",)
    if shape is not None and shape[batch_dim] % _axis_size(mesh, axes) != 0:
        # try data only
        axes = ("data",)
        if shape[batch_dim] % _axis_size(mesh, axes) != 0:
            axes = None
    spec = [None] * ndim
    if axes:
        # keep the tuple form even for a single axis so specs compare
        # consistently (PartitionSpec('data') != PartitionSpec(('data',)))
        spec[batch_dim] = tuple(axes)
    return P(*spec)


def current_mesh() -> Optional[Mesh]:
    """The mesh installed by ``with mesh:`` (empty -> None)."""
    try:
        from jax._src.mesh import thread_resources
        m = thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None


def constrain_batch(x, dim: int = 0):
    """with_sharding_constraint pinning the batch dim over ("pod","data").

    GSPMD sometimes resolves the FSDP-weight x batch-sharded-activation
    contraction by all-gathering ACTIVATIONS (replicating the whole forward
    on every data shard). Pinning activations after each block keeps the
    batch distributed. No-op outside a mesh context or when indivisible.
    """
    m = current_mesh()
    if m is None or x.ndim <= dim:
        return x
    axes = tuple(a for a in ("pod", "data") if a in m.shape)
    if not axes:
        return x
    size = 1
    for a in axes:
        size *= m.shape[a]
    if x.shape[dim] % size != 0:
        axes = ("data",) if "data" in m.shape else ()
        if not axes or x.shape[dim] % m.shape["data"] != 0:
            return x
    spec = [None] * x.ndim
    spec[dim] = axes if len(axes) > 1 else axes[0]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(m, P(*spec)))


def constrain_replicated(x):
    """Pin a (small) activation to full replication — decode-time FFN inputs
    are (B, d) ~ 1 MB; replicating them lets 2D-sharded resident weights
    matmul locally with partial-sum all-reduces instead of weight gathers."""
    m = current_mesh()
    if m is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(m, P(*([None] * x.ndim))))

