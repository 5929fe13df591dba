"""Mesh-aware sharding rules: DP x TP (+pod), EP for MoE, SP for long KV.

Models call :func:`constrain` with *logical* axis names; when a mesh
context is active these become `with_sharding_constraint`, otherwise
they are no-ops (unit tests, single host).  Parameter shardings are
derived from pytree paths by :func:`param_shardings`.
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical -> physical mesh axes
_LOGICAL = {
    "batch": ("pod", "data"),  # gradient/data parallel (pod folds into DP)
    "model": ("model",),       # tensor/expert parallel
    "seq": ("data",),          # sequence parallel (long-context KV)
    "seq_tp": ("model",),      # KV-cache seq sharded over TP axis (GQA kv < tp)
    None: None,
}

_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar("repro_mesh", default=None)


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the rules below are GSPMD
    constraints, which jax refuses on the default Explicit axes."""
    return jax.make_mesh(
        axis_shapes,
        axis_names,
        axis_types=(AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    tok = _MESH.set(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _MESH.reset(tok)


def current_mesh() -> Optional[Mesh]:
    return _MESH.get()


def _resolve(mesh: Mesh, logical):
    """Logical axis -> physical axes present in this mesh (or None)."""
    if logical is None:
        return None
    phys = [a for a in _LOGICAL[logical] if a in mesh.axis_names]
    if not phys:
        return None
    return tuple(phys) if len(phys) > 1 else phys[0]


def pspec(mesh: Mesh, dims) -> P:
    return P(*[_resolve(mesh, d) for d in dims])


def constrain(x, *dims):
    """Constrain activation sharding by logical dims; no-op without mesh."""
    mesh = _MESH.get()
    if mesh is None:
        return x
    if x.ndim != len(dims):
        raise ValueError(f"rank {x.ndim} vs dims {dims}")
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec(mesh, dims)))


# ---------------------------------------------------------------------------
# parameter sharding rules (matched against '/'-joined pytree paths)
# ---------------------------------------------------------------------------
# Megatron-style TP: column-parallel in-projections, row-parallel
# out-projections; vocab-parallel embeddings; expert-parallel MoE.
_PARAM_RULES = [
    (r"unembed$", (None, "model")),             # [d, V]
    (r"(^|/)embed$", ("model", None)),          # [V, d] vocab-parallel
    (r"(wq|wk|wv)$", (None, "model")),          # column parallel
    (r"wo$", ("model", None)),                  # row parallel
    (r"(wu|wg)$", (None, "model")),             # MLP up/gate: column
    (r"wd$", ("model", None)),                  # MLP down: row
    (r"moe/(wu|wg)$", (None, None, "model")),   # [E, d, ff]: TP inside expert
    (r"moe/wd$", (None, "model", None)),
    (r"moe/router$", (None, None)),
    (r"in_proj$", (None, "model")),             # mamba in: column
    (r"out_proj$", ("model", None)),            # mamba out: row
]
# MoE expert-parallel alternative (E over model axis) is selected by
# rule-set name; see expert_parallel_rules().
_PARAM_RULES_EP = [
    (r"moe/(wu|wg)$", ("model", None, None)),   # [E, d, ff]: experts sharded
    (r"moe/wd$", ("model", None, None)),
] + _PARAM_RULES


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_param(path: str, ndim: int, rules=None) -> tuple:
    for pat, dims in (rules or _PARAM_RULES):
        if re.search(pat, path):
            if len(dims) < ndim:  # stacked-layer leading axes -> replicated
                dims = (None,) * (ndim - len(dims)) + tuple(dims)
            return dims
    return (None,) * ndim


def _axis_size(mesh: Mesh, phys) -> int:
    if phys is None:
        return 1
    if isinstance(phys, tuple):
        out = 1
        for a in phys:
            out *= mesh.shape[a]
        return out
    return mesh.shape[phys]


def sanitize(mesh: Mesh, dims, shape):
    """Drop shardings whose dimension size is not divisible (e.g. a
    49155-entry vocab over a 16-way model axis, or batch 1 over data)."""
    out = []
    for i, d in enumerate(dims):
        phys = _resolve(mesh, d)
        if phys is not None and shape[i] % _axis_size(mesh, phys) != 0:
            d = None
        out.append(d)
    return tuple(out)


def kernel_weight_dims(mesh: Mesh, shape) -> tuple:
    """Layout of a weight that the Pallas PLAM kernel reads: output
    columns over the model axis, replicated when they do not divide it.
    GSPMD cannot partition the kernel, so ``kernels.ops.plam_dense``
    shard_maps it over this layout, whatever the weight's Megatron role."""
    return sanitize(mesh, (None,) * (len(shape) - 1) + ("model",), shape)


def param_shardings(mesh: Mesh, params, rules=None, kernel_weights=()):
    """NamedSharding pytree for a parameter pytree.

    ``kernel_weights`` holds the '/'-joined paths of prequantized leaves
    served through the PLAM kernel; they take :func:`kernel_weight_dims`
    instead of their rule.
    """
    def one(path, leaf):
        path = _path_str(path)
        if path in kernel_weights:
            dims = kernel_weight_dims(mesh, leaf.shape)
        else:
            dims = sanitize(mesh, spec_for_param(path, leaf.ndim, rules), leaf.shape)
        return NamedSharding(mesh, pspec(mesh, dims))

    return jax.tree_util.tree_map_with_path(one, params)


def expert_parallel_rules():
    return _PARAM_RULES_EP


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def paged_pool_spec(mesh: Mesh, shape) -> NamedSharding:
    """Sharding for a paged KV pool [L, num_blocks, block_size, kv, hd].

    Prefer tensor-parallel over the kv-head axis — it matches the
    column-parallel wk/wv projections, so the per-token pool scatter in
    paged decode stays local to each shard.  When GQA leaves fewer kv
    heads than the model axis (kv % tp != 0) fall back to the ``seq_tp``
    rule: positions-within-block sharded over the model axis (the
    gather-attend path partitions cleanly under GSPMD).  If neither
    divides, replicate.  Block tables and the allocator never shard —
    they are host-side numpy, replicated into every jitted step.
    """
    dims = sanitize(mesh, (None, None, None, "model", None), shape)
    if dims[3] is None:
        dims = sanitize(mesh, (None, None, "seq_tp", None, None), shape)
    return NamedSharding(mesh, pspec(mesh, dims))
