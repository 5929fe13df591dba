"""Prequantized posit weight storage: encode once, serve forever.

``quantize_params`` walks a model's parameter pytree, maps each weight
to its matmul site role, and — where the numerics policy resolves that
site to a posit mode (``posit_quant`` / ``plam_sim``) — replaces the
f32 weight with its Posit<n,es> bit patterns, packed to int16 for
n <= 16.  ``core.modes.nmatmul`` recognizes integer-dtype weights and
consumes them without ever re-encoding: the ``plam_sim`` path feeds
``kernels.ops.plam_dense`` (the deployment layout for posit inference),
exact-posit paths decode to the grid values the per-matmul codec would
have produced, bit-identically.

The pass is inference-only (patterns carry no gradients); training
keeps linear weights and the existing ``prequantized_weights`` flag
semantics.  Quantized pytrees round-trip through
``train.checkpoint.save/restore`` unchanged — the npz stores the int16
leaves and the site metadata rides in the manifest's ``extra`` dict.
"""

from __future__ import annotations

import re
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.numerics import encode, pack16

from .modes import NumericsConfig
from .policy import layer_segments, site_for

# Parameter path -> site role.  Paths are '/'-joined pytree key paths
# (e.g. "layers/attn/wq", "enc_layers/mlp/wu", "shared/out_proj").
# Anything unmatched (embeddings, norms, convs, biases, SSM scalars) is
# left untouched.
_PATH_ROLES: Tuple[Tuple[str, str], ...] = (
    (r"(^|/)xattn/w[qkv]$", "attn.cross.qkv"),
    (r"(^|/)xattn/wo$", "attn.cross.out"),
    (r"(^|/)attn/w[qkv]$", "attn.qkv"),
    (r"(^|/)attn/wo$", "attn.out"),
    (r"(^|/)moe/router$", "moe.router"),
    (r"(^|/)moe/wu$", "moe.expert.up"),
    (r"(^|/)moe/wg$", "moe.expert.gate"),
    (r"(^|/)moe/wd$", "moe.expert.down"),
    (r"(^|/)moe/shared/wu$", "moe.shared.up"),
    (r"(^|/)moe/shared/wg$", "moe.shared.gate"),
    (r"(^|/)moe/shared/wd$", "moe.shared.down"),
    (r"(^|/)mlp/wu$", "mlp.up"),
    (r"(^|/)mlp/wg$", "mlp.gate"),
    (r"(^|/)mlp/wd$", "mlp.down"),
    (r"(^|/)mamba/in_proj$", "ssm.proj.in"),
    (r"(^|/)mamba/out_proj$", "ssm.proj.out"),
    (r"^shared/out_proj$", "hybrid.proj"),
    (r"^frontend_proj$", "frontend"),
    (r"^unembed$", "lm_head"),
)

_POSIT_MODES = ("posit_quant", "plam_sim")


def param_role(path: str) -> Optional[str]:
    """Site role for a '/'-joined parameter path, or None (skip)."""
    for pat, role in _PATH_ROLES:
        if re.search(pat, path):
            return role
    return None


def _path_str(key_path) -> str:
    parts = []
    for p in key_path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def _site_cfg_if_uniform(cfg, role: str, layered: bool) -> Optional[NumericsConfig]:
    """Resolve `role` under cfg.numerics, requiring layer-uniformity.

    Stacked per-layer weights share one array (and one dtype), so a
    site can only be prequantized when every layer resolves to the SAME
    posit config; mixed-over-layers sites stay linear f32 and keep
    quantizing per matmul.
    """
    if not layered:
        segs = [(0, 1, None)]
        n_layers = None
    else:
        n_layers = cfg.n_layers
        segs = layer_segments(cfg.numerics, n_layers)
    resolved = []
    for start, _, _ in segs:
        layer = start if layered else None
        resolved.append(site_for(cfg.numerics, role, layer, n_layers))
    first = resolved[0]
    if any(r != first for r in resolved[1:]):
        return None
    return first


@partial(jax.jit, static_argnames=("spec", "pack"))
def _encode_leaf(leaf, spec, pack: bool):
    """One fused elementwise pass from the stored dtype to patterns, so no
    f32 or int32 copy of a stacked weight is ever materialized."""
    bits = encode(leaf.astype(jnp.float32), spec)
    return pack16(bits) if pack else bits


def quantize_params(cfg, params, *, pack: bool = True):
    """Encode policy-selected weights to posit patterns once.

    Returns ``(params_q, meta)`` where ``meta`` maps parameter path ->
    ``{"role", "mode", "n", "es"}`` for every quantized leaf (the
    manifest-ready record).  Only sites whose resolved mode is a posit
    mode are touched; tied embeddings are never quantized (the lm_head
    then serves from the shared f32 embedding, as before).
    """
    meta = {}

    def one(key_path, leaf):
        path = _path_str(key_path)
        role = param_role(path)
        if role is None:
            return leaf
        # enc/dec stacks resolve layer-free in the model (layer-range
        # rules target decoder-only LM depth), so only the main LM
        # stack is layer-sensitive here
        layered = path.startswith("layers/")
        site_cfg = _site_cfg_if_uniform(cfg, role, layered)
        if site_cfg is None or site_cfg.mode not in _POSIT_MODES:
            return leaf
        spec = site_cfg.spec
        bits = _encode_leaf(leaf, spec, pack and spec.n <= 16)
        meta[path] = {
            "role": role,
            "mode": site_cfg.mode,
            "n": spec.n,
            "es": spec.es,
        }
        return bits

    params_q = jax.tree_util.tree_map_with_path(one, params)
    return params_q, meta


def dequantize_params(params_q, meta, dtype=jnp.float32):
    """Inverse of :func:`quantize_params` (to the posit-grid values);
    everything needed to decode is in ``meta``."""
    from repro.numerics import decode, unpack16
    from repro.numerics.posit import PositSpec

    def one(key_path, leaf):
        path = _path_str(key_path)
        info = meta.get(path)
        if info is None:
            return leaf
        bits = unpack16(leaf) if leaf.dtype == jnp.int16 else leaf
        return decode(bits, PositSpec(info["n"], info["es"])).astype(dtype)

    return jax.tree_util.tree_map_with_path(one, params_q)
