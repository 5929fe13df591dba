"""Seeded random weights for a configuration file, made on the device.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed key,
layer + 1), leaf index)`` (layer 0 holds the embedding, the final norm
and the output head), so the reference can remake one layer at a time
without holding the whole model.  Values are uniform with the standard
deviation of a ``1/sqrt(fan_in)`` init, drawn in float32 and rounded to
the configuration's parameter dtype: uniform bits map to floats by exact
operations, so a layer remade alone is bit-identical to its slice of
the stacked tree.

Which leaves a layer has is its model family's (``families/``); the
primitives here are shared by every family.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any non-negative seed, also one past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _uniform(key, shape, fan_in: int, dtype):
    half_width = math.sqrt(3.0 / fan_in)
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    return (u * half_width).astype(dtype)


def layer_leaves(leaves, sizes: dict, key, layer: int, dtype):
    """One layer's leaves: ``{(group, name): array}``.

    ``leaves``: ``(group, name, fan_in key, shape keys)`` in the order
    that numbers their keys; a key names an entry of ``sizes``.
    """
    lk = jax.random.fold_in(key, layer + 1)
    out = {}
    for i, (group, name, fan, shape) in enumerate(leaves):
        out[(group, name)] = _uniform(
            jax.random.fold_in(lk, i), tuple(sizes[s] for s in shape), sizes[fan], dtype
        )
    return out


def top_weights(model: dict, key, dtype):
    """``embed`` [V, d] and ``unembed`` [d, V]."""
    d, v = model["hidden_size"], model["vocab_size"]
    tk = jax.random.fold_in(key, 0)
    embed = _uniform(jax.random.fold_in(tk, 0), (v, d), d, dtype)
    unembed = _uniform(jax.random.fold_in(tk, 1), (d, v), d, dtype)
    return embed, unembed


def decoder_params(model: dict, seed: int, dtype, layer_weights, placement=None):
    """A decoder's whole served tree, in one jitted call: ``embed``,
    ``layers`` (``ln1``, ``ln2`` and each group of ``layer_weights(model,
    key, layer, dtype)`` stacked on a leading layer axis), ``ln_f`` and
    ``unembed``; norm scales are ones.

    ``placement`` maps the tree's shapes to the shardings it is built in,
    so that each chip makes only its own shard; None builds it on the
    default device.  The bits do not depend on it: random bits are drawn
    partitionably (``jax_threefry_partitionable``)."""
    n_layers = model["num_hidden_layers"]
    d = model["hidden_size"]
    dtype = jnp.dtype(dtype)

    def build(key):
        layers = jax.vmap(lambda i: layer_weights(model, key, i, dtype))(
            jnp.arange(n_layers)
        )
        tree = {"ln1": {"scale": jnp.ones((n_layers, d), dtype)},
                "ln2": {"scale": jnp.ones((n_layers, d), dtype)}}
        for (group, name), leaf in layers.items():
            tree.setdefault(group, {})[name] = leaf
        embed, unembed = top_weights(model, key, dtype)
        return {
            "embed": embed,
            "layers": tree,
            "ln_f": {"scale": jnp.ones((d,), dtype)},
            "unembed": unembed,
        }

    key = seed_key(seed)
    if placement is None:
        return jax.jit(build)(key)
    return jax.jit(build, out_shardings=placement(jax.eval_shape(build, key)))(key)
