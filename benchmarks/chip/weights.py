"""Seeded random weights for a configuration file, made on the device.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed key,
layer + 1), leaf index)`` (layer 0 holds the embedding, the final norm
and the output head), so the reference can remake one layer at a time
without holding the whole model.  Values are uniform with the standard
deviation of a ``1/sqrt(fan_in)`` init, drawn in float32 and rounded to
the configuration's parameter dtype: uniform bits map to floats by exact
operations, so a layer remade alone is bit-identical to its slice of
the stacked tree.

The tree has the layout ``build_engine(params=...)`` takes for a dense
decoder: ``embed``, ``layers/{ln1,attn/{wq,wk,wv,wo},ln2,mlp/{wg,wu,wd}}``
stacked on a leading layer axis, ``ln_f`` and ``unembed``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# (group, name, fan_in key, shape keys); a shape key names a model size
LAYER_LEAVES = (
    ("attn", "wq", "d", ("d", "q")),
    ("attn", "wk", "d", ("d", "kv")),
    ("attn", "wv", "d", ("d", "kv")),
    ("attn", "wo", "q", ("q", "d")),
    ("mlp", "wg", "d", ("d", "ff")),
    ("mlp", "wu", "d", ("d", "ff")),
    ("mlp", "wd", "ff", ("ff", "d")),
)


def seed_key(seed: int):
    """A key for any non-negative seed, also one past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _sizes(m: dict) -> dict:
    return {
        "d": m["hidden_size"],
        "q": m["num_attention_heads"] * m["head_dim"],
        "kv": m["num_key_value_heads"] * m["head_dim"],
        "ff": m["intermediate_size"],
        "v": m["vocab_size"],
    }


def _uniform(key, shape, fan_in: int, dtype):
    half_width = math.sqrt(3.0 / fan_in)
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    return (u * half_width).astype(dtype)


def layer_weights(model: dict, key, layer: int, dtype):
    """One layer's leaves (no norms): ``{(group, name): array}``."""
    sz = _sizes(model)
    lk = jax.random.fold_in(key, layer + 1)
    out = {}
    for i, (group, name, fan, shape) in enumerate(LAYER_LEAVES):
        out[(group, name)] = _uniform(
            jax.random.fold_in(lk, i), tuple(sz[s] for s in shape), sz[fan], dtype
        )
    return out


def top_weights(model: dict, key, dtype):
    """``embed`` [V, d] and ``unembed`` [d, V]."""
    sz = _sizes(model)
    tk = jax.random.fold_in(key, 0)
    embed = _uniform(jax.random.fold_in(tk, 0), (sz["v"], sz["d"]), sz["d"], dtype)
    unembed = _uniform(jax.random.fold_in(tk, 1), (sz["d"], sz["v"]), sz["d"], dtype)
    return embed, unembed


def make_params(model: dict, seed: int, dtype):
    """The whole served tree, in one jitted call on the default device."""
    n_layers = model["num_hidden_layers"]
    d = model["hidden_size"]
    dtype = jnp.dtype(dtype)

    def build(key):
        layers = jax.vmap(lambda i: layer_weights(model, key, i, dtype))(
            jnp.arange(n_layers)
        )
        tree = {"ln1": {"scale": jnp.ones((n_layers, d), dtype)},
                "ln2": {"scale": jnp.ones((n_layers, d), dtype)},
                "attn": {}, "mlp": {}}
        for (group, name), leaf in layers.items():
            tree[group][name] = leaf
        embed, unembed = top_weights(model, key, dtype)
        return {
            "embed": embed,
            "layers": tree,
            "ln_f": {"scale": jnp.ones((d,), dtype)},
            "unembed": unembed,
        }

    return jax.jit(build)(seed_key(seed))
