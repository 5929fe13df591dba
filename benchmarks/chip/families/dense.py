"""Dense decoder family: Yi- and Llama-shaped layers.

Each layer is pre-norm causal grouped-query attention with RoPE and a
SwiGLU MLP, ``x + attn(norm(x))`` then ``x + down(silu(gate) * up)``.
The configuration's ``model`` section uses Hugging Face key names.

A family module gives everything the harness derives from a model's
layer: ``model_config`` (the program's ModelConfig), ``make_params``
(the tree ``build_engine(params=...)`` takes), ``served_logits`` (the
plain reference and its float8 control) and ``layer_sites`` (the
matmuls one token passes through in one layer).
"""
from __future__ import annotations

from functools import partial

import jax

import reference
import weights

# (group, name, fan_in key, shape keys), in the order that numbers their keys
LAYER_LEAVES = (
    ("attn", "wq", "d", ("d", "q")),
    ("attn", "wk", "d", ("d", "kv")),
    ("attn", "wv", "d", ("d", "kv")),
    ("attn", "wo", "q", ("q", "d")),
    ("mlp", "wg", "d", ("d", "ff")),
    ("mlp", "wu", "d", ("d", "ff")),
    ("mlp", "wd", "ff", ("ff", "d")),
)


def model_config(conf: dict):
    """The program's ModelConfig, built from the file's own sizes."""
    from repro.configs.base import ModelConfig
    from repro.core.policy import parse_cfg_spec, parse_policy

    m = conf["model"]
    policy = conf["numerics"]["policy"]
    numerics = parse_policy(policy) if "=" in policy else parse_cfg_spec(policy)
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv=m["num_key_value_heads"],
        head_dim=m["head_dim"], d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        act=m["hidden_act"], glu=True, rope_theta=m["rope_theta"],
        tie_embeddings=m["tie_word_embeddings"], numerics=numerics,
        param_dtype=conf["dtypes"]["param"], act_dtype=conf["dtypes"]["act"],
    )


def sizes(model: dict) -> dict:
    return {
        "d": model["hidden_size"],
        "q": model["num_attention_heads"] * model["head_dim"],
        "kv": model["num_key_value_heads"] * model["head_dim"],
        "ff": model["intermediate_size"],
    }


def layer_weights(model: dict, key, layer: int, dtype):
    """One layer's leaves (no norms): ``{(group, name): array}``."""
    return weights.layer_leaves(LAYER_LEAVES, sizes(model), key, layer, dtype)


def make_params(model: dict, seed: int, dtype, placement=None):
    return weights.decoder_params(model, seed, dtype, layer_weights, placement)


def layer_sites(model: dict):
    """(K, N) of every matmul one token passes through in one layer."""
    sz = sizes(model)
    return [(sz[shape[0]], sz[shape[1]]) for _, _, _, shape in LAYER_LEAVES]


@partial(jax.jit, static_argnames=("model", "mode", "control"))
def layer_forward(x, w, *, model, mode, control):
    """One decoder layer over one sequence's rows x [T, d] (norm scales
    are ones)."""
    m = dict(model)
    eps = m["rms_norm_eps"]
    mm = partial(reference.matmul, mode=mode, control=control)
    x = x + reference.attention(reference.rmsnorm(x, eps), w, m, mm)
    xn = reference.rmsnorm(x, eps)
    up = jax.nn.silu(mm(xn, w["wg"])) * mm(xn, w["wu"])
    return x + mm(up, w["wd"])


def served_logits(conf: dict, seed: int, seqs, controls=(False,)):
    """``reference.served_logits`` through this family's layers."""
    return reference.served_logits(conf, seed, seqs, controls,
                                   layer_forward=layer_forward, layer_weights=layer_weights)
