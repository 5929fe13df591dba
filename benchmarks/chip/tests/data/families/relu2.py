"""A second model family, kept as test data: Minitron's layer.

Minitron-8B (arXiv:2407.14679, pruned from Nemotron-4) has the dense
family's attention and an ungated squared-ReLU MLP,
``x + down(relu(up(norm(x)))**2)``.  It enters the harness as this one
file: the dense family's pieces it shares are loaded by name, the
numerics and attention come from ``reference.py`` and ``weights.py``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

import reference
import weights
from cell import load_family

DENSE = load_family("dense")

LAYER_LEAVES = (
    ("attn", "wq", "d", ("d", "q")),
    ("attn", "wk", "d", ("d", "kv")),
    ("attn", "wv", "d", ("d", "kv")),
    ("attn", "wo", "q", ("q", "d")),
    ("mlp", "wu", "d", ("d", "ff")),
    ("mlp", "wd", "ff", ("ff", "d")),
)


def model_config(conf: dict):
    return dataclasses.replace(DENSE.model_config(conf), glu=False)


def layer_weights(model: dict, key, layer: int, dtype):
    return weights.layer_leaves(LAYER_LEAVES, DENSE.sizes(model), key, layer, dtype)


def make_params(model: dict, seed: int, dtype, placement=None):
    return weights.decoder_params(model, seed, dtype, layer_weights, placement)


def layer_sites(model: dict):
    sz = DENSE.sizes(model)
    return [(sz[shape[0]], sz[shape[1]]) for _, _, _, shape in LAYER_LEAVES]


@partial(jax.jit, static_argnames=("model", "mode", "control"))
def layer_forward(x, w, *, model, mode, control):
    m = dict(model)
    eps = m["rms_norm_eps"]
    mm = partial(reference.matmul, mode=mode, control=control)
    x = x + reference.attention(reference.rmsnorm(x, eps), w, m, mm)
    up = jnp.square(jax.nn.relu(mm(reference.rmsnorm(x, eps), w["wu"])))
    return x + mm(up, w["wd"])


def served_logits(conf: dict, seed: int, seqs, controls=(False,)):
    return reference.served_logits(conf, seed, seqs, controls,
                                   layer_forward=layer_forward, layer_weights=layer_weights)
