"""Operations, bytes and least times of the served work, from shapes.

The yardstick's own arithmetic: what a call has to compute and move,
whatever implements it.  ``model`` is a configuration file's ``model``
section (Hugging Face key names); ``sites`` is the (K, N) of the
matmuls one token passes through in one layer, as the configuration's
model family gives them (``layer_sites(model)`` in ``families/``).
Shares of a peak count the cell's ``chips``: a call's whole work, split
evenly over them, against each chip's peak.
"""
from __future__ import annotations

import json
import math
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
BF16 = 2  # bytes of a served weight, activation or cache entry
POSIT16 = 2  # bytes of a posit<16,1> operand
F32 = 4


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def layer_macs(sites, model: dict) -> int:
    """Multiply-adds of one token through the layers' matmuls."""
    return model["num_hidden_layers"] * sum(k * n for k, n in sites)


def head_macs(model: dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def attention_flops(model: dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` cached positions, all layers."""
    h, hd = model["num_attention_heads"], model["head_dim"]
    return 4 * model["num_hidden_layers"] * keys * h * hd


def token_flops(sites, model: dict, keys: int, logits: bool) -> int:
    """Model FLOPs of one token: 2 per matmul MAC plus attention; the
    output head only where the token's logits are used."""
    macs = layer_macs(sites, model) + (head_macs(model) if logits else 0)
    return 2 * macs + attention_flops(model, keys)


def matmul_least_s(m: int, k: int, n: int, pk: dict) -> float:
    """Least time of an [m, k] x [k, n] matmul with posit16 operands and
    a float32 result: the larger of its MACs at the bf16 peak and its
    bytes at the HBM peak."""
    flops = 2 * m * k * n
    nbytes = k * n * POSIT16 + m * k * POSIT16 + m * n * F32
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def program_matmul_least_s(sites, model: dict, rows: int, head_rows: int,
                           pk: dict) -> float:
    """Least time of every matmul of one model call: ``rows`` through
    each layer and ``head_rows`` through the output head."""
    per_layer = sum(matmul_least_s(rows, k, n, pk) for k, n in sites)
    head = matmul_least_s(head_rows, model["hidden_size"], model["vocab_size"], pk)
    return model["num_hidden_layers"] * per_layer + head


def decode_attention_bytes(model: dict, keys: int, block: int) -> int:
    """Bytes one slot's decode attention must move, all layers: the K
    and V blocks that hold ``keys`` positions, the query and the output."""
    kv, h, hd = model["num_key_value_heads"], model["num_attention_heads"], model["head_dim"]
    cached = math.ceil(keys / block) * block * kv * hd * 2 * BF16
    return model["num_hidden_layers"] * (cached + 2 * h * hd * BF16)


def peak_share(work, seconds: float, peak_per_s: float, chips: int) -> float:
    """Per cent of ``chips`` chips' peak that ``work`` over ``seconds`` reaches."""
    return 100.0 * work / seconds / (chips * peak_per_s)


def roofline_share(least, kernel_s: float, chips: int, per_s: float = 1.0) -> float:
    """Per cent of a kernel's device time per chip (``kernel_s``, averaged
    over the chips) that its work would take at the roofline, split
    evenly over ``chips``: ``least`` seconds, or ``least`` bytes at
    ``per_s``."""
    return 100.0 * least / per_s / chips / kernel_s
