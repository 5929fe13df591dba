"""Plain float32 reference of a served decoder, and its control.

This file imports nothing of the program under test.  From a
configuration file and a seed it remakes the weights one layer at a
time (``weights.py`` and the model family's leaves), and runs the
causal LM over whole sequences (prompt plus served tokens) in float32,
with every matmul of the configuration's numerics:

* ``posit_quant``: both operands rounded to Posit<16,1> (round to
  nearest, ties to the even pattern, saturating at minpos and maxpos),
  then an exact product, float32 sum at ``highest`` precision;
* ``plam_sim``: both operands rounded to Posit<16,1>, each product the
  paper's logarithm-approximate one, summed in float32.  For posit
  values a PLAM product is Mitchell's product of their float32 words:
  the words' integer sum less the exponent bias, sign bits added mod 2.

The control computes the same model with every matmul operand rounded
to float8 e4m3 instead (scaled per row of activations and per column of
weights), the next precision below the configuration's 16-bit weights
and activations.

A model family (``families/<family>.py``) gives its layer's equations
and leaves; the numerics, attention, the embedding and output head and
the loop over sequences are here, shared by every family.

Each sequence runs alone (attention over one sequence's positions, so
the scores of a 2.5k-token sequence take under a gigabyte), padded at
its end to a multiple of ``ROW_BUCKET`` so that few programs are
compiled; padded rows come after every real one and are never
attended.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from weights import seed_key, top_weights

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
BIAS = 127 << 23
ROW_BUCKET = 256
FP8_MAX = 448.0


def posit16_round(x):
    """Round float32 ``x`` to the nearest Posit<16,1> value (as float32)."""
    x = x.astype(F32)
    ax = jnp.abs(x)
    _, e = jnp.frexp(ax)  # ax = m * 2**e, 0.5 <= m < 1
    s = e - 1  # 2**s <= ax < 2**(s + 1)
    k = s >> 1  # regime (es = 1), floor division
    regime_len = jnp.where(k >= 0, k + 2, 1 - k)
    nf = 14 - regime_len  # fraction bits left of the 15 after the sign
    t = jnp.ldexp(ax, nf - s)  # in [2**nf, 2**(nf + 1))
    r = jnp.round(t)  # ties to even: the last fraction bit
    # with no fraction bit the pattern ends in the exponent bit, s & 1
    r = jnp.where((nf == 0) & (t == 1.5) & ((s & 1) == 0), 1.0, r)
    q = jnp.ldexp(r, s - nf)
    # scales past +-26 lose the exponent bit: 2**26 | 2**28 and
    # 2**-28 | 2**-26, split where the 17-bit pattern would put the tie
    hi = jnp.where(ax > 2.0**27, 2.0**28, 2.0**26)
    lo = jnp.where(ax >= 2.0**-27, 2.0**-26, 2.0**-28)
    q = jnp.where(s >= 26, hi, jnp.where(s <= -27, lo, q))
    # judged by the bits: a backend may flush float32 subnormals to zero
    mag = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0x7FFFFFFF)
    q = jnp.where(mag < jnp.uint32(0x00800000), 2.0**-28, q)
    q = jnp.where(jnp.isfinite(x), jnp.copysign(q, x), jnp.nan)
    return jnp.where(mag == 0, 0.0, q)


def fp8_round(x, axis: int):
    """Round to float8 e4m3 after scaling each slice along ``axis`` to
    the format's largest value."""
    x = x.astype(F32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def plam_dot(x, w, chunk: int = 16):
    """sum_k PLAM(x[m, k], w[k, n]) in float32; operands hold posit values."""
    m, k = x.shape
    n = w.shape[1]
    pad = (-k) % chunk
    xw = jax.lax.bitcast_convert_type(x, jnp.int32) - jnp.int32(BIAS)
    ww = jax.lax.bitcast_convert_type(w, jnp.int32)
    xnz, wnz = x != 0, w != 0
    if pad:
        xw, xnz = jnp.pad(xw, ((0, 0), (0, pad))), jnp.pad(xnz, ((0, 0), (0, pad)))
        ww, wnz = jnp.pad(ww, ((0, pad), (0, 0))), jnp.pad(wnz, ((0, pad), (0, 0)))
    nk = (k + pad) // chunk
    xs = (xw.reshape(m, nk, chunk).transpose(1, 0, 2),
          xnz.reshape(m, nk, chunk).transpose(1, 0, 2))
    ws = (ww.reshape(nk, chunk, n), wnz.reshape(nk, chunk, n))

    def body(acc, ops):
        (a, anz), (b, bnz) = ops
        word = a[:, :, None] + b[None, :, :]  # sign bits add mod 2
        prod = jax.lax.bitcast_convert_type(word, F32)
        prod = jnp.where(anz[:, :, None] & bnz[None, :, :], prod, 0.0)
        return acc + jnp.sum(prod, axis=1), None

    acc, _ = jax.lax.scan(body, jnp.zeros((m, n), F32), (xs, ws))
    return acc


def exact_dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


def matmul(x, w, mode: str, control: bool):
    """x [M, K] @ w [K, N] under the configuration's numerics ``mode``."""
    if control:
        xq, wq = fp8_round(x, axis=1), fp8_round(w, axis=0)
    else:
        xq, wq = posit16_round(x), posit16_round(w)
    if mode == "plam_sim":
        return plam_dot(xq, wq)
    if mode == "posit_quant":
        return exact_dot(xq, wq)
    raise ValueError(f"reference has no numerics mode {mode!r}")


def rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, pos, theta):
    """x [T, H, hd]; rotates the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(xn, w, m: dict, mm):
    """Causal grouped-query attention with RoPE over one sequence's
    normed rows xn [T, d], through ``w["wq"]``, ``wk``, ``wv`` and the
    output projection ``wo``; ``mm`` is the matmul under the numerics."""
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    theta = m["rope_theta"]
    t = xn.shape[0]
    pos = jnp.arange(t)
    q = rope(mm(xn, w["wq"]).reshape(t, h, hd), pos, theta)
    k = rope(mm(xn, w["wk"]).reshape(t, kv, hd), pos, theta)
    v = mm(xn, w["wv"]).reshape(t, kv, hd)
    qg = q.reshape(t, kv, h // kv, hd)
    s = jnp.einsum("qkgh,skh->kgqs", qg, k, precision=HIGHEST) / math.sqrt(hd)
    s = jnp.where((pos[None, :] <= pos[:, None])[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skh->qkgh", p, v, precision=HIGHEST).reshape(t, h * hd)
    return mm(o, w["wo"])


@partial(jax.jit, static_argnames=("model", "mode", "control"))
def head_forward(x, unembed, *, model, mode, control):
    xn = rmsnorm(x, dict(model)["rms_norm_eps"])
    return matmul(xn, unembed, mode, control)


@partial(jax.jit, static_argnames=("model", "dtype", "leaves"))
def _layer_weights(key, layer, *, model, dtype, leaves):
    w = leaves(dict(model), key, layer, jnp.dtype(dtype))
    return {name: leaf.astype(F32) for (_, name), leaf in w.items()}


@partial(jax.jit, static_argnames=("model", "dtype"))
def _top_weights(key, *, model, dtype):
    embed, unembed = top_weights(dict(model), key, jnp.dtype(dtype))
    return embed.astype(F32), unembed.astype(F32)


def _bucket(n: int) -> int:
    return max(ROW_BUCKET, -(-n // ROW_BUCKET) * ROW_BUCKET)


def served_logits(cfg: dict, seed: int, seqs, controls, *, layer_forward, layer_weights):
    """Logits at every position that predicts a served token.

    ``seqs``: ``[(prompt, served)]`` token lists.  Returns, for each
    entry of ``controls``, a float32 array [sum of len(served), V] in
    the order of ``seqs``: row ``j`` of a sequence is the distribution
    of its served token ``j``, given the prompt and served tokens
    before it.  The family gives ``layer_forward(x, w, *, model, mode,
    control)``, one jitted layer over one sequence's rows, and
    ``layer_weights(model, key, layer, dtype)``, that layer's leaves.
    """
    model = cfg["model"]
    frozen = tuple(sorted(model.items()))
    mode = cfg["numerics"]["mode"]
    dtype = cfg["dtypes"]["param"]
    key = seed_key(seed)
    tokens, rows = [], []
    for prompt, served in seqs:
        ctx = list(prompt) + list(served[:-1])
        tokens.append(np.array(ctx + [0] * (_bucket(len(ctx)) - len(ctx)), np.int32))
        rows.append(np.arange(len(prompt) - 1, len(ctx)))

    embed, unembed = _top_weights(key, model=frozen, dtype=dtype)
    xs = {(c, i): embed[jnp.asarray(tok)] for c in controls for i, tok in enumerate(tokens)}
    del embed
    for layer in range(model["num_hidden_layers"]):
        w = _layer_weights(key, jnp.int32(layer), model=frozen, dtype=dtype,
                           leaves=layer_weights)
        for c, i in xs:
            xs[c, i] = layer_forward(xs[c, i], w, model=frozen, mode=mode, control=c)
        del w
    out = []
    for c in controls:
        last = jnp.concatenate([xs[c, i][r] for i, r in enumerate(rows)])
        n = last.shape[0]
        last = jnp.pad(last, ((0, _bucket(n) - n), (0, 0)))
        out.append(head_forward(last, unembed, model=frozen, mode=mode, control=c)[:n])
    return out


def served_token_gaps(logits, served):
    """Per served token: how far its logit lies below the row's best."""
    ref = jnp.asarray(logits)
    tok = jnp.asarray(np.asarray(served, np.int32))
    best = jnp.max(ref, axis=-1)
    return np.asarray(best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0])
