"""Pallas TPU kernel for the PLAM matrix multiplier.

TPU-native adaptation of the paper's Fig. 4 datapath (see DESIGN.md §3):

* A posit's (regime‖exponent‖fraction) is a fixed-point log2 of its
  magnitude.  We decode each operand tile once into "f32-aligned log
  words"  L = (scale + 127) << 23 | mantissa23  — i.e. the log-fixed
  point *in the position of the IEEE-754 exponent/mantissa fields*.
* A PLAM product is then ONE integer add (La_pre + Lb, with the bias
  pre-subtracted from A's words) followed by a BITCAST to f32 —
  Mitchell's antilogarithm is exactly the float bit layout.  No
  multiplier is used anywhere, mirroring the paper's hardware claim.
* Products accumulate in linear f32 (EMAC / Johnson-style).

The kernel runs on the VPU (element-wise integer adds), not the MXU:
it is the *simulation engine* for posit-hardware studies, and its
roofline is the VPU add throughput, which this layout saturates.

Grid: (M/bm, N/bn, K/bk), K innermost for in-place accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.numerics import PositSpec
from repro.numerics.posit import I32, U32, decode_fields

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128
_BIAS = 127 << 23


def _log_words(bits, spec: PositSpec):
    """Posit patterns -> (sign<<31 words, f32-aligned log magnitudes, valid).

    zero/NaR inputs are marked invalid; their products contribute 0.
    """
    fb = spec.fbmax
    sign, scale, frac, is_zero, is_nar = decode_fields(bits, spec)
    if fb <= 23:
        mant = frac.astype(U32) << U32(23 - fb)
    else:
        mant = frac.astype(U32) >> U32(fb - 23)
    lmag = ((scale + I32(127)).astype(U32) << U32(23)) | mant
    s31 = sign.astype(U32) << U32(31)
    valid = ~(is_zero | is_nar)
    return s31, lmag.astype(I32), valid


def _plam_matmul_kernel(a_ref, b_ref, o_ref, *, spec: PositSpec, bk: int):
    """One (bm, bn) output tile; a_ref (bm, bk) int32, b_ref (bk, bn) int32."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Element-wise decode of both tiles: O(bm*bk + bk*bn) integer ops.
    sa, la, va = _log_words(a_ref[...], spec)
    sb, lb, vb = _log_words(b_ref[...], spec)
    la_pre = la - I32(_BIAS)  # pre-subtract the bias once per A element

    acc = o_ref[...]
    # Static unroll over k: Mosaic lowers a static [bm, 1] lane slice,
    # but not a column read at a loop-carried index.  k ascends, so the
    # f32 accumulation order is that of ``ref.plam_matmul_seqref``.
    for k in range(bk):
        # [bm,1] x [1,bn] broadcasts: per pair ONE add + bitcast (+mask).
        lsum = la_pre[:, k:k + 1] + lb[k:k + 1, :]
        sgn = sa[:, k:k + 1] ^ sb[k:k + 1, :]
        bits = sgn | lsum.astype(U32)
        val = jax.lax.bitcast_convert_type(bits, jnp.float32)
        ok = va[:, k:k + 1] & vb[k:k + 1, :]
        acc = acc + jnp.where(ok, val, jnp.float32(0))
    o_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("spec", "bm", "bn", "bk", "interpret")
)
def plam_matmul(
    a_bits,
    b_bits,
    spec: PositSpec = PositSpec(16, 1),
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
):
    """C = A ⊗_PLAM B with linear-f32 accumulation.

    a_bits: int32 [M, K] posit patterns;  b_bits: int32 [K, N].
    Shapes are padded to block multiples (pattern 0 == posit zero, whose
    products are exactly zero, so padding is value-preserving).
    """
    assert spec.max_scale * 2 + 127 <= 254, "spec's product scale must fit f32"
    m, k = a_bits.shape
    k2, n = b_bits.shape
    assert k == k2, (a_bits.shape, b_bits.shape)
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)

    def pad(x, mult0, mult1):
        p0 = (-x.shape[0]) % mult0
        p1 = (-x.shape[1]) % mult1
        if p0 or p1:
            x = jnp.pad(x, ((0, p0), (0, p1)))
        return x

    a_p = pad(a_bits, bm_, bk_)
    b_p = pad(b_bits, bk_, bn_)
    mp, kp = a_p.shape
    _, np_ = b_p.shape

    grid = (mp // bm_, np_ // bn_, kp // bk_)
    out = pl.pallas_call(
        functools.partial(_plam_matmul_kernel, spec=spec, bk=bk_),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]
