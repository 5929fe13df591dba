"""Pallas TPU kernel for the PLAM matrix multiplier.

TPU-native adaptation of the paper's Fig. 4 datapath (see DESIGN.md §3):

* A posit's (regime‖exponent‖fraction) is a fixed-point log2 of its
  magnitude.  We decode each operand tile once into signed "log words"
  W = sign << 31 | (scale + bias) << 23 | mantissa23 — the log fixed
  point *in the position of the IEEE-754 exponent/mantissa fields*, its
  sign in the f32 sign bit.  A's words take bias 0, B's bias 127.
* A PLAM product is then ONE int32 add (Wa + Wb) followed by a BITCAST
  to f32 — Mitchell's antilogarithm is exactly the float bit layout.
  The sign bits add mod 2^32, which is their XOR; the magnitudes' sum
  lies in [0, 2^31) for every spec the kernel accepts, so it never
  carries into the sign.  No multiplier is used anywhere, mirroring the
  paper's hardware claim.
* Zero and NaR operands contribute exactly +0.0: the product bits are
  ANDed with both operands' all-ones/zero validity masks.
* Products accumulate in linear f32 (EMAC / Johnson-style), k strictly
  ascending, the order of ``ref.plam_matmul_seqref``.

The kernel runs on the VPU (element-wise integer adds), not the MXU:
it is the *simulation engine* for posit-hardware studies.  Per product
vreg its inner loop issues four VPU ops (add, two ANDs, the f32 add);
B's rows reach it through sublane-replicating loads and each A column
through one lane broadcast shared by the whole N tile.  It is VPU-bound,
not HBM- or MXU-bound: decoding B on every grid step takes about 60% of
its VPU slots at decode M, and serving Yi-6B at 8 rows on a TPU v5e it
measured 12% of its roofline (the larger of 2MKN at the bf16 peak and
its posit16 bytes at the HBM peak; PERF.md, section 5).

Grid: (M/bm, N/bn, K/bk), K innermost for in-place accumulation; the
default tiles are ``tile_sizes``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.numerics import PositSpec
from repro.numerics.posit import I32, decode_fields

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128
_WIDE_BN = (512, 384, 256, 128)
# An A word no real posit takes (its scale would be 127; the kernel
# accepts |scale| <= 63): marks zero/NaR in A's words themselves, so the
# loop rebuilds A's mask from the word it already broadcast.
_INVALID = 127 << 23


def tile_sizes(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Default (bm, bn, bk) of an [m, k] x [k, n] product.

    Where the rows fit one vreg row (m <= 8, the decode batch), the N
    tile is the widest of ``_WIDE_BN`` that divides n, so that one lane
    broadcast of an A column serves up to four output vregs and B is
    never padded.  Otherwise bn is 128 and a ragged n is padded to it.
    """
    bn = min(DEFAULT_BN, n)
    if m <= 8 and n % 128 == 0:
        bn = next(t for t in _WIDE_BN if n % t == 0)
    return min(DEFAULT_BM, m), bn, min(DEFAULT_BK, k)


def _log_words(bits, spec: PositSpec, bias: int):
    """Posit patterns -> (signed log words, validity masks), both int32.

    A word is ``sign << 31 | (scale + bias) << 23 | mantissa23`` mod
    2^32; the mask is -1 for a real number and 0 for zero or NaR.
    """
    fb = spec.fbmax
    sign, scale, frac, is_zero, is_nar = decode_fields(bits, spec)
    mant = frac << I32(23 - fb) if fb <= 23 else frac >> I32(fb - 23)
    words = (sign << I32(31)) + ((scale + I32(bias)) << I32(23)) + mant
    valid = jnp.where(is_zero | is_nar, I32(0), I32(-1))
    return words, valid


def _plam_matmul_kernel(a_ref, b_ref, o_ref, wb_ref, vb_ref, *, spec: PositSpec):
    """One (bm, bn) output tile over one bk slice of K.

    a_ref (bm, bk) and b_ref (bk, bn) hold int32 patterns; wb_ref and
    vb_ref are (bk, bn) VMEM scratch for B's words and masks.
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    wa, va = _log_words(a_ref[...], spec, 0)
    wa = jnp.where(va == 0, I32(_INVALID), wa)
    wb_ref[...], vb_ref[...] = _log_words(b_ref[...], spec, 127)
    bm, bk = wa.shape
    bn = wb_ref.shape[1]
    w = 128 if bn % 128 == 0 else bn  # one vreg column per slab
    slabs = [pl.ds(j * w, w) for j in range(bn // w)]
    acc = [o_ref[:, s] for s in slabs]
    # Static unroll over k: Mosaic lowers a static [bm, 1] lane slice,
    # but not a column read at a loop-carried index.
    for k in range(bk):
        wak = jnp.broadcast_to(wa[:, k:k + 1], (bm, w))
        vak = jnp.where(wak == I32(_INVALID), I32(0), I32(-1))
        for j, s in enumerate(slabs):
            # a one-row load and its broadcast lower to one load that
            # replicates the row over sublanes: no VPU op
            wbk = jnp.broadcast_to(wb_ref[pl.ds(k, 1), s], (bm, w))
            vbk = jnp.broadcast_to(vb_ref[pl.ds(k, 1), s], (bm, w))
            bits = (wak + wbk) & (vak & vbk)
            acc[j] = acc[j] + jax.lax.bitcast_convert_type(bits, jnp.float32)
    for s, a in zip(slabs, acc):
        o_ref[:, s] = a


@functools.partial(
    jax.jit, static_argnames=("spec", "bm", "bn", "bk", "interpret")
)
def plam_matmul(
    a_bits,
    b_bits,
    spec: PositSpec = PositSpec(16, 1),
    *,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool = False,
):
    """C = A ⊗_PLAM B with linear-f32 accumulation.

    a_bits: int32 [M, K] posit patterns;  b_bits: int32 [K, N].
    Tiles not given default to ``tile_sizes``.  Shapes are padded to
    block multiples (pattern 0 == posit zero, whose products are exactly
    zero, so padding is value-preserving).
    """
    assert spec.max_scale * 2 + 127 <= 254, "spec's product scale must fit f32"
    m, k = a_bits.shape
    k2, n = b_bits.shape
    assert k == k2, (a_bits.shape, b_bits.shape)
    dm, dn, dk = tile_sizes(m, k, n)
    bm_, bn_, bk_ = min(bm or dm, m), min(bn or dn, n), min(bk or dk, k)

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
        functools.partial(_plam_matmul_kernel, spec=spec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk_, bn_), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]
