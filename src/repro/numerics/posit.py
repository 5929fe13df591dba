"""Vectorized Posit<n,es> codec in pure JAX (int32/uint32 bit kernels).

Patterns are carried in ``int32`` arrays (one posit per lane; the unused
high bits of patterns with n < 32 are zero).  All field arithmetic uses
``uint32`` internally so shifts are logical.

Bit-exactness guarantees (validated in tests against ``golden.py``):

* ``decode``/``encode`` are bit-exact for every supported spec with
  n <= 24 (the f32 mantissa holds the full posit fraction).  For
  n in (24, 32] decode-to-f32 performs one extra RNE rounding step.
* ``encode_fields`` implements SoftPosit-style pattern rounding
  (round-to-nearest-even on the underlying bit pattern, saturating at
  +-maxpos, never rounding a non-zero value to zero/NaR).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

I32 = jnp.int32
U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class PositSpec:
    """Static description of a Posit<n,es> format."""

    n: int = 16
    es: int = 1

    def __post_init__(self):
        assert 4 <= self.n <= 32, "posit width must be in [4, 32]"
        assert 0 <= self.es <= 3
        assert self.fbmax >= 1
        # decode-to-f32 requires the scale range to fit the f32 exponent
        assert (self.n - 2) * (1 << self.es) <= 126

    # -- derived static fields -------------------------------------------------
    @property
    def useed_exp(self) -> int:  # log2(useed) = 2^es
        return 1 << self.es

    @property
    def fbmax(self) -> int:
        # sign + minimal 2-bit regime + es exponent bits
        return self.n - 3 - self.es

    @property
    def mask_n(self) -> int:
        return (1 << self.n) - 1 if self.n < 32 else 0xFFFFFFFF

    @property
    def nar(self) -> int:
        return 1 << (self.n - 1)

    @property
    def maxpos_body(self) -> int:
        return (1 << (self.n - 1)) - 1

    @property
    def max_scale(self) -> int:  # scale of maxpos
        return (self.n - 2) * self.useed_exp

    @property
    def storage_dtype(self):
        return jnp.int32


P16 = PositSpec(16, 1)
P8 = PositSpec(8, 0)
P32 = PositSpec(32, 2)


def _clz32(x):
    """Count leading zeros of a uint32 via smear + popcount."""
    x = x.astype(U32)
    x = x | (x >> U32(1))
    x = x | (x >> U32(2))
    x = x | (x >> U32(4))
    x = x | (x >> U32(8))
    x = x | (x >> U32(16))
    return (U32(32) - jax.lax.population_count(x)).astype(I32)


def _shl(x, s):
    """Safe variable left shift: result 0 when s >= 32 or s < 0."""
    s = s.astype(I32) if hasattr(s, "astype") else jnp.asarray(s, I32)
    ok = (s >= 0) & (s < 32)
    sc = jnp.clip(s, 0, 31).astype(U32)
    return jnp.where(ok, x.astype(U32) << sc, U32(0))


def _shr(x, s):
    """Safe variable logical right shift: 0 when s >= 32, identity floor 0."""
    s = s.astype(I32) if hasattr(s, "astype") else jnp.asarray(s, I32)
    ok = (s >= 0) & (s < 32)
    sc = jnp.clip(s, 0, 31).astype(U32)
    return jnp.where(ok, x.astype(U32) >> sc, U32(0))


@partial(jax.jit, static_argnames=("spec",))
def decode_fields(bits, spec: PositSpec):
    """Unpack patterns -> (sign, scale, frac, is_zero, is_nar).

    * ``sign``  : int32, 0 or 1
    * ``scale`` : int32, k * 2^es + e   (eq. (1) exponent of 2)
    * ``frac``  : int32 in [0, 2^fbmax), fraction left-aligned to
      ``spec.fbmax`` fractional bits, so value = (-1)^s 2^scale (1 + frac/2^fbmax)
    """
    n, es, fb = spec.n, spec.es, spec.fbmax
    u = bits.astype(U32) & U32(spec.mask_n)
    is_zero = u == U32(0)
    is_nar = u == U32(spec.nar)
    sign = (u >> U32(n - 1)).astype(I32) & I32(1)
    mag = jnp.where(sign == 1, (U32(0) - u) & U32(spec.mask_n), u)
    body = mag & U32(spec.maxpos_body)
    # Left-align the n-1 body bits so the first regime bit is bit 31.
    v = body << U32(33 - n)
    r0 = (v >> U32(31)).astype(I32)
    pad = U32((1 << (33 - n)) - 1)
    w = jnp.where(r0 == 1, ~v, v) | pad
    m = _clz32(w)  # regime run length, in [1, n-1]
    k = jnp.where(r0 == 1, m - 1, -m)
    rest = _shl(v, m + 1)  # exponent+fraction bits, left-aligned at bit 31
    if es > 0:
        e = (rest >> U32(32 - es)).astype(I32)
    else:
        e = jnp.zeros_like(k)
    frac = ((rest << U32(es)) >> U32(32 - fb)).astype(I32)
    scale = k * I32(1 << es) + e
    return sign, scale, frac, is_zero, is_nar


@partial(jax.jit, static_argnames=("spec", "fbits_static"))
def encode_fields(sign, scale, frac, fbits, spec: PositSpec, fbits_static=None):
    """Pack (sign, scale, fraction) -> posit pattern with RNE rounding.

    ``frac`` holds ``fbits`` fractional bits (value = frac / 2^fbits in
    [0, 1)).  ``fbits`` may be a per-element int32 array (needed by the
    exact multiplier, where fraction normalization shifts the width) or
    a Python int.  Requires es + max(fbits) <= 30 so the combined
    exponent|fraction word fits uint32 with headroom.

    Implements pattern-space round-to-nearest-even (== SoftPosit):
    assemble regime|exp|frac at full precision, then RNE the dropped
    low bits; the carry correctly rolls fraction -> exponent -> regime.
    Saturates at maxpos / minpos.
    """
    n, es = spec.n, spec.es
    del fbits_static
    scale = scale.astype(I32)
    fbits = jnp.asarray(fbits, I32)
    frac = frac.astype(U32)

    if es > 0:
        k = scale >> I32(es)  # arithmetic shift == floor division
        e = (scale & I32((1 << es) - 1)).astype(U32)
    else:
        k = scale
        e = jnp.zeros_like(scale, dtype=U32)

    too_big = k >= I32(n - 2)
    too_small = k <= I32(-(n - 1))
    kc = jnp.clip(k, -(n - 2), n - 3)
    m = jnp.where(kc >= 0, kc + 2, 1 - kc)  # regime field width incl. terminator
    avail = I32(n - 1) - m  # bits left for exponent+fraction
    regime = jnp.where(kc >= 0, _shl(jnp.ones_like(kc, U32), kc + 2) - U32(2), U32(1))

    combined = _shl(e, fbits) | frac  # es + fbits significant bits
    tot = I32(es) + fbits
    shift_out = tot - avail

    kept = jnp.where(
        shift_out > 0, _shr(combined, shift_out), _shl(combined, -shift_out)
    )
    round_bit = jnp.where(
        shift_out > 0, _shr(combined, shift_out - 1) & U32(1), U32(0)
    )
    sticky_mask = jnp.where(
        shift_out > 1, _shl(jnp.ones_like(combined), shift_out - 1) - U32(1), U32(0)
    )
    sticky = (combined & sticky_mask) != U32(0)
    # ties-to-even on the FULL pattern (regime included): SoftPosit's
    # `ui += bitNPlusOne & (bitsMore | (ui & 1))`
    body_pre = _shl(regime, avail) + kept
    inc = round_bit & (sticky | ((body_pre & U32(1)) == U32(1))).astype(U32)
    body = body_pre + inc
    # a carry past maxpos saturates; body <= maxpos_body + 1 == 2^(n-1),
    # so subtracting bit n-1 clamps it without an unsigned min, which
    # Mosaic cannot lower
    body = body - (body >> U32(n - 1))
    body = jnp.where(too_big, U32(spec.maxpos_body), body)
    body = jnp.where(too_small, U32(1), body)  # minpos: never round to zero

    pattern = jnp.where(sign.astype(I32) == 1, (U32(0) - body) & U32(spec.mask_n), body)
    return pattern.astype(I32)


@partial(jax.jit, static_argnames=("spec",))
def decode(bits, spec: PositSpec):
    """Posit patterns -> float32 values (bit-exact for n <= 24)."""
    fb = spec.fbmax
    sign, scale, frac, is_zero, is_nar = decode_fields(bits, spec)
    if fb <= 23:
        mant = frac.astype(U32) << U32(23 - fb)
    else:  # one extra RNE step into the f32 mantissa
        sh = fb - 23
        mant = frac.astype(U32)
        lower = mant & U32((1 << sh) - 1)
        half = U32(1 << (sh - 1))
        mant_hi = mant >> U32(sh)
        rnd = (lower > half) | ((lower == half) & ((mant_hi & U32(1)) == U32(1)))
        mant = mant_hi + rnd.astype(U32)
        # mantissa carry into the exponent
        ovf = mant >> U32(23)
        scale = scale + ovf.astype(I32)
        mant = mant & U32(0x7FFFFF)
    fbits32 = (
        sign.astype(U32) << U32(31)
        | ((scale + I32(127)).astype(U32) << U32(23))
        | mant
    )
    val = jax.lax.bitcast_convert_type(fbits32, jnp.float32)
    val = jnp.where(is_zero, jnp.float32(0), val)
    val = jnp.where(is_nar, jnp.float32(jnp.nan), val)
    return val


@partial(jax.jit, static_argnames=("spec",))
def encode(x, spec: PositSpec):
    """float32 values -> posit patterns (RNE, saturating)."""
    x32 = x.astype(jnp.float32)
    b = jax.lax.bitcast_convert_type(x32, U32)
    sign = (b >> U32(31)).astype(I32)
    raw_e = ((b >> U32(23)) & U32(0xFF)).astype(I32)
    mant = b & U32(0x7FFFFF)
    is_zero = (b & U32(0x7FFFFFFF)) == U32(0)
    is_nar = raw_e == I32(255)  # inf/nan -> NaR
    scale = raw_e - I32(127)  # subnormals get scale -127 -> clamps to minpos
    bits = encode_fields(sign, scale, mant, 23, spec)
    bits = jnp.where(is_zero, I32(0), bits)
    bits = jnp.where(is_nar, I32(spec.nar), bits)
    return bits


@partial(jax.custom_jvp, nondiff_argnums=(1,))
def quantize(x, spec: PositSpec):
    """Project x onto the Posit<n,es> grid (straight-through gradient)."""
    return decode(encode(x, spec), spec).astype(x.dtype)


@quantize.defjvp
def _quantize_jvp(spec, primals, tangents):
    (x,), (dx,) = primals, tangents
    return quantize(x, spec), dx  # STE: identity pass-through


def pack16(bits):
    """int32 posit16 patterns -> int16 storage."""
    return bits.astype(jnp.uint16).astype(jnp.int16)


def unpack16(stored):
    return stored.astype(jnp.uint16).astype(jnp.int32)
