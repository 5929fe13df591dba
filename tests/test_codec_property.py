"""Property tests: bit codec (posit.py) ≡ exhaustive-table codec
(table.py) across every supported (n, es) spec with n <= 16.

The two codecs are independent formulations (bit-twiddling pattern-RNE
vs golden-model value-space nearest-ties-to-even-pattern); agreement on
encode, decode and round trips — including the zero / NaR / ±maxpos
edges — is one of the repo's strongest invariants.  Runs through
tests/_hypothesis_shim.py when hypothesis is not installed.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.numerics import (
    PositSpec,
    decode,
    decode_table,
    encode,
    encode_table,
)

# every (n, es) the exhaustive-table codec supports (n <= 16), subject
# to the PositSpec constraints (fbmax >= 1, scale range fits f32)
ALL_SPECS = [
    PositSpec(n, es)
    for n in range(4, 17)
    for es in range(0, 4)
    if n - 3 - es >= 1 and (n - 2) * (1 << es) <= 126
]
# the sweep below samples floats per spec; keep a smaller exhaustive
# core for the pattern round-trip to bound runtime
CORE_SPECS = [
    PositSpec(4, 0), PositSpec(5, 1), PositSpec(6, 2), PositSpec(8, 0),
    PositSpec(8, 1), PositSpec(8, 3), PositSpec(10, 2), PositSpec(12, 1),
    PositSpec(16, 0), PositSpec(16, 1), PositSpec(16, 2), PositSpec(16, 3),
]

# hypothesis needs float32-representable bounds for width=32 draws
_BOUND = float(np.float32(1e30))


def _match(a, b):
    return (a == b) | (np.isnan(a) & np.isnan(b))


def _maxpos(spec):
    return float(2.0 ** spec.max_scale)


@pytest.mark.parametrize("spec", ALL_SPECS, ids=str)
def test_edges_zero_nar_maxpos(spec):
    """zero / NaR / ±maxpos agree between both codecs."""
    maxpos = _maxpos(spec)
    xs = jnp.asarray(
        np.array([0.0, -0.0, np.nan, np.inf, -np.inf,
                  maxpos, -maxpos, 10 * maxpos, -10 * maxpos,
                  1 / maxpos, -1 / maxpos, 0.1 / maxpos], np.float32))
    eb = np.asarray(encode(xs, spec)) & spec.mask_n
    et = np.asarray(encode_table(xs, spec)) & spec.mask_n
    assert np.array_equal(eb, et)
    assert eb[0] == 0 and eb[1] == 0  # ±0 -> zero pattern
    assert eb[2] == spec.nar and eb[3] == spec.nar and eb[4] == spec.nar
    assert eb[5] == spec.maxpos_body  # maxpos encodes to maxpos
    assert eb[7] == spec.maxpos_body  # saturation, never NaR
    assert eb[9] == 1  # minpos
    assert eb[11] == 1  # underflow saturates to minpos, never to zero
    pats = jnp.asarray(
        np.array([0, spec.nar, spec.maxpos_body, 1,
                  (-spec.maxpos_body) & spec.mask_n,
                  (-1) & spec.mask_n], np.int32))
    db = np.asarray(decode(pats, spec), np.float64)
    dt = np.asarray(decode_table(pats, spec), np.float64)
    assert _match(db, dt).all()
    assert db[0] == 0.0 and np.isnan(db[1])
    assert db[2] == maxpos and db[4] == -maxpos


@pytest.mark.parametrize("spec", CORE_SPECS, ids=str)
def test_exhaustive_pattern_round_trip_both_codecs(spec):
    """For EVERY pattern: table decode == bit decode, and both codecs
    re-encode the decoded value back to the same pattern (bijection)."""
    pats = np.arange(1 << spec.n, dtype=np.int32)
    jp = jnp.asarray(pats)
    db = np.asarray(decode(jp, spec))
    dt = np.asarray(decode_table(jp, spec))
    assert _match(db, dt).all()
    rb = np.asarray(encode(jnp.asarray(db), spec)) & spec.mask_n
    rt = np.asarray(encode_table(jnp.asarray(dt), spec)) & spec.mask_n
    assert np.array_equal(rb, pats & spec.mask_n)
    assert np.array_equal(rt, pats & spec.mask_n)


@settings(max_examples=60, deadline=None)
@given(
    st.sampled_from(ALL_SPECS),
    st.floats(min_value=-_BOUND, max_value=_BOUND, allow_nan=False, width=32),
)
def test_property_encode_agrees(spec, x):
    xs = jnp.float32(x)
    eb = int(encode(xs, spec)) & spec.mask_n
    et = int(encode_table(xs, spec)) & spec.mask_n
    assert eb == et, (spec, x, hex(eb), hex(et))


@settings(max_examples=60, deadline=None)
@given(
    st.sampled_from(ALL_SPECS),
    st.floats(min_value=-_BOUND, max_value=_BOUND, allow_nan=False, width=32),
)
def test_property_quantize_round_trip_agrees(spec, x):
    """decode(encode(x)) is identical through either codec, and
    re-encoding the quantized value is a fixed point (idempotence)."""
    xs = jnp.float32(x)
    qb = float(decode(encode(xs, spec), spec))
    qt = float(decode_table(encode_table(xs, spec), spec))
    assert qb == qt or (np.isnan(qb) and np.isnan(qt)), (spec, x, qb, qt)
    rb = int(encode(jnp.float32(qb), spec)) & spec.mask_n
    assert rb == int(encode(xs, spec)) & spec.mask_n


@settings(max_examples=40, deadline=None)
@given(
    st.sampled_from(ALL_SPECS),
    st.integers(min_value=0, max_value=(1 << 16) - 1),
)
def test_property_pattern_decode_agrees(spec, pat):
    pat &= spec.mask_n
    db = float(decode(jnp.int32(pat), spec))
    dt = float(decode_table(jnp.int32(pat), spec))
    assert db == dt or (np.isnan(db) and np.isnan(dt)), (spec, hex(pat))
