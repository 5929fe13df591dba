"""Differential conformance suite: the oracle matrix must agree.

Four independent formulations of the posit/PLAM numerics (pure-Python
golden, vectorized JAX bit kernels, exhaustive-table codec, Pallas
kernels) are compared per-op:

* committed golden vectors under ``tests/vectors/`` (the fast drift
  gate — regenerate with ``python -m repro.conformance gen``),
* exhaustive all-pairs multiplier sweeps vs golden for small n,
* bit-identical Pallas matmul parity on ragged/tile-boundary shapes,
* the paper's Sec. III-C error-model claims (eq. 24) promoted from
  ``benchmarks/error_analysis.py`` into asserted tests,
* metamorphic properties through the hypothesis shim, and
* fault-injection meta-tests: a single flipped bit in ANY layer must
  be caught by the fuzzer and shrunk to a minimal reproducer.

``REPRO_PROP_MULT`` scales the drawn-example budgets (CI stress lane).
"""
import os
import pathlib
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.conformance import (
    FaultyImpl,
    GoldenImpl,
    boundary_patterns,
    check_vectors,
    default_impls,
    outputs_equal,
    run_fuzz,
    shrink_pair,
)
from repro.conformance.shrink import describe_pattern, reproducer
from repro.conformance.vectors import VECTOR_DIR, pair_grid, plan
from repro.kernels import plam_matmul_bits
from repro.kernels.ref import plam_matmul_ref, plam_matmul_seqref
from repro.numerics import PositSpec, plam_relative_error
from repro.numerics.plam import exact_mul, plam_mul

_MULT = int(os.environ.get("REPRO_PROP_MULT", "1"))

SWEEP_SPECS = [PositSpec(6, 0), PositSpec(8, 0), PositSpec(8, 1),
               PositSpec(10, 1)]


# ---------------------------------------------------------------- vectors

def test_committed_vectors_present_and_green():
    """Every planned vector file exists and every impl reproduces it."""
    assert VECTOR_DIR.is_dir(), (
        f"{VECTOR_DIR} missing — run `python -m repro.conformance gen`")
    failures = check_vectors()
    assert not failures, "\n".join(failures)


def test_vector_plan_covers_spec_matrix():
    items = plan()
    specs = {(i["n"], i["es"]) for i in items}
    assert (16, 1) in specs, "the headline P16 spec must be pinned"
    assert all((n, es) in specs for n, es in [(6, 0), (8, 0), (8, 1), (10, 1)])
    ops = {i["op"] for i in items}
    assert ops == {"plam_mul", "exact_mul", "decode"}


# ------------------------------------------- exhaustive multiplier sweeps

@pytest.mark.parametrize("spec", SWEEP_SPECS, ids=str)
@pytest.mark.parametrize("op", ["plam_mul", "exact_mul"])
def test_exhaustive_mul_jax_vs_golden(spec, op):
    """ALL bit pairs: the JAX multiplier == the pure-Python golden model."""
    pa, pb = pair_grid(spec.n)
    fn = plam_mul if op == "plam_mul" else exact_mul
    jax_out = np.asarray(fn(pa, pb, spec)) & spec.mask_n
    gold = GoldenImpl().run(op, (pa, pb), spec) & spec.mask_n
    bad = jax_out != gold
    if bad.any():
        i = int(np.flatnonzero(bad)[0])
        raise AssertionError(
            f"{op} {spec}: first mismatch at pair "
            f"({describe_pattern(int(pa[i]), spec)}; "
            f"{describe_pattern(int(pb[i]), spec)}): "
            f"jax {jax_out[i]:#x} vs golden {gold[i]:#x} "
            f"[{bad.sum()} total]")


@pytest.mark.slow
@pytest.mark.parametrize("op", ["plam_mul", "exact_mul"])
def test_exhaustive_mul_n12_jax_vs_golden(op):
    """16.7M-pair sweep for Posit<12,1> (slow lane)."""
    spec = PositSpec(12, 1)
    pa, pb = pair_grid(spec.n)
    fn = plam_mul if op == "plam_mul" else exact_mul
    jax_out = np.asarray(fn(pa, pb, spec)) & spec.mask_n
    gold = GoldenImpl().run(op, (pa, pb), spec) & spec.mask_n
    assert np.array_equal(jax_out, gold), f"{op} {spec}: sweep diverged"


# ------------------------------------------------- Pallas matmul parity

RAGGED_SHAPES = [
    (4, 5, 3),      # K not a block multiple
    (1, 7, 1),      # M = N = 1
    (3, 130, 9),    # K just past one 128-block
    (9, 257, 5),    # K spans three blocks with a ragged tail
    (2, 1, 2),      # K = 1
    (17, 64, 33),   # ragged M and N
    (8, 256, 1536),  # decode M: three 512-wide N tiles
    (8, 384, 1280),  # decode M: five 256-wide N tiles
    (8, 256, 1100),  # decode M, ragged N: 128-wide tiles, N padded
    (16, 130, 640),  # prefill M, K just past one block
    (64, 384, 1024),  # prefill M: 128-wide tiles
]


@pytest.mark.parametrize("shape", RAGGED_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_plam_matmul_bit_identical_to_seqref(shape):
    """Pallas matmul == sequential-k reference, bit for bit, on shapes
    that exercise the zero-padding paths (ragged K/M/N, unit dims)."""
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) & 0xFFFF)
    spec = PositSpec(16, 1)
    a = rng.integers(0, 1 << 16, (m, k)).astype(np.int32)
    b = rng.integers(0, 1 << 16, (k, n)).astype(np.int32)
    a.flat[:: max(1, a.size // 7)] = spec.nar  # NaR lanes must mask to 0
    b.flat[:: max(1, b.size // 5)] = 0
    want = np.asarray(plam_matmul_seqref(a, b, spec))
    got = np.asarray(plam_matmul_bits(a, b, spec, interpret=True))
    assert np.array_equal(want.view(np.uint32), got.view(np.uint32)), (
        f"shape {shape}: kernel diverged from sequential reference")


def _extreme_patterns(spec):
    """maxpos, minpos, their negatives, +-1, zero and NaR."""
    one = 1 << (spec.n - 2)
    pos = [spec.maxpos_body, 1, one]
    return pos + [(-p) & spec.mask_n for p in pos] + [0, spec.nar]


@pytest.mark.parametrize("k", [1, 16], ids=["single", "summed"])
@pytest.mark.parametrize(
    "spec", [PositSpec(16, 1), PositSpec(8, 0), PositSpec(16, 2)], ids=str)
def test_plam_matmul_extremes_bit_identical_to_seqref(spec, k):
    """The sign folded into the log words at the scale extremes: every
    pair of maxpos, minpos, their negatives, +-1, zero and NaR, alone
    (k=1) and summed, across a 512-wide decode tile."""
    pats = np.array(_extreme_patterns(spec), np.int32)
    p = len(pats)
    m, n = 8, 512
    a = pats[(np.arange(m)[:, None] + np.arange(k)[None, :]) % p]
    b = pats[(3 * np.arange(k)[:, None] + np.arange(n)[None, :]) % p]
    want = np.asarray(plam_matmul_seqref(a, b, spec))
    got = np.asarray(plam_matmul_bits(a, b, spec, interpret=True))
    assert np.array_equal(want.view(np.uint32), got.view(np.uint32))
    if k == 1:  # a lone product of a zero or NaR is exactly +0.0
        live = ~np.isin(a[:, :1], [0, spec.nar]) & ~np.isin(b[:1, :], [0, spec.nar])
        assert np.all(got.view(np.uint32)[~live] == 0)
        assert np.all(got[live] != 0)


def test_plam_matmul_seqref_close_to_sum_ref():
    """The two references agree to f32 reduction-order noise."""
    rng = np.random.default_rng(7)
    spec = PositSpec(16, 1)
    a = rng.integers(0, 1 << 16, (8, 40)).astype(np.int32)
    b = rng.integers(0, 1 << 16, (40, 6)).astype(np.int32)
    np.testing.assert_allclose(
        np.asarray(plam_matmul_seqref(a, b, spec)),
        np.asarray(plam_matmul_ref(a, b, spec)),
        rtol=1e-5, atol=1e-30)


@pytest.mark.skipif(
    __import__("jax").default_backend() != "tpu",
    reason="compiled Pallas needs a TPU backend")
def test_plam_matmul_compiled_matches_interpret():
    rng = np.random.default_rng(3)
    spec = PositSpec(16, 1)
    a = rng.integers(0, 1 << 16, (9, 130)).astype(np.int32)
    b = rng.integers(0, 1 << 16, (130, 5)).astype(np.int32)
    ci = np.asarray(plam_matmul_bits(a, b, spec, interpret=True))
    cc = np.asarray(plam_matmul_bits(a, b, spec, interpret=False))
    assert np.array_equal(ci.view(np.uint32), cc.view(np.uint32))


# -------------------------------------------- error model (paper eq. 24)

def _error_analysis():
    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks import error_analysis
    return error_analysis


def test_eq24_bound_and_argmax_on_fraction_grid():
    """Empirical error grid obeys eq. (24): max 1/9 at fa = fb = 0.5."""
    ea = _error_analysis()
    fa, fb, err = ea.error_grid(n=64)
    assert err.max() <= 1 / 9 + 1e-6, f"error {err.max()} exceeds 1/9 bound"
    am = np.unravel_index(err.argmax(), err.shape)
    assert abs(fa[am[0]] - 0.5) <= 1 / 64 and abs(fb[am[1]] - 0.5) <= 1 / 64
    # independently-written eq. (24) vs the grid, pointwise: without a
    # fraction carry the approximation is 1+fa+fb (error fa*fb/exact);
    # with a carry it is 2(fa+fb) (error (1-fa)(1-fb)/exact)
    ga, gb = fa[:, None], fb[None, :]
    exact = (1 + ga) * (1 + gb)
    analytic = np.where(ga + gb < 1,
                        ga * gb / exact,
                        (1 - ga) * (1 - gb) / exact)
    np.testing.assert_allclose(err, analytic, atol=2e-4)


def test_error_scale_independence():
    """Same fractions across regimes/exponents -> identical error."""
    ea = _error_analysis()
    errs = ea.scale_independence(trials=32)
    assert float(errs.std()) <= 1e-7, (
        f"PLAM error varied with scale fields: std={errs.std():.3e}")


def test_dnn_distribution_mean_error_band():
    """N(0,1) operands land in the paper's few-percent mean-error regime."""
    ea = _error_analysis()
    err = ea.dnn_distribution_error(n=20_000)
    assert 0.0 <= float(err.mean()) <= 0.08
    assert float(err.max()) <= 1 / 9 + 1e-6


@settings(max_examples=50 * _MULT, deadline=None)
@given(st.integers(0, (1 << 16) - 1), st.integers(0, (1 << 16) - 1))
def test_relative_error_bound_property(pa, pb):
    spec = PositSpec(16, 1)
    err = float(np.asarray(
        plam_relative_error(np.int32([pa]), np.int32([pb]), spec))[0])
    assert -1e-6 <= err <= 1 / 9 + 1e-6


# ------------------------------------------------ metamorphic properties

@settings(max_examples=40 * _MULT, deadline=None)
@given(st.sampled_from([(8, 0), (10, 1), (16, 1)]),
       st.integers(0, (1 << 16) - 1), st.integers(0, (1 << 16) - 1))
def test_mul_commutes_across_impls(spec_ne, pa, pb):
    spec = PositSpec(*spec_ne)
    pa &= spec.mask_n
    pb &= spec.mask_n
    impls = default_impls(spec)
    for name, im in impls.items():
        for op in ("plam_mul", "exact_mul"):
            if op not in im.ops(spec):
                continue
            ab = im.run(op, (np.int32([pa]), np.int32([pb])), spec)
            ba = im.run(op, (np.int32([pb]), np.int32([pa])), spec)
            assert outputs_equal(ab, ba).all(), (
                f"{name}.{op} not commutative on ({pa:#x}, {pb:#x})")


@settings(max_examples=40 * _MULT, deadline=None)
@given(st.integers(0, (1 << 16) - 1))
def test_nar_absorbs_and_one_is_identity(p):
    spec = PositSpec(16, 1)
    p &= spec.mask_n
    one = 1 << (spec.n - 2)
    impls = default_impls(spec)
    for name, im in impls.items():
        for op in ("plam_mul", "exact_mul"):
            if op not in im.ops(spec):
                continue
            out = im.run(op, (np.int32([p]), np.int32([spec.nar])), spec)
            assert (np.asarray(out, np.int64) & spec.mask_n == spec.nar).all(), (
                f"{name}.{op}: NaR not absorbing for {p:#x}")
            out = im.run("exact_mul", (np.int32([p]), np.int32([one])), spec) \
                if op == "exact_mul" else None
            if out is not None:
                assert (np.asarray(out, np.int64) & spec.mask_n == p).all(), (
                    f"{name}: x * 1 != x for {p:#x}")


def test_boundary_patterns_cover_edges():
    spec = PositSpec(8, 0)
    pats = set(int(p) for p in boundary_patterns(spec))
    assert {0, spec.nar, 1, 1 << (spec.n - 2)} <= pats
    assert all(0 <= p <= spec.mask_n for p in pats)


# ------------------------------------------------------- fuzz (fast run)

def test_fuzz_small_budget_is_clean():
    """A small seeded fuzz across two specs finds no disagreements."""
    report = run_fuzz(specs=(PositSpec(8, 0),), seed=3, count=128,
                      modes=("uniform", "boundary"))
    assert report.ok, report.summary()
    assert report.checked > 0


# ------------------------------------------------------- fault injection

FAULT_PLANS = [
    ("golden", "exact_mul", 0),
    ("jax", "plam_mul", 2),
    ("table", "plam_mul", 0),
    ("pallas_interp", "decode", 7),
]


@pytest.mark.parametrize("layer,op,bit", FAULT_PLANS,
                         ids=[f"{p[0]}.{p[1]}" for p in FAULT_PLANS])
def test_single_bit_fault_is_caught_and_shrunk(layer, op, bit):
    """Flipping one output bit in ANY layer must be detected by the
    differential fuzzer and reduced to a minimal reproducer."""
    spec = PositSpec(8, 0)
    impls = default_impls(spec)
    impls[layer] = FaultyImpl(impls[layer], op, bit=bit)
    report = run_fuzz(specs=(spec,), seed=1, count=256, impls=impls,
                      modes=("uniform",))
    assert not report.ok, f"fault in {layer}.{op} went undetected"
    caught = [m for m in report.mismatches
              if layer in m.impl_a or layer in m.impl_b]
    assert caught, f"mismatches found but none attributed to {layer}"
    shrunk = [m for m in caught if m.report]
    assert shrunk, "no shrunk reproducer attached"
    rep = shrunk[0].report
    assert "CONFORMANCE MISMATCH" in rep
    assert "def test_regression_" in rep, "missing paste-ready snippet"


def test_faulty_impl_trigger_gates_the_fault():
    spec = PositSpec(8, 0)
    base = default_impls(spec)["jax"]
    faulty = FaultyImpl(base, "plam_mul", bit=0,
                        trigger=lambda a, b: np.zeros(np.shape(a), bool))
    pa = np.int32([12]); pb = np.int32([34])
    assert outputs_equal(
        faulty.run("plam_mul", (pa, pb), spec),
        base.run("plam_mul", (pa, pb), spec)).all()


# ------------------------------------------------------------- shrinker

def test_shrink_pair_reaches_minimal_pair():
    """A predicate true whenever bit 0 of pa is set shrinks to (1, 0)."""
    pa, pb = shrink_pair(lambda a, b: bool(a & 1), 0xB7, 0x5D, 8)
    assert pa == 1 and pb == 0


def test_shrink_pair_respects_joint_predicate():
    pred = lambda a, b: (a & 0x80) != 0 and (b & 0x80) != 0  # noqa: E731
    pa, pb = shrink_pair(pred, 0xFF, 0xD3, 8)
    assert pred(pa, pb)
    assert bin(pa).count("1") == 1 and bin(pb).count("1") == 1


def test_describe_pattern_fields():
    spec = PositSpec(8, 0)
    assert describe_pattern(0, spec).endswith("zero")
    assert describe_pattern(spec.nar, spec).endswith("NaR")
    line = describe_pattern(1 << 6, spec)  # +1.0
    assert "value 1" in line and "k=0" in line


def test_reproducer_snippet_is_paste_ready():
    from repro.conformance.fuzz import Mismatch
    spec = PositSpec(8, 0)
    mm = Mismatch(op="plam_mul", spec=spec, impl_a="golden", impl_b="table",
                  inputs=(0x40, 0x41), out_a=0x41, out_b=0x42, count=1)
    text = reproducer(mm, spec)
    assert "from repro.conformance import default_impls" in text
    assert "outputs_equal" in text and "PositSpec(8, 0)" in text


# --------------------------------------------------------------- oracles

def test_oracle_matrix_ops_cover_contract():
    """Every default impl exposes a coherent subset of the op set."""
    spec = PositSpec(16, 1)
    impls = default_impls(spec)
    assert {"golden", "jax", "jax_logfix", "table", "pallas_interp"} <= set(impls)
    for name, im in impls.items():
        ops = im.ops(spec)
        assert ops, f"{name} exposes no ops"
        assert set(ops) <= {"encode", "decode", "quantize", "exact_mul",
                            "plam_mul"}
    assert set(impls["golden"].ops(spec)) == {
        "encode", "decode", "quantize", "exact_mul", "plam_mul"}


def test_encode_subnormal_regression():
    """Regression for the DAZ bug the fuzzer caught: an f32-subnormal
    input must encode to minpos (never to zero) in EVERY layer."""
    x = np.float32([9.99994610111476e-41, -9.99994610111476e-41])
    for spec in (PositSpec(8, 0), PositSpec(16, 1)):
        impls = default_impls(spec)
        want = np.array([1, spec.mask_n], np.int64)
        for name, im in impls.items():
            if "encode" not in im.ops(spec):
                continue
            got = np.asarray(im.run("encode", (x,), spec), np.int64) & spec.mask_n
            assert np.array_equal(got, want), (
                f"{name}.encode flushed a subnormal to {got} (want {want})")
