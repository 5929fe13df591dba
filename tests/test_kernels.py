"""Pallas kernel tests (interpret mode) vs pure-jnp oracles."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.numerics import P16, PositSpec, decode, encode
from repro.kernels import (
    plam_dense,
    plam_matmul_bits,
    posit_decode,
    posit_encode,
    posit_quantize,
)
from repro.kernels.plam_matmul import tile_sizes
from repro.kernels.ref import plam_dense_ref, plam_matmul_ref, posit_quantize_ref

SPECS = [PositSpec(16, 1), PositSpec(8, 0), PositSpec(16, 2)]
SHAPES = [(8, 16, 8), (32, 32, 32), (17, 23, 9), (128, 64, 130), (1, 7, 1), (256, 128, 64)]


def _rand_bits(rng, shape, spec):
    x = np.float32(rng.standard_normal(shape) * np.exp(rng.uniform(-2, 2, shape)))
    return encode(jnp.asarray(x), spec)


@pytest.mark.parametrize("spec", SPECS, ids=str)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_plam_matmul_kernel_vs_oracle(spec, shape):
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    a = _rand_bits(rng, (m, k), spec)
    b = _rand_bits(rng, (k, n), spec)
    ref = np.asarray(plam_matmul_ref(a, b, spec))
    ker = np.asarray(plam_matmul_bits(a, b, spec, bm=16, bn=16, bk=16, interpret=True))
    np.testing.assert_allclose(ker, ref, rtol=1e-4, atol=1e-4)


def test_plam_matmul_block_shape_sweep():
    """Result must be block-shape independent (accumulation assoc.)."""
    spec = P16
    rng = np.random.default_rng(42)
    a = _rand_bits(rng, (48, 64), spec)
    b = _rand_bits(rng, (64, 40), spec)
    ref = np.asarray(plam_matmul_ref(a, b, spec))
    for bm, bn, bk in [(8, 8, 8), (16, 32, 16), (48, 40, 64), (128, 128, 128)]:
        ker = np.asarray(plam_matmul_bits(a, b, spec, bm=bm, bn=bn, bk=bk, interpret=True))
        np.testing.assert_allclose(ker, ref, rtol=1e-4, atol=1e-4)


def test_plam_matmul_zero_and_sign_handling():
    spec = P16
    a = encode(jnp.asarray(np.float32([[0.0, -1.5, 2.0], [1.0, 0.0, -4.0]])), spec)
    b = encode(jnp.asarray(np.float32([[1.0, 0.0], [-2.0, 3.0], [0.5, -1.0]])), spec)
    ref = np.asarray(plam_matmul_ref(a, b, spec))
    ker = np.asarray(plam_matmul_bits(a, b, spec, bm=8, bn=8, bk=8, interpret=True))
    np.testing.assert_allclose(ker, ref, rtol=1e-6, atol=1e-6)


# Yi-6B's (K, N) weight shapes whole, then as the column slices each
# device multiplies at tp=4, with the decode N tile each should take.
YI_WEIGHTS = [
    ((4096, 4096), 512), ((4096, 512), 512), ((4096, 11008), 256),
    ((11008, 4096), 512), ((4096, 64000), 512),
    ((4096, 1024), 512), ((4096, 128), 128), ((4096, 2752), 128),
    ((11008, 1024), 512), ((4096, 16000), 128),
]


@pytest.mark.parametrize("kn,decode_bn", YI_WEIGHTS, ids=str)
def test_tile_sizes_never_pad_a_weight(kn, decode_bn):
    """At decode M the N tile widens to the widest that divides N, so a
    weight whose N is a multiple of 128 is never padded; from M = 16 up
    the tiles stay (min(M, 128), 128, 128)."""
    k, n = kn
    for m in (1, 8):
        bm, bn, bk = tile_sizes(m, k, n)
        assert (bm, bn, bk) == (m, decode_bn, 128)
        assert n % 128 or n % bn == 0
    for m in (16, 64, 256):
        assert tile_sizes(m, k, n) == (min(m, 128), 128, 128)


@pytest.mark.parametrize(
    "n,bn", [(1536, 512), (1152, 384), (1280, 256), (640, 128), (1100, 128), (100, 100)])
def test_tile_sizes_take_the_widest_dividing_tile(n, bn):
    assert tile_sizes(8, 256, n)[1] == bn


def test_plam_dense_batched():
    spec = P16
    rng = np.random.default_rng(7)
    x = jnp.asarray(np.float32(rng.standard_normal((4, 6, 32))))  # batch dims
    w = _rand_bits(rng, (32, 16), spec)
    ref = np.asarray(plam_dense_ref(np.reshape(x, (24, 32)), w, spec)).reshape(4, 6, 16)
    out = np.asarray(plam_dense(x, w, spec, bm=16, bn=16, bk=16, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("spec", SPECS, ids=str)
@pytest.mark.parametrize("shape", [(16, 128), (37, 211), (1, 5), (300, 300)], ids=str)
def test_codec_kernels_vs_oracle(spec, shape):
    rng = np.random.default_rng(3)
    x = jnp.asarray(np.float32(rng.standard_normal(shape) * np.exp(rng.uniform(-10, 10, shape))))
    q_k = np.asarray(posit_quantize(x, spec, block=(8, 128), interpret=True))
    q_r = np.asarray(posit_quantize_ref(x, spec))
    assert np.array_equal(q_k, q_r)
    e_k = np.asarray(posit_encode(x, spec, block=(8, 128), interpret=True))
    e_r = np.asarray(encode(x, spec))
    assert np.array_equal(e_k, e_r)
    d_k = np.asarray(posit_decode(e_r, spec, block=(8, 128), interpret=True))
    d_r = np.asarray(decode(e_r, spec))
    assert np.array_equal(d_k, d_r)


def test_codec_kernel_nd_shapes():
    spec = P16
    rng = np.random.default_rng(4)
    x = jnp.asarray(np.float32(rng.standard_normal((3, 5, 7, 11))))
    q_k = np.asarray(posit_quantize(x, spec, block=(8, 128), interpret=True))
    q_r = np.asarray(posit_quantize_ref(x, spec))
    assert np.array_equal(q_k, q_r)


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=1, max_value=40),
    st.integers(min_value=1, max_value=40),
    st.integers(min_value=1, max_value=40),
    st.sampled_from([(8, 8, 8), (16, 16, 16), (32, 8, 16)]),
)
def test_hypothesis_matmul_shapes(m, k, n, blocks):
    """Property: kernel == oracle for arbitrary small shapes/blocks."""
    spec = P16
    rng = np.random.default_rng(m * 1600 + k * 40 + n)
    a = _rand_bits(rng, (m, k), spec)
    b = _rand_bits(rng, (k, n), spec)
    bm, bn, bk = blocks
    ref = np.asarray(plam_matmul_ref(a, b, spec))
    ker = np.asarray(plam_matmul_bits(a, b, spec, bm=bm, bn=bn, bk=bk, interpret=True))
    np.testing.assert_allclose(ker, ref, rtol=1e-4, atol=1e-4)
