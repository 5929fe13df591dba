"""The reference's numerics against the program's, on the CPU.

Run by path: ``python -m pytest -q benchmarks/chip/tests``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import reference
import weights
from cell import load_family
from repro.kernels.plam_matmul import plam_matmul
from repro.numerics import PositSpec, decode, encode, quantize

SPEC = PositSpec(16, 1)


def _all_posit_values():
    bits = jnp.arange(1 << 16, dtype=jnp.int32)
    vals = np.asarray(decode(bits, SPEC), np.float32)
    return np.unique(vals[np.isfinite(vals)])


def test_posit16_round_matches_program_on_grid_and_ties():
    vals = _all_posit_values()
    pos = vals[vals > 0]
    mids = (pos[:-1].astype(np.float64) + pos[1:]) / 2  # value midpoints
    geo = np.sqrt(pos[:-1].astype(np.float64) * pos[1:])  # geometric midpoints
    probes = np.concatenate([
        vals, mids, geo, np.nextafter(mids, 0), np.nextafter(mids, np.inf),
        [2.0**-30, 2.0**-27, 2.0**27, 2.0**29, 1e-38, 3e38],
    ]).astype(np.float32)
    probes = np.concatenate([probes, -probes])
    want = np.asarray(quantize(jnp.asarray(probes), SPEC))
    got = np.asarray(reference.posit16_round(jnp.asarray(probes)))
    bad = np.flatnonzero(want.view(np.uint32) != got.view(np.uint32))
    assert bad.size == 0, [(probes[i], want[i], got[i]) for i in bad[:8]]


def test_posit16_round_matches_program_on_random_floats():
    rng = np.random.default_rng(1)
    x = (rng.standard_normal(200_000) * np.exp(rng.uniform(-25, 25, 200_000)))
    x = x.astype(np.float32)
    want = np.asarray(quantize(jnp.asarray(x), SPEC))
    got = np.asarray(reference.posit16_round(jnp.asarray(x)))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("m,k,n", [(8, 64, 48), (5, 200, 33)])
def test_plam_dot_matches_the_kernel(m, k, n):
    rng = np.random.default_rng(m * k)
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = (rng.standard_normal((k, n)) * 0.05).astype(np.float32)
    x[0, :3] = 0.0
    w[5, :] = 0.0
    xq = reference.posit16_round(jnp.asarray(x))
    wq = reference.posit16_round(jnp.asarray(w))
    want = np.asarray(plam_matmul(encode(jnp.asarray(x), SPEC),
                                  encode(jnp.asarray(w), SPEC), SPEC, interpret=True))
    got = np.asarray(reference.plam_dot(xq, wq))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the order of a float32 sum is all that may differ
    exact = np.asarray(reference.exact_dot(xq, wq))
    assert np.max(np.abs(got - exact)) > 1e-3


def test_one_layer_remade_equals_its_slice_of_the_served_tree():
    model = {"num_hidden_layers": 3, "hidden_size": 32, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 48,
             "vocab_size": 64}
    dense = load_family("dense")
    tree = dense.make_params(model, 2**31 + 5, "bfloat16")
    key = weights.seed_key(2**31 + 5)
    for layer in range(3):
        alone = dense.layer_weights(model, key, layer, jnp.bfloat16)
        for (group, name), leaf in alone.items():
            np.testing.assert_array_equal(
                np.asarray(leaf, np.float32),
                np.asarray(tree["layers"][group][name][layer], np.float32))
    embed, unembed = weights.top_weights(model, key, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(embed, np.float32),
                                  np.asarray(tree["embed"], np.float32))
    assert tree["unembed"].shape == (32, 64)


def test_fp8_control_is_coarser_than_posit16():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((16, 256)), jnp.float32)
    e8 = float(jnp.max(jnp.abs(reference.fp8_round(x, axis=1) - x)))
    e16 = float(jnp.max(jnp.abs(reference.posit16_round(x) - x)))
    assert e8 > 10 * e16
