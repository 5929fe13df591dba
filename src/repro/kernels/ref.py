"""Pure-jnp oracles for the Pallas kernels.

These are the semantic references the kernels must match bit-for-bit
(`assert_allclose` with tight tolerances in tests).  They are written
for clarity, not speed — full [M, K, N] broadcasts — so keep shapes
small when calling them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.numerics import PositSpec, decode, encode, plam_product_f32


@partial(jax.jit, static_argnames=("spec",))
def plam_matmul_ref(a_bits, b_bits, spec: PositSpec):
    """EMAC-style PLAM matmul oracle.

    C[m, n] = sum_k PLAM(A[m, k], B[k, n]) with each approximate product
    antilogged to linear f32 and accumulated in f32 (Johnson-style
    linear accumulation; the paper's DNN experiments do the same via
    Deep PeNSieve's fused dot).
    """
    prods = plam_product_f32(a_bits[:, :, None], b_bits[None, :, :], spec)
    return jnp.sum(prods, axis=1, dtype=jnp.float32)


@partial(jax.jit, static_argnames=("spec",))
def plam_matmul_seqref(a_bits, b_bits, spec: PositSpec):
    """Sequential-k PLAM matmul: BIT-identical to the Pallas kernel.

    ``plam_matmul_ref`` reduces with ``jnp.sum``, whose f32 reduction
    order XLA does not pin down, so it is only allclose to the kernel.
    This reference accumulates k strictly ascending — the order the
    kernel's k loop walks lanes within and across K blocks — so
    ``np.array_equal`` comparisons are valid for any (M, N, K), ragged
    or not.  The kernel's zero-padding lanes add exactly +0.0 and both
    accumulators start at +0.0, so padding never perturbs a bit.
    """
    prods = plam_product_f32(a_bits[:, :, None], b_bits[None, :, :], spec)
    m, k, n = prods.shape
    acc0 = jnp.zeros((m, n), jnp.float32)

    def body(i, acc):
        return acc + prods[:, i, :]

    return jax.lax.fori_loop(0, k, body, acc0)


@partial(jax.jit, static_argnames=("spec",))
def plam_dense_ref(x, w_bits, spec: PositSpec):
    """x (f32 [M,K]) @ posit-weights (bits [K,N]): quantize x, PLAM-matmul."""
    return plam_matmul_ref(encode(x, spec), w_bits, spec)


@partial(jax.jit, static_argnames=("spec",))
def posit_quantize_ref(x, spec: PositSpec):
    """Project f32 onto the posit grid (decode(encode(x)))."""
    return decode(encode(x, spec), spec)
