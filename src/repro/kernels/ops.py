"""Public jit'd wrappers over the Pallas kernels.

Off TPU the kernels default to interpret mode — the kernel body
executes as pure jnp on the host, which validates results only; on TPU
the same code lowers through Mosaic.  ``interpret=`` overrides the
default either way.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from repro.numerics import PositSpec, encode
from repro.parallel.sharding import current_mesh, kernel_weight_dims, pspec

from . import plam_matmul as _pm
from . import posit_codec as _pc


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def plam_matmul_bits(a_bits, b_bits, spec: PositSpec = PositSpec(16, 1), **kw):
    """PLAM matmul over posit patterns -> f32."""
    kw.setdefault("interpret", _interpret_default())
    return _pm.plam_matmul(a_bits, b_bits, spec, **kw)


def plam_dense(x, w_bits, spec: PositSpec = PositSpec(16, 1), **kw):
    """f32 activations x posit-pattern weights via the PLAM kernel.

    Activations are posit-quantized (encoded) on the fly; weights are
    stored pre-encoded — the deployment layout for posit inference.
    Leading batch dims of x are flattened into M.

    Under a tensor-parallel mesh the kernel, which GSPMD cannot
    partition, is shard_mapped over output columns: x is replicated,
    each device multiplies its column slice of ``w_bits`` (the layout of
    ``parallel.sharding.kernel_weight_dims``) and the slices are
    all-gathered.  Every output element keeps the full,
    ascending K accumulation that one device computes.
    """
    kw.setdefault("interpret", _interpret_default())
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])

    def local(xb, wb):
        return _pm.plam_matmul(encode(xb, spec), wb, spec, **kw)

    mesh = current_mesh()
    if mesh is None or mesh.shape.get("model", 1) == 1:
        out = local(x2, w_bits)
    else:
        dims = kernel_weight_dims(mesh, w_bits.shape)
        split = dims[-1] is not None  # else every device runs it whole

        def body(xb, wb):
            y = local(xb, wb)
            return jax.lax.all_gather(y, "model", axis=1, tiled=True) if split else y

        out = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), pspec(mesh, dims)),
            out_specs=P(),
            check_vma=False,
        )(x2, w_bits)
    return out.reshape(*lead, w_bits.shape[-1])


def plam_mul_elementwise(a_bits, b_bits, spec: PositSpec = PositSpec(16, 1), **kw):
    """Element-wise PLAM pattern product (conformance oracle surface)."""
    kw.setdefault("interpret", _interpret_default())
    return _pc.plam_mul_elementwise(a_bits, b_bits, spec, **kw)


def exact_mul_elementwise(a_bits, b_bits, spec: PositSpec = PositSpec(16, 1), **kw):
    """Element-wise exact posit pattern product (n <= 16)."""
    kw.setdefault("interpret", _interpret_default())
    return _pc.exact_mul_elementwise(a_bits, b_bits, spec, **kw)


def posit_encode(x, spec: PositSpec = PositSpec(16, 1), **kw):
    kw.setdefault("interpret", _interpret_default())
    return _pc.posit_encode(x, spec, **kw)


def posit_decode(bits, spec: PositSpec = PositSpec(16, 1), **kw):
    kw.setdefault("interpret", _interpret_default())
    return _pc.posit_decode(bits, spec, **kw)


def posit_quantize(x, spec: PositSpec = PositSpec(16, 1), **kw):
    kw.setdefault("interpret", _interpret_default())
    return _pc.posit_quantize(x, spec, **kw)
