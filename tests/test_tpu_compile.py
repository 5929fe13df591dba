"""Compile guard: the main path's Pallas kernels at Yi-6B widths compile
for a TPU v5e chip that is described, not attached.

Interpret-mode tests cannot see what Mosaic refuses (primitives it does
not lower, unaligned slices, unsupported ops); these compiles can, with
no chip.  The topology is described inside a fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import paged_decode_attention_kernel
from repro.kernels.plam_matmul import plam_matmul
from repro.kernels.posit_codec import posit_decode, posit_encode, posit_quantize
from repro.numerics import PositSpec

SPEC = PositSpec(16, 1)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize(
    "m,k,n",
    [(8, 4096, 11008), (128, 4096, 4096), (64, 4096, 11008), (8, 4096, 64000),
     (8, 4096, 2752)],
)
def test_plam_matmul_compiles(one_chip, m, k, n):
    a = jax.ShapeDtypeStruct((m, k), jnp.int32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), jnp.int32, sharding=one_chip)
    text = _compiled_text(lambda x, y: plam_matmul(x, y, SPEC), a, b)
    assert "%plam_matmul" in text and "tpu_custom_call" in text
    if n % 128 == 0:  # the weight reaches the kernel as it is, unpadded
        assert " pad(" not in text


@pytest.mark.parametrize(
    "heads,kv,dtype",
    [
        (32, 4, jnp.bfloat16),  # Yi-6B on one chip
        (32, 4, jnp.float32),
        (8, 1, jnp.bfloat16),  # one shard of Yi-6B at tp=4
    ],
)
def test_paged_decode_attention_compiles(one_chip, heads, kv, dtype):
    b, hd, bs, nb, max_blk = 8, 128, 16, 64, 16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = _compiled_text(
        paged_decode_attention_kernel,
        sds((b, heads, hd), dtype),
        sds((nb, bs, kv, hd), dtype),
        sds((nb, bs, kv, hd), dtype),
        sds((b, max_blk), jnp.int32),
        sds((b,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "fn,dtype",
    [
        (posit_encode, jnp.float32),
        (posit_decode, jnp.int32),
        (posit_quantize, jnp.float32),
    ],
    ids=["encode", "decode", "quantize"],
)
def test_posit_codec_compiles(one_chip, fn, dtype):
    x = jax.ShapeDtypeStruct((4096, 11008), dtype, sharding=one_chip)
    text = _compiled_text(lambda v: fn(v, SPEC), x)
    assert "tpu_custom_call" in text


def test_serving_steps_round_bf16_as_written(one_chip):
    """The engines' model steps reach the TPU compiler without excess
    precision: a fused bf16 chain is rounded after every op, as written,
    so where it rounds does not depend on fusion or on tensor-parallel
    collectives.  Plain jit keeps the chain in f32."""
    from repro.serving.engine import _step_jit

    def chain(a):
        return (jax.nn.silu(a) * a).astype(jnp.float32)

    x = jax.ShapeDtypeStruct((8, 128), jnp.bfloat16, sharding=one_chip)
    plain = _compiled_text(chain, x)
    step = _step_jit(chain).lower(x).compile().as_text()
    assert "reduce-precision" not in plain
    assert "reduce-precision" in step
