"""Pallas TPU kernel: single-token GQA decode attention over a KV cache.

Motivated directly by the §Perf hillclimb (EXPERIMENTS.md): the XLA
lowering of decode attention materializes transposed copies and
convert round-trips of the cache slice per layer, and an XLA-level
blockwise scan round-trips its online-softmax accumulator through HBM.
This kernel streams KV blocks through VMEM with the (m, l, acc) state
held in VMEM scratch — one HBM read of the cache, no score
materialization: the true "flash-decode" data movement.

Grid: (B, S/blk) — batch parallel, KV blocks sequential (innermost) so
the running softmax state lives across grid steps in scratch.
q: [B, H, hd];  k,v: [B, S, kv, hd];  lengths: [B] valid cache length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import current_mesh

DEFAULT_BLOCK = 512


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, blk, kv, group, hd):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32).reshape(kv, group, hd) * hd ** -0.5
    k = k_ref[0].astype(jnp.float32)  # [blk, kv, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jnp.einsum("kgh,skh->kgs", q, k)  # [kv, group, blk]
    k_idx = si * blk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, blk), 2)
    valid = k_idx < len_ref[0]
    s = jnp.where(valid, s, -1e30)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    scale = jnp.exp(m_prev - m_new)
    l_new = l_prev * scale + jnp.sum(p, axis=-1)
    acc = acc_ref[...] * scale[..., None] + jnp.einsum("kgs,skh->kgh", p, v)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc

    @pl.when(si == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc / l_new[..., None]).reshape(kv * group, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def decode_attention(q, k, v, lengths, *, blk: int = DEFAULT_BLOCK, interpret: bool = False):
    """q: [B, H, hd]; k, v: [B, S, kv, hd]; lengths: [B] int32.

    Returns [B, H, hd].  S is padded to a block multiple (padded keys
    masked by `lengths`).
    """
    b, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    group = h // kv
    blk = min(blk, s)
    pad = (-s) % blk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = k.shape[1]
    grid = (b, sp // blk)
    return pl.pallas_call(
        functools.partial(_kernel, blk=blk, kv=kv, group=group, hd=hd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, si: (bi,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, h, hd), lambda bi, si: (bi, 0, 0)),
            pl.BlockSpec((1, blk, kv, hd), lambda bi, si: (bi, si, 0, 0)),
            pl.BlockSpec((1, blk, kv, hd), lambda bi, si: (bi, si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda bi, si: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((kv, group), jnp.float32),
            pltpu.VMEM((kv, group), jnp.float32),
            pltpu.VMEM((kv, group, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v)


def decode_attention_ref(q, k, v, lengths):
    """Pure-jnp oracle."""
    b, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    group = h // kv
    qg = q.astype(jnp.float32).reshape(b, kv, group, hd) * hd ** -0.5
    logits = jnp.einsum("bkgh,bskh->bkgs", qg, k.astype(jnp.float32))
    mask = jnp.arange(s)[None, :] < lengths[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w, v.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode attention: KV lives in a block pool, indexed per sequence
# through a block table (the continuous-batching serving layout).
# ---------------------------------------------------------------------------


def _paged_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, bs, kv, group, hd):
    """Same online-softmax state machine as `_kernel`, but each grid step's
    KV block is fetched from the pool at `bt_ref[bi, si]` (scalar-prefetched
    block table) instead of a contiguous slice."""
    bi = pl.program_id(0)
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32).reshape(kv, group, hd) * hd ** -0.5
    k = k_ref[0].astype(jnp.float32)  # [bs, kv, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jnp.einsum("kgh,skh->kgs", q, k)  # [kv, group, bs]
    k_idx = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    valid = k_idx < len_ref[bi]
    s = jnp.where(valid, s, -1e30)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    scale = jnp.exp(m_prev - m_new)
    l_new = l_prev * scale + jnp.sum(p, axis=-1)
    acc = acc_ref[...] * scale[..., None] + jnp.einsum("kgs,skh->kgh", p, v)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc

    @pl.when(si == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc / l_new[..., None]).reshape(kv * group, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_kernel(q, k_pool, v_pool, block_tables, lengths,
                                  *, interpret: bool = False):
    """Pallas paged decode attention.

    q: [B, H, hd]; k_pool, v_pool: [num_blocks, bs, kv, hd];
    block_tables: [B, max_blk] int32 pool indices (row-padded with any
    valid block id); lengths: [B] int32 valid key count per sequence.

    The block table rides the scalar-prefetch channel, so the BlockSpec
    index map dereferences it on the fly — the kernel streams exactly
    the pool blocks each sequence owns, never a contiguous copy.
    """
    b, h, hd = q.shape
    bs, kv = k_pool.shape[1], k_pool.shape[2]
    group = h // kv
    max_blk = block_tables.shape[1]
    grid = (b, max_blk)
    spec_kv = pl.BlockSpec(
        (1, bs, kv, hd), lambda bi, si, lens, bt: (bt[bi, si], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, kv=kv, group=group, hd=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h, hd), lambda bi, si, lens, bt: (bi, 0, 0)),
                spec_kv,
                spec_kv,
            ],
            out_specs=pl.BlockSpec((1, h, hd), lambda bi, si, lens, bt: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kv, group), jnp.float32),
                pltpu.VMEM((kv, group), jnp.float32),
                pltpu.VMEM((kv, group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, block_tables, q, k_pool, v_pool)


def gather_pages(pool, block_tables):
    """[num_blocks, bs, kv, hd] pool + [B, max_blk] table ->
    contiguous [B, max_blk*bs, kv, hd] per-sequence cache view."""
    b, max_blk = block_tables.shape
    bs, kv, hd = pool.shape[1:]
    pages = jnp.take(pool, block_tables.reshape(-1), axis=0)
    return pages.reshape(b, max_blk * bs, kv, hd)


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths):
    """Gather-then-attend oracle (the CPU / interpret-free path).

    Matches `attn_core`'s operation order exactly (einsum then scale,
    f32 softmax, weights cast to the value dtype) so paged decode is
    token-identical to the monolithic-cache engine under greedy decode.
    """
    b, h, hd = q.shape
    kv = k_pool.shape[2]
    group = h // kv
    k = gather_pages(k_pool, block_tables)  # [B, S, kv, hd]
    v = gather_pages(v_pool, block_tables)
    s = k.shape[1]
    qg = q.reshape(b, 1, kv, group, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    logits *= hd ** -0.5
    mask = jnp.arange(s)[None, :] < lengths[:, None]
    logits = jnp.where(mask[:, None, None, None, :], logits, jnp.float32(-1e30))
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(b, h, hd)


def paged_decode_attention_tp(q, k_pool, v_pool, block_tables, lengths, mesh,
                              *, use_kernel: bool | None = None,
                              interpret: bool = False):
    """Head-sharded paged decode attention under tensor parallelism.

    shard_map over the mesh ``model`` axis: each device runs the paged
    kernel (or the gather oracle) on its local kv-head slice of the
    pool and the matching q-head slice — no collectives, because GQA
    groups q heads contiguously by kv head, so shard i's q heads attend
    exactly shard i's kv heads.  Block tables and lengths are
    replicated scalars/rows, same values on every shard.

    Requires kv % tp == 0 (the caller falls back to the GSPMD gather
    path, with the pool sharded on positions via ``seq_tp``, otherwise).
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"

    def local(q_l, kp_l, vp_l, bt, lens):
        if use_kernel:
            return paged_decode_attention_kernel(
                q_l, kp_l, vp_l, bt, lens, interpret=interpret)
        return paged_decode_attention_ref(q_l, kp_l, vp_l, bt, lens)

    pool_spec = P(None, None, "model", None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, "model", None), pool_spec, pool_spec,
                  P(None, None), P(None)),
        out_specs=P(None, "model", None),
        check_vma=False,
    )(q, k_pool, v_pool, block_tables, lengths)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           *, use_kernel: bool | None = None,
                           interpret: bool = False):
    """Paged decode attention, auto-dispatched.

    `use_kernel=None` picks the Pallas kernel on TPU and the jnp gather
    path elsewhere (the kernel also runs anywhere under interpret=True).
    Under an active TP mesh the head-sharded shard_map path is used when
    the kv heads divide the model axis; otherwise the gather path runs
    and GSPMD partitions it over whatever axis the pool is sharded on.
    """
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        tp = mesh.shape["model"]
        h, kv = q.shape[1], k_pool.shape[2]
        if tp > 1 and kv % tp == 0 and h % tp == 0:
            return paged_decode_attention_tp(
                q, k_pool, v_pool, block_tables, lengths, mesh,
                use_kernel=use_kernel, interpret=interpret)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return paged_decode_attention_kernel(
            q, k_pool, v_pool, block_tables, lengths, interpret=interpret)
    return paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths)
