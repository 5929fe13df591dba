"""Serving engines over the uniform ModelAPI, posit/PLAM numerics live
in every matmul.

Two engines:

* :class:`Engine` — the original static batcher: one fixed batch in,
  prefill once, decode in lockstep, everything padded to the longest
  prompt and running until the last sequence finishes.  Kept as the
  reference implementation (and for the stateful SSM/hybrid/encdec
  families, whose caches are not paged).
* :class:`ContinuousBatchingEngine` — admission-controlled request
  lifecycle over a paged KV cache: requests are admitted and retired
  every decode step, each sequence owns exactly the cache blocks it
  needs, and the jitted decode step gathers per-sequence block tables
  (`repro.models.transformer.paged_decode_step`).

The continuous engine is mesh-aware: ``PagedServeConfig.tp`` shards
model weights tensor-parallel (Megatron-style, via
``repro.parallel.sharding.param_shardings``) and the paged KV pool over
its kv-head axis (``seq_tp`` positions fallback for GQA), while the
block table, allocator and scheduler stay replicated host-side — the
control plane never notices the mesh.  ``prefill_chunk`` turns on
chunked prefill on top of either: long prompts are written in
fixed-size chunks, one per engine step, interleaved with decode, so a
long prompt bounds per-step latency instead of stalling every running
sequence behind one monolithic prefill.  ``spec_k`` turns on
speculative decoding: a drafter (``repro.serving.spec``) proposes k
tokens per slot, the target scores all k+1 positions in one batched
verify call, accepted prefixes commit and rejected tails roll back —
greedy-token-identical to plain decode, but up to k+1 tokens per step.

Both engines keep per-step wall-clock latencies in ``ServeStats`` so
benchmarks read p50/p95 from either engine through the same interface.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from functools import partial
from typing import Deque, Dict, Iterator, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ModelAPI, build
from repro.parallel.sharding import (
    make_mesh,
    paged_pool_spec,
    param_shardings,
    use_mesh,
)

from .kv_cache import BlockAllocator, SCRATCH_BLOCK, padded_prompt_len
from .observability import (
    MetricsRegistry,
    TraceRecorder,
    macs_per_token_by_mode,
    phase_annotation,
)
from .scheduler import Request, RequestState, Scheduler


# Model steps round each bf16 value where the program does.  By default
# XLA may keep a bf16 intermediate in f32 inside a fusion, so rounding
# would follow fusion and collective boundaries, and tensor parallelism
# moves those: a tp=N step could then round elsewhere than tp=1.
STEP_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


def _step_jit(fn, **kw):
    return jax.jit(fn, compiler_options=STEP_COMPILER_OPTIONS, **kw)


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 16
    temperature: float = 0.0  # 0 => greedy
    seed: int = 0
    # sync the device after every decode step so ServeStats records true
    # per-step wall latency.  Off by default: the sync costs a host
    # round-trip per token, and generate()'s plain callers should keep
    # XLA's async dispatch (benchmarks turn it on)
    time_steps: bool = False


@dataclasses.dataclass
class ServeStats:
    """Padding/utilization/latency accounting: what serve_bench reports.

    Both engines fill the same fields — ``step_latency_s`` holds one
    wall-clock entry per engine step (the static engine counts its
    prefill as step 0, then one entry per lockstep decode), so latency
    percentiles compare across engines without attribute guards.

    Since the observability layer, this class is a thin *façade*: the
    engines wire a :class:`~repro.serving.observability.MetricsRegistry`
    with live sources over these fields, and once bound (``_registry``)
    the latency quantiles are computed THROUGH the registry's
    ``serve_step_latency_seconds`` histogram — same numbers, one code
    path, and ``serve_bench`` reads the registry instead of reaching
    into fields.  Unbound instances (constructed standalone) keep the
    original list-based behavior.
    """

    steps: int = 0
    prefills: int = 0
    prefill_tokens: int = 0  # real prompt tokens
    prefill_padding: int = 0  # bucket/chunk padding on top of them
    decode_steps: int = 0
    active_slot_steps: int = 0  # slot-steps doing useful decode work
    idle_slot_steps: int = 0  # slot-steps wasted (empty slot, step ran)
    generated_tokens: int = 0
    # speculative decoding: per-verify-step draft/accept accounting
    spec_steps: int = 0  # batched verify steps run
    drafted_tokens: int = 0  # k drafts per active slot per verify step
    accepted_tokens: int = 0  # drafts the target model agreed with
    spec_committed_tokens: int = 0  # tokens committed via verify steps
    step_latency_s: List[float] = dataclasses.field(default_factory=list)
    # preemption / deadline accounting (preemption="recompute")
    preemptions: int = 0  # running sequences evicted under pool pressure
    resumes: int = 0  # preempted sequences re-admitted (recompute-resume)
    deadline_cancelled: int = 0  # requests cancelled at deadline expiry
    resume_latency_s: List[float] = dataclasses.field(default_factory=list)
    resume_latency_steps: List[int] = dataclasses.field(default_factory=list)
    # observability binding (engine-managed): once set, quantiles are
    # computed from the registry's step-latency histogram, whose live
    # source is this object's own step_latency_s — one source of truth
    _registry: Optional[MetricsRegistry] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def padding_waste(self) -> float:
        """Fraction of engine capacity spent on padding/idle slots."""
        spent = (
            self.prefill_tokens
            + self.prefill_padding
            + self.active_slot_steps
            + self.idle_slot_steps
        )
        wasted = self.prefill_padding + self.idle_slot_steps
        return wasted / spent if spent else 0.0

    def record_step(self, seconds: float) -> None:
        self.step_latency_s.append(seconds)

    def latency_quantile(self, q: float) -> float:
        if self._registry is not None:
            return self._registry.histogram("serve_step_latency_seconds").quantile(q)
        if not self.step_latency_s:
            return 0.0
        return float(np.quantile(np.asarray(self.step_latency_s), q))

    def latency_p50(self) -> float:
        return self.latency_quantile(0.50)

    def latency_p95(self) -> float:
        return self.latency_quantile(0.95)

    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the target model accepted."""
        if not self.drafted_tokens:
            return 0.0
        return self.accepted_tokens / self.drafted_tokens

    def resume_latency_mean_s(self) -> float:
        """Mean wall seconds a preempted request spent parked before
        its recompute-resume was admitted."""
        if not self.resume_latency_s:
            return 0.0
        return float(np.mean(np.asarray(self.resume_latency_s)))

    def tokens_per_verify_step(self) -> float:
        """Mean committed tokens per verify step per active slot — the
        speculative speedup over one-token-per-step decode (1.0 = no
        speedup, k+1 = every draft accepted)."""
        return (
            self.spec_committed_tokens / self.active_slot_steps
            if self.spec_steps and self.active_slot_steps
            else 0.0
        )


class Engine:
    """Minimal batched inference engine (static batching).

    `generate` runs one jitted prefill followed by a jitted
    lax.while-free python decode loop (each step is one jitted call —
    the deployment pattern when steps stream back to clients).
    """

    def __init__(self, cfg: ModelConfig, params=None, key=None, prequantize=False):
        self.cfg = cfg
        self.api: ModelAPI = build(cfg)
        self.params = (
            params
            if params is not None
            else self.api.init(key if key is not None else jax.random.PRNGKey(0))
        )
        self.prequant_meta = {}
        if prequantize:
            from repro.core.prequant import quantize_params

            self.params, self.prequant_meta = quantize_params(cfg, self.params)
        self._prefill = _step_jit(self.api.prefill)
        self._decode = _step_jit(self.api.decode_step)
        self._enc_cache = None  # encdec: encoder output, fixed per generate()
        self.stats = ServeStats()
        # same registry surface as the continuous engine (sourced subset:
        # the static batcher has no pool / scheduler / drafter to sample)
        self.metrics = MetricsRegistry()
        for mname, field in (
            ("serve_steps_total", "steps"),
            ("serve_prefills_total", "prefills"),
            ("serve_prefill_tokens_total", "prefill_tokens"),
            ("serve_decode_steps_total", "decode_steps"),
            ("serve_generated_tokens_total", "generated_tokens"),
        ):
            self.metrics.counter(mname).set_source(
                lambda field=field: getattr(self.stats, field)
            )
        self.metrics.histogram("serve_step_latency_seconds").set_source(
            lambda: self.stats.step_latency_s
        )
        self.stats._registry = self.metrics

    def generate(self, prompt_batch: dict, scfg: ServeConfig = ServeConfig()):
        """prompt_batch: family-appropriate prefill inputs (see
        ModelAPI.prefill_inputs).  Returns [B, max_new_tokens] tokens.

        ``self.stats`` is reset per call and filled with the same
        counters the continuous engine keeps: step 0 is the whole
        prefill (+ first sampled token), every later step one lockstep
        decode over the full batch.  Per-step wall latencies are only
        recorded under ``scfg.time_steps`` (they require a device sync
        per step, which would break async dispatch for normal callers).
        """
        self.stats = ServeStats()
        self.stats._registry = self.metrics
        self._enc_cache = None  # recomputed per generate (frames differ)
        t0 = time.perf_counter()
        logits, caches = self._prefill(self.params, prompt_batch)
        b = logits.shape[0]
        if "tokens" in prompt_batch:
            pos0 = prompt_batch["tokens"].shape[1]
            if "embeds_prefix" in prompt_batch:
                # vlm: patch embeddings occupy the cache prefix, so the
                # first decode write/position comes after patches+tokens
                pos0 += prompt_batch["embeds_prefix"].shape[1]
        else:
            pos0 = 0
        caches = self._grow_caches(caches, scfg.max_new_tokens)
        key = jax.random.PRNGKey(scfg.seed)
        out = []
        tok = self._pick(logits[:, -1, :], scfg, key)
        if scfg.time_steps:
            jax.block_until_ready(tok)
            self.stats.record_step(time.perf_counter() - t0)
        out.append(tok)
        self.stats.steps += 1
        self.stats.prefills += 1
        self.stats.prefill_tokens += b * pos0
        self.stats.generated_tokens += b
        for i in range(scfg.max_new_tokens - 1):
            t0 = time.perf_counter()
            batch = {"token": tok[:, None], "cache_len": jnp.int32(pos0 + i)}
            batch.update(self._cache_kw(caches, prompt_batch))
            logits, caches = self._decode(self.params, batch)
            key = jax.random.fold_in(key, i)
            tok = self._pick(logits[:, -1, :], scfg, key)
            if scfg.time_steps:
                jax.block_until_ready(tok)
                self.stats.record_step(time.perf_counter() - t0)
            out.append(tok)
            self.stats.steps += 1
            self.stats.decode_steps += 1
            self.stats.active_slot_steps += b
            self.stats.generated_tokens += b
        return jnp.stack(out, axis=1)

    def _grow_caches(self, caches, max_new_tokens: int):
        """Prefill allocates caches sized to the prompt; decode then
        writes at positions prompt_len..prompt_len+max_new-2, which a
        prompt-sized cache would clamp onto its last slot (silently
        overwriting the final prompt entry).  Pad the seq axis up front
        so every decode write lands in a real slot."""
        if (
            self.cfg.family not in ("dense", "moe", "vlm", "encdec")
            or max_new_tokens <= 1
        ):
            return caches
        pad = ((0, 0), (0, 0), (0, max_new_tokens - 1), (0, 0), (0, 0))
        ck, cv = caches
        return jnp.pad(ck, pad), jnp.pad(cv, pad)

    def _cache_kw(self, caches, prompt_batch):
        fam = self.cfg.family
        if fam in ("dense", "moe", "vlm"):
            return {"kv_caches": caches}
        if fam in ("ssm", "hybrid"):
            return {"caches": caches}
        if fam == "encdec":
            # the encoder output is fixed for the whole generation but
            # api.prefill does not return it — recompute it once from
            # the prompt frames and reuse it for every decode step
            if self._enc_cache is None:
                from repro.models import encdec  # lazy to avoid cycle

                self._enc_cache = _step_jit(partial(encdec.encode, self.cfg))(
                    self.params, prompt_batch["frames"]
                )
            return {"kv_caches": caches, "enc_out": self._enc_cache}
        raise ValueError(fam)

    def _pick(self, logits, scfg: ServeConfig, key):
        if scfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / scfg.temperature).astype(jnp.int32)


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PagedServeConfig:
    """Static capacity of a continuous-batching engine instance.

    block_size: cache positions per KV block.
    num_blocks: pool size (block 0 is reserved scratch, so
        num_blocks - 1 are allocatable).
    max_slots: max sequences decoded per step (the jitted batch width).
    max_seq_len: per-sequence prompt + generated cap; fixes the block
        table width to ceil(max_seq_len / block_size).
    tp: tensor-parallel ways.  >1 builds a (data=1, model=tp) mesh over
        the first tp local devices, shards parameters Megatron-style and
        the KV pool per ``repro.parallel.sharding.paged_pool_spec``; on
        CPU force devices first with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    prefill_chunk: 0 = whole-prompt prefill (one bucket-padded call per
        request).  >0 = chunked prefill: prompts are written
        ``prefill_chunk`` tokens per engine step, interleaved with
        decode.  Must be a multiple of block_size so chunk starts stay
        block-aligned inside the sequence's allocation.
    """

    block_size: int = 16
    num_blocks: int = 128
    max_slots: int = 4
    max_seq_len: int = 256
    temperature: float = 0.0
    seed: int = 0
    cache_dtype: str = "bfloat16"
    use_kernel: Optional[bool] = None  # None = auto (Pallas on TPU)
    tp: int = 1
    prefill_chunk: int = 0
    # encode policy-selected weights to posit patterns once at engine
    # construction (core.prequant.quantize_params); plam_sim sites then
    # serve through kernels.ops.plam_dense with int16 weight storage
    prequantize: bool = False
    # speculative decoding: 0 = off; k > 0 drafts k tokens per active
    # slot per step and verifies all k+1 positions in one batched call
    # (requires greedy sampling — acceptance is exact argmax agreement,
    # so the committed stream is token-identical to spec_k=0).
    # Admission reserves blocks for the worst-case k-token burst and
    # rejected tails are rolled back (stale K/V scrubbed at retirement).
    spec_k: int = 0
    # drafter: "ngram" / "ngram:N" (self-speculative context lookup),
    # "model:<arch>" (registry draft model sharing the tokenizer), or a
    # Drafter instance (repro.serving.spec)
    spec_draft: object = "ngram"
    # preemptive scheduling under KV pressure.  "off" = PR 1-4
    # behavior: admission reserves whole-lifetime blocks, FCFS, no
    # eviction.  "recompute" = admission allocates only the prefill
    # context, sequences grow on demand, and under pool pressure the
    # least deserving running request (lowest Request.priority, then
    # latest arrival) is preempted — all its written blocks scrubbed —
    # and later resumed by recomputing its committed tokens through the
    # chunked-prefill path; resumed streams are greedy-token-identical
    # to uninterrupted runs.
    preemption: str = "off"
    # content-addressed prefix caching over the paged pool (opt-in).
    # Admission walks the prompt's full blocks through a chain-hash map
    # (hash(parent_hash, block_tokens)) kept by the BlockAllocator,
    # reuses every leading hit (refcount++) and prefills only the miss
    # suffix through the chunked-prefill path; freed registered blocks
    # park on an LRU and are evicted (scrubbed, then freed) only under
    # pool pressure.  Greedy streams are token-identical with the cache
    # on or off — K/V at a position is a deterministic function of the
    # token prefix, which is exactly what the chain hash keys.
    prefix_cache: bool = False
    # injectable wall clock (monotonic seconds) for deadline expiry and
    # resume-latency stats; None = time.monotonic.  Tests inject a fake
    # clock to drive Request.deadline_s deterministically.
    clock: Optional[object] = None
    # observability (see repro.serving.observability).  trace=True (the
    # default) records one typed TraceEvent per request lifecycle
    # transition — host-side appends, well under 5% of a step's cost —
    # exportable as JSON-lines / Chrome trace and the source of the
    # per-request latency breakdown.  profile=True additionally wraps
    # each engine phase in a jax.profiler TraceAnnotation so phases
    # show as named spans in a profiler capture (off by default: it is
    # only meaningful inside jax.profiler.trace()).
    trace: bool = True
    profile: bool = False


class ContinuousBatchingEngine:
    """Admission-controlled serving over a paged KV cache.

    Each `step()`:
      1. admits waiting requests FCFS while a slot + blocks are free —
         whole-prompt prefill immediately, or queued for chunked
         prefill when ``prefill_chunk`` is set;
      2. feeds at most ONE prompt chunk (head-of-line) when chunking;
      3. runs ONE jitted batched decode step over all fully-prefilled
         slots, gathering per-sequence block tables and lengths — or,
         under ``spec_k``, ONE batched k+1-position verify step that
         commits each slot's accepted draft prefix plus the target's
         correction token and rolls back the rejected tail;
      4. retires finished sequences, returning blocks to the free list
         (stale never-committed K/V scrubbed first).

    Supported families: dense / moe (attention KV caches).  SSM, hybrid
    and enc-dec keep the static :class:`Engine` — their caches are
    O(1)-state or encoder-tied, so paging buys nothing.

    Under ``tp > 1`` every jitted call runs inside the engine's mesh:
    parameters and KV pool are device_put with their shardings once at
    construction, activations follow the model's ``constrain`` rules,
    and decode attention dispatches to the head-sharded shard_map path
    (`repro.kernels.decode_attention.paged_decode_attention_tp`) when
    kv heads divide tp.  Host-side state (block tables, lengths, last
    tokens, the scheduler) is identical to the single-device engine.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params=None,
        key=None,
        pcfg: PagedServeConfig = PagedServeConfig(),
    ):
        self.cfg = cfg
        self.pcfg = pcfg
        self.api: ModelAPI = build(cfg)
        if self.api.paged_decode_step is None:
            raise ValueError(
                f"family {cfg.family!r} has no paged KV layout; use Engine"
            )
        if cfg.attn_logit_softcap is not None:
            raise ValueError("paged decode does not support logit softcap")
        if pcfg.prefill_chunk and pcfg.prefill_chunk % pcfg.block_size:
            raise ValueError(
                f"prefill_chunk={pcfg.prefill_chunk} must be a multiple of "
                f"block_size={pcfg.block_size}"
            )
        if pcfg.prefill_chunk and self.api.paged_prefill_chunk is None:
            raise ValueError(f"family {cfg.family!r} has no chunked prefill path")
        if pcfg.prefix_cache and self.api.paged_prefill_chunk is None:
            raise ValueError(
                f"family {cfg.family!r} has no chunked prefill path; "
                "prefix caching needs it to prefill the cache-miss suffix"
            )
        if pcfg.spec_k:
            if pcfg.temperature > 0:
                raise ValueError(
                    "speculative decoding requires greedy sampling "
                    "(temperature=0): acceptance is exact argmax agreement"
                )
            if self.api.paged_score_tokens is None:
                raise ValueError(
                    f"family {cfg.family!r} has no multi-token scoring path"
                )

        self._mesh = None
        if pcfg.tp > 1:
            ndev = len(jax.devices())
            if ndev < pcfg.tp:
                raise ValueError(
                    f"tp={pcfg.tp} needs at least {pcfg.tp} devices, found {ndev}; "
                    "on CPU force more with "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N"
                )
            self._mesh = make_mesh(
                (1, pcfg.tp), ("data", "model"), devices=jax.devices()[: pcfg.tp]
            )

        self.params, self.prequant_meta = self._init_params(params, key)

        bs, nb = pcfg.block_size, pcfg.num_blocks
        # the block table is wide enough for the worst-case speculative
        # burst: a verify step may write spec_k positions past the
        # committed tail before acceptance is known, and those writes
        # must land in the sequence's own (reserved) blocks — never be
        # clamped back onto committed positions by dynamic_update_slice
        self.max_blocks_per_seq = -(-(pcfg.max_seq_len + pcfg.spec_k) // bs)
        pool_init = partial(
            self.api.paged_pool_init, nb, bs, jnp.dtype(pcfg.cache_dtype)
        )
        pool_sharding = None
        if self._mesh is not None:
            pool_shape = jax.eval_shape(pool_init)[0].shape
            pool_sharding = paged_pool_spec(self._mesh, pool_shape)
            pool_sharding = (pool_sharding, pool_sharding)
        self._k_pool, self._v_pool = jax.jit(pool_init, out_shardings=pool_sharding)()
        self.allocator = BlockAllocator(nb, bs, prefix_cache=pcfg.prefix_cache)
        self._clock = pcfg.clock if pcfg.clock is not None else time.monotonic
        self.scheduler = Scheduler(
            self.allocator,
            pcfg.max_slots,
            pcfg.max_seq_len,
            spec_k=pcfg.spec_k,
            preemption=pcfg.preemption,
            clock=self._clock,
        )

        donate = (2, 3) if jax.default_backend() != "cpu" else ()
        self._prefill = _step_jit(self.api.paged_prefill, donate_argnums=donate)
        self._prefill_chunk = (
            _step_jit(self.api.paged_prefill_chunk, donate_argnums=donate)
            if self.api.paged_prefill_chunk is not None
            else None
        )
        self._decode = _step_jit(
            partial(self.api.paged_decode_step, use_kernel=pcfg.use_kernel),
            donate_argnums=donate,
        )
        self.drafter = None
        self._score = None
        if pcfg.spec_k:
            from .spec import make_drafter

            self.drafter = (
                make_drafter(
                    pcfg.spec_draft, cfg, key=jax.random.PRNGKey(pcfg.seed)
                )
                if isinstance(pcfg.spec_draft, str)
                else pcfg.spec_draft
            )
            self._score = _step_jit(self.api.paged_score_tokens, donate_argnums=donate)
        # zero freed blocks that still hold written-but-never-committed
        # K/V (rolled-back draft tails, prefill padding) before the
        # allocator can hand them to another sequence; the id row is
        # padded with the scratch block so every scrub shares one
        # compile (re-zeroing scratch is harmless)
        scrub_donate = (0, 1) if jax.default_backend() != "cpu" else ()
        self._scrub_fn = jax.jit(
            lambda kp, vp, ids: (kp.at[:, ids].set(0), vp.at[:, ids].set(0)),
            donate_argnums=scrub_donate,
        )
        # blocks freed this step but not yet zeroed: scrubs coalesce
        # into one padded scatter per flush (see _flush_scrubs) instead
        # of one dispatch per retire/preempt/evict event
        self._scrub_pending: List[int] = []
        # copy-on-write: duplicate one pool block (all layers) into a
        # private block before a sequence writes into a shared tail
        self._cow_fn = jax.jit(
            lambda kp, vp, src, dst: (
                kp.at[:, dst].set(kp[:, src]),
                vp.at[:, dst].set(vp[:, src]),
            ),
            donate_argnums=scrub_donate,
        )

        m = pcfg.max_slots
        self._tables = np.full((m, self.max_blocks_per_seq), SCRATCH_BLOCK, np.int32)
        self._lengths = np.zeros((m,), np.int32)
        self._last_tok = np.zeros((m,), np.int32)
        self._prefilling: Deque[Request] = deque()
        self._step_no = 0
        self._next_rid = 0
        self.stats = ServeStats()
        # observability: trace recorder (on by default — cheap host-side
        # appends), metrics registry wired with live sources, opt-in
        # profiler annotations
        self._profile = pcfg.profile
        self.trace: Optional[TraceRecorder] = (
            TraceRecorder(
                clock=self._clock,
                occupancy=lambda: (self.allocator.num_free, self.allocator.num_used),
            )
            if pcfg.trace
            else None
        )
        self.metrics = MetricsRegistry()
        self._wire_metrics()
        self.stats._registry = self.metrics

    def _init_params(self, params, key):
        """Return ``(params, prequant_meta)`` placed in their shardings.

        Without ``params``, weights are initialised from ``key`` — and
        prequantized, under ``prequantize`` — in ONE jitted program whose
        outputs are the final shardings: no float copy of a quantized
        weight outlives its encode, and under ``tp > 1`` every device
        builds only its own shard (nothing is staged on device 0).
        """
        from repro.core.prequant import quantize_params

        meta = {}

        def prepare(p):
            if not self.pcfg.prequantize:
                return p
            p, m = quantize_params(self.cfg, p)
            meta.update(m)
            return p

        def shardings(tree):
            if self._mesh is None:
                return None
            kernel = {p for p, m in meta.items() if m["mode"] == "plam_sim"}
            return param_shardings(self._mesh, tree, kernel_weights=kernel)

        if params is None:
            key = key if key is not None else jax.random.PRNGKey(0)

            def build_params(k):
                return prepare(self.api.init(k))

            out = shardings(jax.eval_shape(build_params, key))
            return jax.jit(build_params, out_shardings=out)(key), meta
        params = prepare(params)
        if self._mesh is not None:
            params = jax.device_put(params, shardings(params))
        return params, meta

    @property
    def current_step(self) -> int:
        """Engine step counter (arrival_step values are absolute)."""
        return self._step_no

    def _wire_metrics(self) -> None:
        """Register every serving metric with a live *source* over
        engine state — collection reads current values on demand, so
        the hot path pays nothing and a benchmark-style
        ``eng.stats = ServeStats()`` reset is reflected automatically.
        Per-numerics-mode MAC counters resolve each matmul site through
        ``repro.core.policy`` (PLAM's savings as a serving metric)."""
        m = self.metrics
        counters = {
            "serve_steps_total": ("engine steps run", lambda: self.stats.steps),
            "serve_prefills_total": (
                "prefill calls (whole-prompt or chunk)",
                lambda: self.stats.prefills,
            ),
            "serve_prefill_tokens_total": (
                "real prompt tokens written",
                lambda: self.stats.prefill_tokens,
            ),
            "serve_prefill_padding_total": (
                "bucket/chunk padding tokens",
                lambda: self.stats.prefill_padding,
            ),
            "serve_decode_steps_total": (
                "batched decode/verify steps",
                lambda: self.stats.decode_steps,
            ),
            "serve_generated_tokens_total": (
                "committed output tokens",
                lambda: self.stats.generated_tokens,
            ),
            "serve_drafted_tokens_total": (
                "speculative tokens drafted",
                lambda: self.stats.drafted_tokens,
            ),
            "serve_accepted_tokens_total": (
                "speculative tokens accepted",
                lambda: self.stats.accepted_tokens,
            ),
            "serve_preemptions_total": (
                "running sequences evicted",
                lambda: self.stats.preemptions,
            ),
            "serve_resumes_total": (
                "recompute-resume re-admissions",
                lambda: self.stats.resumes,
            ),
            "serve_deadline_cancelled_total": (
                "requests cancelled at deadline",
                lambda: self.stats.deadline_cancelled,
            ),
            "serve_prefix_cache_hits_total": (
                "prefix-cache block hits at admission",
                lambda: self.allocator.hits,
            ),
            "serve_prefix_cache_misses_total": (
                "prefix-cache block misses at admission",
                lambda: self.allocator.misses,
            ),
            "serve_prefix_cache_evictions_total": (
                "idle cached blocks reclaimed under pool pressure",
                lambda: self.allocator.evictions,
            ),
            "serve_prefill_tokens_saved_total": (
                "prompt tokens skipped via prefix-cache hits",
                lambda: self.allocator.tokens_saved,
            ),
            "serve_prefix_cache_cow_total": (
                "copy-on-write block duplications",
                lambda: self.allocator.cow_copies,
            ),
        }
        for name, (help_, src) in counters.items():
            m.counter(name, help_).set_source(src)
        gauges = {
            "serve_pool_blocks_free": (
                "KV pool blocks on the free list",
                lambda: self.allocator.num_free,
            ),
            "serve_pool_blocks_used": (
                "KV pool blocks owned by live sequences",
                lambda: self.allocator.num_used,
            ),
            "serve_pool_utilization": (
                "fraction of allocatable KV pool in use",
                self.allocator.utilization,
            ),
            "serve_prefix_cached_blocks": (
                "pool blocks holding registered prefix-cache content",
                lambda: self.allocator.num_cached,
            ),
            "serve_waiting_requests": (
                "submitted, not yet admitted",
                lambda: self.scheduler.num_waiting,
            ),
            "serve_preempted_requests": (
                "parked awaiting recompute-resume",
                lambda: self.scheduler.num_preempted,
            ),
            "serve_running_requests": (
                "admitted sequences holding a slot",
                lambda: self.scheduler.num_running,
            ),
            "serve_padding_waste": (
                "capacity fraction lost to padding/idle slots",
                lambda: self.stats.padding_waste(),
            ),
            "serve_spec_acceptance_rate": (
                "fraction of drafts the target accepted",
                lambda: self.stats.acceptance_rate(),
            ),
            "serve_tokens_per_verify_step": (
                "committed tokens per verify step per slot",
                lambda: self.stats.tokens_per_verify_step(),
            ),
            "serve_tok_per_s": (
                "generated tokens over summed step wall time",
                lambda: (
                    self.stats.generated_tokens / t
                    if (t := sum(self.stats.step_latency_s))
                    else 0.0
                ),
            ),
        }
        for name, (help_, src) in gauges.items():
            m.gauge(name, help_).set_source(src)
        m.histogram(
            "serve_step_latency_seconds", "wall seconds per engine step"
        ).set_source(lambda: self.stats.step_latency_s)
        try:
            by_mode = macs_per_token_by_mode(self.cfg)
        except Exception:  # exotic family/policy: MAC attribution is best-effort
            by_mode = {}
        for mode, macs in sorted(by_mode.items()):
            m.counter(
                "serve_macs_total",
                "forward-pass MACs by resolved numerics mode",
                mode=mode,
            ).set_source(
                lambda macs=macs: macs
                * (self.stats.prefill_tokens + self.stats.generated_tokens)
            )
        if self.drafter is not None:
            m.counter(
                "serve_draft_proposals_total", "drafter propose() calls"
            ).set_source(lambda: getattr(self.drafter, "proposals", 0))
            m.counter(
                "serve_draft_proposed_tokens_total", "tokens proposed by drafter"
            ).set_source(lambda: getattr(self.drafter, "proposed_tokens", 0))

    def _emit(self, etype: str, rid: int, **payload) -> None:
        """Trace hook: record one typed event (no-op when tracing off)."""
        if self.trace is not None:
            self.trace.emit(etype, rid, self._step_no, **payload)

    def _mesh_ctx(self):
        """Context manager activating the engine's mesh (no-op at tp=1)."""
        if self._mesh is None:
            return contextlib.nullcontext()
        return use_mesh(self._mesh)

    # -- request intake ----------------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: int = 16,
        arrival_step: int = 0,
        stop_token: Optional[int] = None,
        priority: int = 0,
        deadline_s: Optional[float] = None,
    ) -> "SubmitHandle":
        """Queue a request; returns a :class:`~repro.serving.api.
        SubmitHandle` exposing ``.result()`` / ``.cancel()`` /
        ``.trace()`` and delegating every ``Request`` attribute, so
        pre-redesign callers keep working unchanged.  Requests must
        be submitted in non-decreasing arrival_step order.  ``priority``
        orders admission and preemption immunity under
        ``preemption="recompute"`` (larger wins; FCFS ignores it);
        ``deadline_s`` is a wall-clock budget from now — an expired
        request is cancelled wherever it is, keeping any output already
        committed."""
        from .api import SubmitHandle  # local: api imports this module

        req = Request(
            rid=self._next_rid,
            prompt=[int(t) for t in prompt],
            max_new_tokens=max_new_tokens,
            arrival_step=arrival_step,
            stop_token=stop_token,
            priority=priority,
            deadline_s=deadline_s,
            submit_time=self._clock(),
        )
        self._next_rid += 1
        self.scheduler.submit(req)
        self._emit(
            "SUBMIT",
            req.rid,
            prompt_len=req.prompt_len,
            max_new=req.max_new_tokens,
            priority=req.priority,
            arrival_step=req.arrival_step,
        )
        return SubmitHandle(self, req)

    def cancel(self, req) -> None:
        """Client-side abort: cancel ``req`` (a ``Request`` or a
        ``SubmitHandle``) wherever it is (waiting, running, preempted),
        keeping its committed output.  No-op for already-finished/
        cancelled requests."""
        req = getattr(req, "request", req)
        if req.state in (RequestState.FINISHED, RequestState.CANCELLED):
            return
        self._cancel(req, self._step_no)
        self._emit("CANCEL", req.rid, reason="client", out_len=len(req.output))

    # -- engine loop -------------------------------------------------------

    def step(self) -> List[Request]:
        """One engine iteration; returns requests finished this step.

        With chunked prefill, at most one prompt chunk (head-of-line
        FCFS) is processed before the decode step for every
        fully-prefilled sequence — per-step latency stays bounded by
        one chunk + one decode instead of one whole prompt.
        """
        t0 = time.perf_counter()
        step = self._step_no
        finished: List[Request] = []

        # deadline sweep: expired requests are cancelled wherever they
        # live (waiting / running / preempted), keeping committed output
        for req in self.scheduler.expired(self._clock()):
            self._cancel(req, step)
            self.stats.deadline_cancelled += 1
            self._emit(
                "DEADLINE",
                req.rid,
                deadline_s=req.deadline_s,
                out_len=len(req.output),
            )
            finished.append(req)

        for req in self.scheduler.admit(step, on_preempt=self._on_preempt):
            if req.preempted_step >= 0:  # recompute-resume re-admission
                self.stats.resumes += 1
                self.stats.resume_latency_steps.append(step - req.preempted_step)
                self.stats.resume_latency_s.append(
                    self._clock() - req.preempted_time
                )
                self._emit(
                    "RESUME",
                    req.rid,
                    slot=req.slot,
                    blocks=len(req.alloc.blocks),
                    parked_steps=step - req.preempted_step,
                    cached_len=req.cached_len,
                )
                req.preempted_step = -1
            else:
                self._emit(
                    "ADMIT",
                    req.rid,
                    slot=req.slot,
                    blocks=len(req.alloc.blocks),
                    cached_len=req.cached_len,
                )
            if self.pcfg.prefill_chunk:
                # blocks + slot reserved; the prompt is fed chunkwise
                # (the slot stays scratch-masked until prefill is done)
                self._prefilling.append(req)
            else:
                self._do_prefill(req)
                if req.is_done():  # max_new_tokens == 1: done at prefill
                    self._release(req, step)
                    finished.append(req)

        if self._prefilling:
            req = self._prefilling[0]
            if self._do_prefill_chunk(req):
                self._prefilling.popleft()
                if req.is_done():  # max_new_tokens == 1 / stop at first token
                    self._release(req, step)
                    finished.append(req)

        if self.pcfg.preemption == "recompute":
            self._grow_active(step)

        if any(r.prefill_done for r in self.scheduler.running.values()):
            if self.pcfg.spec_k:
                finished.extend(self._do_verify(step))
            else:
                finished.extend(self._do_decode(step))

        # drain any scrub work this step produced after its last
        # compute call (retires, cancels, deadline sweeps on an
        # otherwise-idle step) so freed blocks never stay dirty across
        # a step boundary
        self._flush_scrubs()

        self.stats.steps += 1
        self._step_no += 1
        self.stats.record_step(time.perf_counter() - t0)
        # benchmarks reset counters with `eng.stats = ServeStats()`; the
        # registry's source callables read `self.stats.<field>` live, so
        # the swap is already reflected — only the façade's back-pointer
        # needs refreshing for latency_quantile() to keep routing here.
        if self.stats._registry is not self.metrics:
            self.stats._registry = self.metrics
        self.metrics.tick(self._step_no)
        return finished

    def run(self) -> Dict[int, List[int]]:
        """Drive step() until every submitted request has finished.
        Returns {rid: generated tokens}."""
        done: Dict[int, List[int]] = {}
        while self.scheduler.has_work():
            for req in self.step():
                done[req.rid] = req.output
        return done

    def stream(self, prompt: List[int], **submit_kw) -> Iterator[dict]:
        """Submit one prompt and drive the engine, yielding incremental
        progress as dicts: ``{"tokens": [...]}`` for tokens committed
        since the previous yield, interleaved (in emission order) with
        ``{"event": TraceEvent}`` for this request's trace events when
        tracing is on.  Other queued requests keep making progress —
        stream() drives the shared ``step()`` loop, it does not pin the
        engine to one request.  Terminates after the request's terminal
        event (FINISH / CANCEL / DEADLINE)."""
        handle = self.submit(prompt, **submit_kw)
        req = handle.request
        n_tok = 0
        n_evt = 0
        if self.trace is not None:
            for ev in self.trace.request_events(req.rid)[n_evt:]:
                n_evt += 1
                yield {"event": ev}
        while req.state not in (RequestState.FINISHED, RequestState.CANCELLED):
            self.step()
            if self.trace is not None:
                for ev in self.trace.request_events(req.rid)[n_evt:]:
                    n_evt += 1
                    yield {"event": ev}
            if len(req.output) > n_tok:
                new = req.output[n_tok:]
                n_tok = len(req.output)
                yield {"tokens": new}

    # -- internals ---------------------------------------------------------

    def _do_prefill(self, req: Request) -> None:
        """Whole-context prefill: the prompt for a fresh request, or —
        on a recompute-resume — the frozen committed context.  A resume
        routes through the chunked-prefill gather->attend->scatter path
        (one whole-width chunk) when the family has one: it is pinned
        bit-identical to monolithic prefill and shares its compiles
        with chunked serving.  A prefix-cache hit (``prefill_pos > 0``
        set by admission) takes the same route: only the miss suffix is
        written, over the shared blocks as attended context."""
        if (
            req.resume_ctx is not None or req.prefill_pos > 0
        ) and self._prefill_chunk is not None:
            self._resume_via_chunk(req)
            return
        self._flush_scrubs()
        bs = self.pcfg.block_size
        plen = req.prefill_len
        s_pad = padded_prompt_len(plen, bs)
        toks = np.zeros((1, s_pad), np.int32)
        toks[0, :plen] = req.prefill_tokens
        block_ids = jnp.asarray(req.alloc.blocks[: s_pad // bs], jnp.int32)
        with self._mesh_ctx(), phase_annotation("serve.prefill", self._profile):
            logits, (self._k_pool, self._v_pool) = self._prefill(
                self.params,
                jnp.asarray(toks),
                self._k_pool,
                self._v_pool,
                block_ids,
                jnp.int32(plen),
            )
        req.prefill_pos = plen
        req.verified_len = plen
        req.drafted_len = s_pad  # pad positions hold junk K/V until overwritten
        self._finish_prefill(req, logits[0, -1])
        self.stats.prefills += 1
        self.stats.prefill_tokens += plen
        self.stats.prefill_padding += s_pad - plen
        self._emit(
            "PREFILL_CHUNK",
            req.rid,
            start=0,
            tokens=plen,
            width=s_pad,
            done=True,
            out_len=len(req.output),
        )

    def _resume_via_chunk(self, req: Request) -> None:
        """Recompute-resume: rewrite the K/V of the committed context
        into freshly-allocated blocks with ONE padded
        ``paged_prefill_chunk`` call.  The logits are discarded — the
        next token after the context is the already-committed last
        output token, re-fed by the normal decode step — so resume only
        has to reproduce the K/V, which the chunk path does
        bit-identically to an uninterrupted run.

        With prefix caching the same path prefills only the cache-MISS
        suffix: ``prefill_pos`` starts at the cached boundary (set by
        admission), the chunk's ``cache_len`` is that boundary, and the
        hit blocks are attended over exactly as committed context is on
        a resume.  ``start == 0`` reproduces the historical resume call
        bit-for-bit."""
        if req.cow_src is not None:
            self._apply_cow(req)
        self._flush_scrubs()
        bs = self.pcfg.block_size
        plen = req.prefill_len
        start = req.prefill_pos
        remaining = plen - start
        width = padded_prompt_len(remaining, bs)
        toks = np.zeros((1, width), np.int32)
        toks[0, :remaining] = req.prefill_tokens[start:]
        table_row = jnp.asarray(
            req.alloc.table_row(self.max_blocks_per_seq), jnp.int32
        )
        with self._mesh_ctx(), phase_annotation("serve.prefill", self._profile):
            logits, (self._k_pool, self._v_pool) = self._prefill_chunk(
                self.params,
                jnp.asarray(toks),
                self._k_pool,
                self._v_pool,
                table_row,
                jnp.int32(start),
                jnp.int32(remaining - 1),
            )
        req.prefill_pos = plen
        req.verified_len = plen
        # suffix padding past capacity lands on the scratch block via
        # the padded table row; only in-capacity positions can be dirty
        req.drafted_len = max(
            req.drafted_len, min(start + width, req.alloc.capacity())
        )
        self._finish_prefill(req, logits[0, -1])
        self.stats.prefills += 1
        self.stats.prefill_tokens += remaining
        self.stats.prefill_padding += width - remaining
        self._emit(
            "PREFILL_CHUNK",
            req.rid,
            start=start,
            tokens=remaining,
            width=width,
            done=True,
            out_len=len(req.output),
        )

    def _finish_prefill(self, req: Request, last_logits) -> None:
        """Activate a fully-prefilled slot.  Fresh requests sample
        their first token from the prefill logits; a resumed request
        already committed that continuation — its last output token is
        re-fed as the next decode input instead (sampling again would
        double-emit it)."""
        if req.output:
            tok = req.output[-1]
        else:
            tok = int(self._pick_one(last_logits, req, len(req.output)))
            req.output.append(tok)
            self.stats.generated_tokens += 1
        slot = req.slot
        self._tables[slot] = req.alloc.table_row(self.max_blocks_per_seq)
        self._lengths[slot] = req.prefill_len
        self._last_tok[slot] = tok
        if self.allocator.prefix_cache:
            # publish only now that the K/V is really in the pool — a
            # hash->block mapping must never race ahead of pool content
            self.allocator.register(req.prefill_tokens, req.alloc.blocks)

    def _do_prefill_chunk(self, req: Request) -> bool:
        """Write ONE chunk of `req`'s prompt into its pool blocks.

        Returns True when the prompt is fully cached — the first token
        is then sampled and the slot activated for decode.  The chunk
        width is fixed at prefill_chunk (one compile); the ragged final
        chunk is padded to a block multiple (<= chunk width, one
        compile per distinct residue bucket — same trade as the
        whole-prompt buckets).
        """
        if req.cow_src is not None:
            self._apply_cow(req)
        self._flush_scrubs()
        bs, chunk = self.pcfg.block_size, self.pcfg.prefill_chunk
        start = req.prefill_pos
        remaining = req.prefill_len - start
        width = chunk if remaining > chunk else padded_prompt_len(remaining, bs)
        real = min(remaining, chunk)
        toks = np.zeros((1, width), np.int32)
        toks[0, :real] = req.prefill_tokens[start : start + real]
        table_row = jnp.asarray(
            req.alloc.table_row(self.max_blocks_per_seq), jnp.int32
        )
        with self._mesh_ctx(), phase_annotation("serve.prefill", self._profile):
            logits, (self._k_pool, self._v_pool) = self._prefill_chunk(
                self.params,
                jnp.asarray(toks),
                self._k_pool,
                self._v_pool,
                table_row,
                jnp.int32(start),
                jnp.int32(real - 1),
            )
        req.prefill_pos = start + real
        req.verified_len = start + real
        # chunk padding past capacity is absorbed by the scratch block
        # (padded table row) — only in-capacity positions can be dirty
        req.drafted_len = max(
            req.drafted_len, min(start + width, req.alloc.capacity())
        )
        self.stats.prefills += 1
        self.stats.prefill_tokens += real
        self.stats.prefill_padding += width - real
        if not req.prefill_done:
            self._emit(
                "PREFILL_CHUNK",
                req.rid,
                start=start,
                tokens=real,
                width=width,
                done=False,
                out_len=len(req.output),
            )
            return False
        self._finish_prefill(req, logits[0, -1])
        self._emit(
            "PREFILL_CHUNK",
            req.rid,
            start=start,
            tokens=real,
            width=width,
            done=True,
            out_len=len(req.output),
        )
        return True

    def _do_decode(self, step: int) -> List[Request]:
        self._flush_scrubs()
        token = jnp.asarray(self._last_tok[:, None])
        with self._mesh_ctx(), phase_annotation("serve.decode", self._profile):
            logits, (self._k_pool, self._v_pool) = self._decode(
                self.params,
                token,
                self._k_pool,
                self._v_pool,
                jnp.asarray(self._tables),
                jnp.asarray(self._lengths),
            )
        logits = np.asarray(logits[:, 0], np.float32)

        finished = []
        active = [
            (slot, req)
            for slot, req in self.scheduler.running.items()
            if req.prefill_done
        ]
        self.stats.decode_steps += 1
        self.stats.active_slot_steps += len(active)
        self.stats.idle_slot_steps += self.pcfg.max_slots - len(active)
        for slot, req in active:
            tok = int(self._pick_one(logits[slot], req, len(req.output)))
            req.output.append(tok)
            self._lengths[slot] += 1
            req.verified_len = int(self._lengths[slot])
            req.drafted_len = max(req.drafted_len, req.verified_len)
            self._last_tok[slot] = tok
            self.stats.generated_tokens += 1
            self._emit("DECODE", req.rid, new_tokens=1, out_len=len(req.output))
            if req.is_done():
                self._release(req, step)
                finished.append(req)
        return finished

    def _do_verify(self, step: int) -> List[Request]:
        """One speculative verify step: draft k tokens per active slot,
        score all k+1 positions in ONE batched `paged_score_tokens`
        call, commit the longest agreed prefix plus the target's own
        correction/bonus token, and roll the logical length back over
        the rejected tail.

        Greedy acceptance: with targets ``t_i = argmax(logits[:, i])``
        and drafts ``d_1..d_k``, accept while ``d_{i+1} == t_i`` — the
        committed tokens ``t_0..t_a`` are exactly what sequential
        one-token decode would have produced, so spec_k only changes
        throughput, never the stream.
        """
        self._flush_scrubs()
        k = self.pcfg.spec_k
        w = k + 1
        m = self.pcfg.max_slots
        active = [
            (slot, req)
            for slot, req in self.scheduler.running.items()
            if req.prefill_done
        ]
        tokens = np.zeros((m, w), np.int32)
        tokens[:, 0] = self._last_tok
        drafts: Dict[int, List[int]] = {}
        propose_hist = self.metrics.histogram("serve_draft_propose_seconds")
        for slot, req in active:
            td = time.perf_counter()
            d = self.drafter.propose(req, k)
            propose_hist.observe(time.perf_counter() - td)
            assert len(d) == k, (len(d), k)
            drafts[slot] = d
            tokens[slot, 1:] = d
        with self._mesh_ctx(), phase_annotation("serve.verify", self._profile):
            logits, (self._k_pool, self._v_pool) = self._score(
                self.params,
                jnp.asarray(tokens),
                self._k_pool,
                self._v_pool,
                jnp.asarray(self._tables),
                jnp.asarray(self._lengths),
            )
        logits = np.asarray(logits, np.float32)  # [m, w, V]

        finished = []
        self.stats.decode_steps += 1
        self.stats.spec_steps += 1
        self.stats.active_slot_steps += len(active)
        self.stats.idle_slot_steps += m - len(active)
        for slot, req in active:
            base = int(self._lengths[slot])
            req.drafted_len = max(req.drafted_len, base + w)
            targets = np.argmax(logits[slot], axis=-1)
            d = drafts[slot]
            a = 0
            while a < k and d[a] == int(targets[a]):
                a += 1
            self.stats.drafted_tokens += k
            self.stats.accepted_tokens += a
            committed = 0
            for t in targets[: a + 1]:
                req.output.append(int(t))
                committed += 1
                self.stats.generated_tokens += 1
                self.stats.spec_committed_tokens += 1
                if req.is_done():  # stop_token / max_new hit mid-burst
                    break
            self._lengths[slot] = base + committed
            self._last_tok[slot] = req.output[-1]
            self.scheduler.rollback(req, base + committed)
            self._emit(
                "VERIFY",
                req.rid,
                k=k,
                accepted=a,
                new_tokens=committed,
                out_len=len(req.output),
            )
            if req.is_done():
                self._release(req, step)
                finished.append(req)
        return finished

    def _grow_active(self, step: int) -> None:
        """On-demand capacity phase (preemption="recompute"), run just
        before the decode/verify call: every fully-prefilled sequence
        must own blocks for the positions this step writes — one for
        plain decode, spec_k + 1 for a verify burst.  Growth runs most
        deserving first, so under pool pressure the victims are exactly
        the least deserving sequences (possibly a grower itself, which
        is then parked and dropped from this step's batch)."""
        w = self.pcfg.spec_k + 1 if self.pcfg.spec_k else 1
        active = sorted(
            (r for r in self.scheduler.running.values() if r.prefill_done),
            key=Scheduler.deserving,
            reverse=True,
        )
        for req in active:
            if req.state is not RequestState.RUNNING:
                continue  # evicted by a more deserving grower above
            before = len(req.alloc.blocks)
            if self.scheduler.grow(
                req, req.verified_len + w, self._on_preempt, step
            ):
                self._tables[req.slot] = req.alloc.table_row(
                    self.max_blocks_per_seq
                )
                after = len(req.alloc.blocks)
                if after != before:
                    self._emit(
                        "GROW",
                        req.rid,
                        new_blocks=after - before,
                        blocks=after,
                    )

    def _on_preempt(self, req: Request, slot: int, scrub: List[int]) -> None:
        """Scheduler preemption callback: scrub every block the victim
        ever wrote (committed K/V included — the resume recomputes it,
        so nothing of the evicted sequence may survive in the pool),
        reset the victim's decode-slot state, and tell a stateful
        drafter its context bookkeeping is void."""
        if scrub:
            self._scrub(scrub)
        self._tables[slot] = SCRATCH_BLOCK
        self._lengths[slot] = 0
        self._last_tok[slot] = 0
        if req in self._prefilling:  # evicted mid-chunk-prefill
            self._prefilling.remove(req)
        if self.drafter is not None:
            hook = getattr(self.drafter, "on_preempt", None)
            if hook is not None:
                hook(req)
        self.stats.preemptions += 1
        self._emit(
            "PREEMPT",
            req.rid,
            blocks_freed=len(scrub),
            preempt_count=req.preempt_count,
            out_len=len(req.output),
        )

    def _cancel(self, req: Request, step: int) -> None:
        was_running = req.state is RequestState.RUNNING
        slot = req.slot
        stale = self.scheduler.cancel(req, step)
        if was_running:
            if stale:
                self._scrub(stale)
            self._tables[slot] = SCRATCH_BLOCK
            self._lengths[slot] = 0
            self._last_tok[slot] = 0
            if req in self._prefilling:
                self._prefilling.remove(req)

    def _release(self, req: Request, step: int) -> None:
        slot = req.slot
        stale = self.scheduler.retire(req, step)
        if stale:
            self._scrub(stale)
        self._tables[slot] = SCRATCH_BLOCK
        self._lengths[slot] = 0
        self._last_tok[slot] = 0
        self._emit("FINISH", req.rid, out_len=len(req.output))

    def _scrub(self, blocks: List[int]) -> None:
        """Queue freed blocks that hold written-but-never-committed K/V
        (rolled-back speculative tails, prefill padding) for zeroing,
        so a future owner can never attend over a previous sequence's
        stale keys — the length masks make such reads unreachable
        today, but the free list is the trust boundary and scrubbed
        blocks keep it airtight against any future mask/length
        accounting bug.  Queued blocks are zeroed in one batched
        scatter (:meth:`_flush_scrubs`) before the next pool write:
        every compute helper flushes first, so a queued block can never
        be reallocated *and written* ahead of its scrub."""
        self._scrub_pending.extend(blocks)

    def _flush_scrubs(self) -> None:
        """Zero every pending freed block — retires, preempts, cancels,
        spec rollbacks and prefix-cache evictions accumulated since the
        last flush — in ONE padded scatter call, instead of one jitted
        dispatch per event.  The id row is padded with the scratch
        block to the next multiple of max_blocks_per_seq so flushes
        share a handful of compiles (re-zeroing scratch is harmless)."""
        self._scrub_pending.extend(self.allocator.drain_evicted())
        if not self._scrub_pending:
            return
        blocks, self._scrub_pending = self._scrub_pending, []
        w = self.max_blocks_per_seq
        n = -(-len(blocks) // w) * w
        ids = np.full((n,), SCRATCH_BLOCK, np.int32)
        ids[: len(blocks)] = blocks
        with self._mesh_ctx(), phase_annotation("serve.scrub", self._profile):
            self._k_pool, self._v_pool = self._scrub_fn(
                self._k_pool, self._v_pool, jnp.asarray(ids)
            )

    def _apply_cow(self, req: Request) -> None:
        """Copy-on-write before a shared tail block absorbs writes: the
        one cache-hit block this sequence must write into (a fully-hit
        block whose last token is recomputed for logits — ``cached_len``
        was capped mid-block) is duplicated into the private block
        allocated in its place, then the pin on the shared source is
        dropped.  Runs before the suffix prefill touches the pool."""
        src = req.cow_src
        assert src is not None
        self._flush_scrubs()
        dst = req.alloc.blocks[req.cached_len // self.pcfg.block_size]
        with self._mesh_ctx(), phase_annotation("serve.cow", self._profile):
            self._k_pool, self._v_pool = self._cow_fn(
                self._k_pool, self._v_pool, jnp.int32(src), jnp.int32(dst)
            )
        req.cow_src = None
        self._scrub(self.allocator.release([src]))

    def _pick_one(self, logits_row, req: Request, token_idx: int):
        if self.pcfg.temperature <= 0:
            # host-side argmax: logits are already materialized as numpy
            # in the decode loop; a jnp.argmax here would re-upload every
            # row and add a device round-trip per slot per step
            return int(np.argmax(np.asarray(logits_row)))
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.pcfg.seed), req.rid),
            token_idx,
        )
        return int(
            jax.random.categorical(
                key, jnp.asarray(logits_row) / self.pcfg.temperature
            )
        )
