#!/usr/bin/env python3
"""Smoke run of the PLAM serving path on TPU, at full Yi-6B width.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # tensor parallelism on four chips

One chip: the compiled kernels are checked against their jnp references
on small inputs.  Then Yi-6B (32 layers, seeded random weights) is
served through ``build_engine`` -> ``ContinuousBatchingEngine`` twice,
one engine at a time: under ``default=plam_sim:16:1`` with prequantized
weights (the Pallas PLAM matmul and the paged decode-attention kernel)
and under the config's own ``posit_quant`` numerics (the jnp codec).
Each serves four requests with staggered arrivals, 64-200 prompt tokens
and 16 new tokens.  Last, the committed conformance vectors are checked
against every oracle, the compiled Pallas one included.

Four chips: the ``plam_sim`` engine at ``tp=4`` and at ``tp=1`` serve the
same requests, and their greedy tokens must be identical.

Earlier lines report the device, parameter bytes, compile and wall
seconds per phase, every request's tokens and peak device memory.  The
last line is one JSON object, printed only when every check passed.  The
script exits non-zero without that line when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
PROMPT_LENS = (200, 64, 137, 96)
NEW_TOKENS = 16
PLAM_POLICY = "default=plam_sim:16:1"
MAX_PEAK_BYTES = 16 * 10**9
# XLA compilation, persistent-cache reads included (trace and lowering
# events nest across inner jits, so they are not summed)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# kernels the compiled plam_sim decode step must contain
DECODE_KERNELS = ("jit(plam_matmul)/pallas_call", "jit(paged_decode_attention_kernel)")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds of XLA compilation, read from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration


class Phase:
    """Prints one phase's wall and compile seconds when it ends."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        comp = self.clock.seconds - self.c0
        print(f"[{self.name}] wall_s={wall:.2f} compile_s={comp:.2f}", flush=True)


def yi_6b(policy=None):
    from repro.configs import get_config

    cfg = get_config("yi-6b")
    return cfg if policy is None else cfg.with_numerics(policy)


def prompts(vocab: int):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, n).tolist() for n in PROMPT_LENS]


def serve_options(**kw):
    from repro.serving import ServeOptions

    max_seq = max(PROMPT_LENS) + NEW_TOKENS
    return ServeOptions(
        engine="continuous",
        block_size=16,
        num_blocks=len(PROMPT_LENS) * (-(-max_seq // 16)) + 1,
        max_slots=len(PROMPT_LENS),
        max_seq_len=max_seq,
        use_kernel=True,
        **kw,
    )


def peak_bytes(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def decode_step_hlo(eng) -> str:
    """Compiled text of the engine's decode step (cached by the run)."""
    import jax.numpy as jnp

    args = (
        eng.params,
        jnp.asarray(eng._last_tok[:, None]),
        eng._k_pool,
        eng._v_pool,
        jnp.asarray(eng._tables),
        jnp.asarray(eng._lengths),
    )
    with eng._mesh_ctx():
        return eng._decode.lower(*args).compile().as_text()


def check_decode_kernels(label: str, eng, kernels) -> None:
    hlo = decode_step_hlo(eng)
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    print(f"[{label}] decode step: {len(calls)} tpu_custom_call ops", flush=True)
    check(calls, f"{label}: no tpu_custom_call in the compiled decode step")
    for name in kernels:
        check(
            any(name in ln for ln in calls),
            f"{label}: compiled decode step has no {name} custom call",
        )


def check_logits(label: str, eng, vocab: int) -> None:
    """One more decode step on the idle engine: finite [slots, 1, V] logits."""
    import jax.numpy as jnp
    import numpy as np

    with eng._mesh_ctx():
        logits, (eng._k_pool, eng._v_pool) = eng._decode(
            eng.params,
            jnp.asarray(eng._last_tok[:, None]),
            eng._k_pool,
            eng._v_pool,
            jnp.asarray(eng._tables),
            jnp.asarray(eng._lengths),
        )
    logits = np.asarray(logits, np.float32)
    check(logits.shape == (eng.pcfg.max_slots, 1, vocab), f"{label}: logits shape {logits.shape}")
    check(np.isfinite(logits).all(), f"{label}: non-finite decode logits")


def serve(label, cfg, opts, reqs, clock, devices, kernels=()):
    """Build an engine, serve ``reqs``, check the output; returns tokens.

    The engine is dropped before returning, so the next phase starts
    with its device memory free.
    """
    import jax
    from repro.serving import build_engine

    with Phase(f"{label}/build", clock):
        eng = build_engine(cfg, opts, key=jax.random.PRNGKey(SEED))
        jax.block_until_ready(eng.params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    print(f"[{label}] param_bytes={nbytes} prequantized={bool(eng.prequant_meta)} "
          f"tp={opts.tp}", flush=True)
    with Phase(f"{label}/serve", clock):
        handles = [
            eng.submit(p, max_new_tokens=NEW_TOKENS, arrival_step=2 * i)
            for i, p in enumerate(reqs)
        ]
        done = eng.run()
    tokens = [done[h.rid] for h in handles]
    print(f"[{label}] engine_steps={eng.stats.steps} "
          f"generated={eng.stats.generated_tokens}", flush=True)
    # host wall per engine step, first compiles included
    print(f"[{label}] step_wall_s={[round(s, 3) for s in eng.stats.step_latency_s]}",
          flush=True)
    for i, (p, t) in enumerate(zip(reqs, tokens)):
        print(f"[{label}] req{i} prompt_len={len(p)} tokens={t}", flush=True)
        check(len(t) == NEW_TOKENS, f"{label}: req{i} got {len(t)} tokens")
        check(all(0 <= x < cfg.vocab for x in t), f"{label}: req{i} token out of range")
    with Phase(f"{label}/checks", clock):
        check_logits(label, eng, cfg.vocab)
        if kernels:
            check_decode_kernels(label, eng, kernels)
    peaks = peak_bytes(devices)
    print(f"[{label}] peak_bytes_in_use={peaks}", flush=True)
    check(max(peaks) < MAX_PEAK_BYTES, f"{label}: peak {max(peaks)} bytes")
    del eng, handles, done
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    print(f"[{label}] engine freed: live_array_bytes={live}", flush=True)
    return tokens


def kernel_checks(clock) -> None:
    """Compiled kernels against their jnp references, Yi-6B widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention import (
        paged_decode_attention_kernel,
        paged_decode_attention_ref,
    )
    from repro.kernels.plam_matmul import plam_matmul
    from repro.kernels.ref import plam_matmul_seqref
    from repro.numerics import PositSpec

    spec = PositSpec(16, 1)
    rng = np.random.default_rng(SEED)
    with Phase("kernels/plam_matmul", clock):
        a = jnp.asarray(rng.integers(0, 1 << 16, (8, 4096)), jnp.int32)
        b = jnp.asarray(rng.integers(0, 1 << 16, (4096, 512)), jnp.int32)
        got = np.asarray(plam_matmul(a, b, spec, interpret=False))
        want = np.asarray(plam_matmul_seqref(a, b, spec))
    check(np.array_equal(got, want, equal_nan=True),
          "compiled plam_matmul differs from plam_matmul_seqref")
    print("[kernels] plam_matmul 8x4096x512: bit-identical to plam_matmul_seqref",
          flush=True)

    with Phase("kernels/paged_decode_attention", clock):
        b_, h, kv, hd, bs, nb, max_blk = 4, 32, 4, 128, 16, 64, 16
        q = jnp.asarray(rng.standard_normal((b_, h, hd)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((nb, bs, kv, hd)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((nb, bs, kv, hd)), jnp.float32)
        tables = jnp.asarray(
            np.stack([rng.permutation(nb)[:max_blk] for _ in range(b_)]), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, max_blk * bs + 1, b_), jnp.int32)
        got = np.asarray(paged_decode_attention_kernel(q, kp, vp, tables, lengths))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(paged_decode_attention_ref(q, kp, vp, tables, lengths))
    err = float(np.max(np.abs(got - want)))
    print(f"[kernels] paged_decode_attention B=4 H=32 kv=4 hd=128: "
          f"max_abs_err={err:.3e}", flush=True)
    check(np.allclose(got, want, rtol=2e-2, atol=2e-2),
          "compiled paged decode attention differs from its reference")


def conformance(clock) -> None:
    from repro.conformance.oracles import default_impls
    from repro.conformance.vectors import check_vectors
    from repro.numerics import PositSpec

    impls = default_impls(PositSpec(16, 1), include_compiled=True)
    with Phase("conformance", clock):
        failures = check_vectors(impls=impls)
    for msg in failures:
        print(f"[conformance] {msg}", flush=True)
    check(not failures, f"conformance: {len(failures)} failures")
    print(f"[conformance] committed vectors agree across {sorted(impls)}", flush=True)


def one_chip(clock, devices) -> None:
    kernel_checks(clock)
    plam_cfg = yi_6b(PLAM_POLICY)
    reqs = prompts(plam_cfg.vocab)
    plam = serve("plam_sim", plam_cfg, serve_options(prequantize=True), reqs,
                 clock, devices[:1], kernels=DECODE_KERNELS)
    posit = serve("posit_quant", yi_6b(), serve_options(), reqs, clock, devices[:1],
                  kernels=DECODE_KERNELS[1:])
    same = sum(a == b for x, y in zip(plam, posit) for a, b in zip(x, y))
    print(f"[policies] plam_sim vs posit_quant: {same}/{len(reqs) * NEW_TOKENS} "
          "greedy tokens equal (informational)", flush=True)
    conformance(clock)


def four_chips(clock, devices) -> None:
    cfg = yi_6b(PLAM_POLICY)
    reqs = prompts(cfg.vocab)
    opts = serve_options(prequantize=True, tp=4)
    tp4 = serve("tp4", cfg, opts, reqs, clock, devices[:4], kernels=DECODE_KERNELS)
    tp1 = serve("tp1", cfg, dataclasses.replace(opts, tp=1), reqs, clock,
                devices[:1], kernels=DECODE_KERNELS)
    print(f"[tp] tp=4 vs tp=1 greedy tokens identical: {tp4 == tp1}", flush=True)
    check(tp4 == tp1, "tp=4 greedy tokens differ from tp=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: both numerics policies on one chip; "
                         "4: tp=4 against tp=1, and nothing else")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache(ROOT)
    clock = CompileClock()
    t0 = time.perf_counter()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    try:
        (one_chip if args.chips == 1 else four_chips)(clock, devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[total] wall_s={time.perf_counter() - t0:.2f} "
          f"compile_s={clock.seconds:.2f}", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
