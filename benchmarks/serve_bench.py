"""Serving benchmark: continuous batching vs the static batcher.

Drives the engines over the same mixed-length, staggered-arrival
request stream (the traffic shape the ROADMAP's north star cares
about) and reports, per engine configuration:

* tokens/sec (generated tokens over wall time, post-warmup);
* p50 / p95 per-step latency — both engines now keep per-step
  wall-clock in ``ServeStats``, so the comparison needs no guards;
  chunked prefill exists precisely to pull the p95 down under mixed
  traffic (a long prompt costs many bounded steps, not one huge one);
* padding waste — capacity spent padding prompts plus slots idling.

The continuous engine runs a small configuration matrix: tp=1 vs
tp=<--tp> (when enough devices exist) crossed with unchunked vs
chunked prefill, plus speculative-decoding rows (``--spec-k``, with
acceptance rate and committed tokens per verify step), and asserts
every configuration generates EXACTLY the same tokens — the greedy
token-identity bar that CI's bench-smoke job re-checks on every push.
A separate OVERLOAD scenario (arrival rate > pool capacity) compares
preemption off vs "recompute": short-request p95 completion latency in
engine steps, eviction/resume counts, resume latency and the
deterministic deadline-miss rate — asserting that preemption never
changes a completed request's tokens.
A PREFIX-CACHE scenario (shared system prompt, wave of requests behind
it) compares prefix_cache off vs on: hit rate, prefill tokens/MACs
saved and TTFT p50/p95 in deterministic engine steps — asserting the
wave saves >50% of its prefill tokens, TTFT p95 improves, and greedy
tokens are identical either way.
The bench model serves in plam_sim numerics (the paper's approximate
multiplier), whose per-matmul quantization also keeps greedy argmax
invariant to TP reduction-order float noise.

Reading the numbers: padding waste is the architectural win and shows
at any scale.  At toy CPU scale the static batcher can still win raw
wall-clock (its whole run is a handful of fused XLA calls, while
continuous batching pays a host round-trip per step) and tp=2 on a
forced CPU "mesh" pays collectives for no real parallel compute; the
reclaimed capacity converts to throughput once model compute, not
dispatch, dominates a step — i.e. at real model sizes on real
accelerators.

Run:
  PYTHONPATH=src python benchmarks/serve_bench.py [--requests 12]
  PYTHONPATH=src python benchmarks/serve_bench.py \
      --tp 2 --prefill-chunk 16 --spec-k 4 --force-host-devices 8 \
      --json BENCH_serving.json
"""
from __future__ import annotations

import argparse
import json
import os
import time


def make_stream(n_requests: int, seed: int = 0):
    """Mixed-length prompts with staggered arrivals (bursty Poisson-ish)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    stream = []
    step = 0
    for _ in range(n_requests):
        plen = int(rng.integers(4, 48))
        max_new = int(rng.integers(4, 24))
        stream.append((rng.integers(0, 256, plen).tolist(), max_new, step))
        step += int(rng.integers(0, 3))  # 0-2 engine steps between arrivals
    return stream


def make_overload_stream(seed: int = 0):
    """Arrival rate > capacity: long low-priority requests saturating
    the pool with short high-priority requests arriving behind them.
    Prompt lengths are drawn from two fixed buckets (32 and 8) so the
    overload rows stay to a handful of prefill compiles.  Returns
    (prompt, max_new, arrival_step, priority, deadline_steps) tuples,
    arrival-ordered; half the shorts carry a step-count deadline."""
    import numpy as np

    rng = np.random.default_rng(seed)
    entries = []
    for i in range(4):  # the saturating background
        entries.append((rng.integers(0, 256, 32).tolist(), 16, i, 0, None))
    for j in range(6):  # the latency-sensitive foreground
        entries.append((rng.integers(0, 256, 8).tolist(), 6, 1 + j, 1,
                        60.0 if j % 2 else None))
    return sorted(entries, key=lambda e: e[2])


def make_prefix_stream(seed: int = 0):
    """Shared-system-prompt traffic: one early request publishes the
    48-token system prompt (six full blocks at the bench block size 8),
    then a wave of requests reuses it with short unique tails.  The
    wave arrives after the first request's chunked prefill completes —
    block hashes are registered at prefill completion, so arrivals
    before that point would reserve their own blocks and miss."""
    import numpy as np

    rng = np.random.default_rng(seed)
    system = rng.integers(0, 256, 48).tolist()
    entries = [(system + rng.integers(0, 256, 4).tolist(), 8, 0)]
    for j in range(6):
        tail = rng.integers(0, 256, 4 + j).tolist()
        entries.append((system + tail, 8, 8 + j))
    return entries


def bench_prefix_cache(base_cfg, params, *, prefix_cache, seed=0):
    """Shared-system-prompt scenario, cache off vs on.  TTFT is
    measured in engine steps on the injected step-counting clock
    (wall-clock at toy CPU scale is compile noise): per wave request,
    queue + prefill steps from the trace breakdown.  With the cache on,
    the 48-token system prompt is six block hits, so the suffix prefill
    is one 16-wide chunk instead of four — the TTFT win is structural,
    not a measurement artifact.  MAC savings price the skipped prefill
    tokens at the model's per-token forward MACs (mode-resolved, i.e.
    the PLAM-approximate-multiplier work the paper counts)."""
    import numpy as np

    from repro.serving import ContinuousBatchingEngine, PagedServeConfig
    from repro.serving.observability import macs_per_token_by_mode

    stream = make_prefix_stream(seed)
    box = {}
    pcfg = PagedServeConfig(
        block_size=8, num_blocks=64, max_slots=4, max_seq_len=96,
        prefill_chunk=16, prefix_cache=prefix_cache,
        clock=lambda: float(box["eng"].current_step) if box else 0.0)
    eng = ContinuousBatchingEngine(base_cfg, params=params, pcfg=pcfg)
    box["eng"] = eng
    reqs = [eng.submit(p, max_new_tokens=m, arrival_step=s)
            for p, m, s in stream]
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    assert len(done) == len(reqs), "prefix-cache bench dropped a request"
    eng.trace.validate()

    ttft = []
    for r in reqs[1:]:  # the wave; entry 0 warms the cache
        bd = eng.trace.breakdown(r.rid)
        ttft.append(bd.queue_s + bd.prefill_s)
    al = eng.allocator
    prompt_tokens = sum(len(p) for p, _, _ in stream)
    macs_per_tok = sum(macs_per_token_by_mode(base_cfg).values())
    return {
        "engine": "prefix",
        "prefix_cache": prefix_cache,
        "wall_s": dt,
        "steps": eng.stats.steps,
        "prefix_hit_rate": al.hits / max(al.hits + al.misses, 1),
        "prefill_tokens_saved": al.tokens_saved,
        "prefill_tokens_saved_frac": al.tokens_saved / prompt_tokens,
        "prefill_macs_saved": al.tokens_saved * macs_per_tok,
        "prefix_evictions": al.evictions,
        "cow_copies": al.cow_copies,
        "ttft_p50_steps": float(np.quantile(np.asarray(ttft), 0.50)),
        "ttft_p95_steps": float(np.quantile(np.asarray(ttft), 0.95)),
        "tokens": {r.rid: list(done[r.rid]) for r in reqs},
    }


def bench_overload(base_cfg, params, *, preemption, seed=0,
                   trace_out=None, metrics_out=None):
    """Overload scenario: the pool holds ~2 of the 4 concurrent long
    requests, so the shorts must either queue behind them (FCFS,
    preemption="off") or evict them (priority victims under
    "recompute").  The metric that separates the regimes is the SHORT
    requests' completion latency in engine steps — wall-clock would
    mostly measure CPU compile noise.  Deadlines tick on an injected
    step-counting clock, so the miss rate is deterministic — and so are
    the per-request queue/prefill/decode/parked breakdowns the trace
    derives (clock units are engine steps here, not seconds).
    ``trace_out`` / ``metrics_out`` write the run's trace (JSON-lines)
    and Prometheus snapshot — the artifacts CI uploads and
    schema-checks."""
    import numpy as np

    from repro.serving import ContinuousBatchingEngine, PagedServeConfig

    stream = make_overload_stream(seed)
    box = {}
    pcfg = PagedServeConfig(
        block_size=8, num_blocks=16, max_slots=4, max_seq_len=64,
        preemption=preemption,
        clock=lambda: float(box["eng"].current_step) if box else 0.0)
    eng = ContinuousBatchingEngine(base_cfg, params=params, pcfg=pcfg)
    box["eng"] = eng
    reqs = []
    for p, m, s, prio, dl in stream:
        reqs.append(eng.submit(p, max_new_tokens=m, arrival_step=s,
                               priority=prio, deadline_s=dl))
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0

    from repro.serving import RequestState

    eng.trace.validate()
    shorts = [r for r, e in zip(reqs, stream) if e[3] > 0]
    finished_shorts = [r for r in shorts if r.state is RequestState.FINISHED]
    short_lat = [r.finished_step - r.arrival_step for r in finished_shorts]
    with_deadline = [r for r in reqs if r.deadline_s is not None]
    # the injected clock counts engine steps, so these breakdowns are
    # deterministic: where each short request's lifetime went, in steps
    short_breakdowns = {}
    for r in shorts:
        bd = eng.trace.breakdown(r.rid)
        short_breakdowns[r.rid] = {
            "queue_steps": bd.queue_s, "prefill_steps": bd.prefill_s,
            "decode_steps": bd.decode_s, "parked_steps": bd.parked_s,
            "total_steps": bd.total_s, "terminal": bd.terminal,
        }
    if trace_out:
        eng.trace.to_jsonl(trace_out)
    if metrics_out:
        with open(metrics_out, "w") as f:
            f.write(eng.metrics.to_prometheus_text())
    return {
        "engine": "overload",
        "preemption": preemption,
        "wall_s": dt,
        "steps": eng.stats.steps,
        "short_p95_latency_steps": (
            float(np.quantile(np.asarray(short_lat), 0.95))
            if short_lat else float("nan")),
        "short_breakdowns": short_breakdowns,
        "preemptions": int(eng.metrics.value("serve_preemptions_total")),
        "resumes": int(eng.metrics.value("serve_resumes_total")),
        "resume_latency_steps_mean": (
            float(np.mean(eng.stats.resume_latency_steps))
            if eng.stats.resume_latency_steps else 0.0),
        "deadline_miss_rate": (
            eng.metrics.value("serve_deadline_cancelled_total")
            / len(with_deadline) if with_deadline else 0.0),
        "tokens": {r.rid: list(r.output) for r in reqs
                   if r.state is RequestState.FINISHED},
    }


def bench_static(base_cfg, params, stream):
    """Static batcher: one batch, padded to max prompt len, decoding
    max(max_new) steps for everyone; late arrivals wait for the batch."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.serving import Engine, ServeConfig

    eng = Engine(base_cfg, params)
    max_plen = max(len(p) for p, _, _ in stream)
    max_new = max(m for _, m, _ in stream)
    toks = np.zeros((len(stream), max_plen), np.int32)
    for i, (p, _, _) in enumerate(stream):
        toks[i, max_plen - len(p):] = p  # left-pad (right-aligned prompts)
    batch = {"tokens": jnp.asarray(toks)}
    # time_steps: sync per decode step so p50/p95 are real wall latency
    scfg = ServeConfig(max_new_tokens=max_new, time_steps=True)
    eng.generate(batch, scfg)  # warmup/compile
    t0 = time.perf_counter()
    out = eng.generate(batch, scfg)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0

    useful = sum(m for _, m, _ in stream)
    total_tok = out.shape[0] * out.shape[1]
    prompt_pad = sum(max_plen - len(p) for p, _, _ in stream)
    prompt_real = sum(len(p) for p, _, _ in stream)
    decode_waste = total_tok - useful
    spent = prompt_real + prompt_pad + total_tok
    return {
        "engine": "static",
        "tp": 1,
        "prefill_chunk": 0,
        "wall_s": dt,
        "useful_tokens": useful,
        "tok_per_s": useful / dt,
        "p50_step_ms": eng.stats.latency_p50() * 1e3,
        "p95_step_ms": eng.stats.latency_p95() * 1e3,
        "padding_waste": (prompt_pad + decode_waste) / spent,
    }


def bench_continuous(base_cfg, params, stream, *, tp=1, prefill_chunk=0,
                     spec_k=0, warmup=True, trace=True):
    """One continuous-engine configuration.  Post-redesign, everything
    this reports is read from the engine's MetricsRegistry (the same
    names a Prometheus scrape would see) rather than ServeStats fields;
    per-request submit->first-token / submit->finish percentiles come
    from the trace.  ``trace=False`` measures the engine with recording
    disabled — the pair of runs is the trace-overhead check."""
    from repro.serving import ContinuousBatchingEngine, PagedServeConfig, ServeStats

    pcfg = PagedServeConfig(block_size=8, num_blocks=256, max_slots=8,
                            max_seq_len=128, tp=tp, prefill_chunk=prefill_chunk,
                            spec_k=spec_k, trace=trace)
    eng = ContinuousBatchingEngine(base_cfg, params=params, pcfg=pcfg)
    if warmup:  # compile prefill buckets/chunks + the decode step off the clock
        for p, m, _ in stream:
            eng.submit(p, max_new_tokens=m, arrival_step=0)
        eng.run()
        eng.stats = ServeStats()
        if eng.trace is not None:
            eng.trace.clear()
    base_step = eng.current_step  # arrival steps are absolute
    reqs = []
    for p, m, s in stream:
        reqs.append(eng.submit(p, max_new_tokens=m, arrival_step=base_step + s))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    useful = sum(len(v) for v in done.values())
    assert useful == sum(m for _, m, _ in stream), "engine dropped tokens"
    step_hist = eng.metrics.histogram("serve_step_latency_seconds")
    row = {
        "engine": "continuous",
        "tp": tp,
        "prefill_chunk": prefill_chunk,
        "spec_k": spec_k,
        "trace": trace,
        "wall_s": dt,
        "useful_tokens": useful,
        "tok_per_s": useful / dt,
        "p50_step_ms": step_hist.quantile(0.50) * 1e3,
        "p95_step_ms": step_hist.quantile(0.95) * 1e3,
        "padding_waste": eng.metrics.value("serve_padding_waste"),
        "steps": int(eng.metrics.value("serve_steps_total")),
        "acceptance_rate": eng.metrics.value("serve_spec_acceptance_rate"),
        "tokens_per_verify_step": eng.metrics.value(
            "serve_tokens_per_verify_step"),
        "tokens": [done[r.rid] for r in reqs],
    }
    if eng.trace is not None:
        eng.trace.validate()
        summary = eng.trace.latency_summary()
        row.update({
            "req_ttft_p50_s": summary["first_token_p50_s"],
            "req_ttft_p95_s": summary["first_token_p95_s"],
            "req_total_p50_s": summary["total_p50_s"],
            "req_total_p95_s": summary["total_p95_s"],
        })
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=2,
                    help="sharded configuration to benchmark against tp=1 "
                         "(skipped when fewer devices exist)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="chunked-prefill width for the chunked rows "
                         "(a multiple of the bench block size, 8)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative-decoding depth for the spec rows "
                         "(0 = skip them); spec rows join the cross-config "
                         "token-identity assertion")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write results (tokens/s, p95 step latency, "
                         "padding-waste %%) as JSON, e.g. BENCH_serving.json")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the overload-recompute run's trace events as "
                         "JSON-lines (the artifact CI schema-checks)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the overload-recompute run's Prometheus text "
                         "snapshot (the artifact CI schema-checks)")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    help="force N CPU devices via XLA_FLAGS (set before jax "
                         "initializes; how CI gets a tp-capable host)")
    args = ap.parse_args()

    if args.force_host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.force_host_devices}"
        )

    import jax

    from repro.configs.base import ModelConfig
    from repro.core.modes import NumericsConfig
    from repro.serving import Engine

    # PLAM-mode numerics, not f32: besides being the paper's serving
    # story, the per-matmul quantization snaps logits onto a shared
    # grid, which makes greedy argmax invariant to the reduction-order
    # float noise TP introduces (f32 near-ties can flip a token between
    # tp=1 and tp=2 even though both engines are correct to ~1e-3)
    base_cfg = ModelConfig(
        name="serve-bench", family="dense", n_layers=4, d_model=128, n_heads=4,
        n_kv=2, head_dim=32, d_ff=256, vocab=256,
        numerics=NumericsConfig(mode="plam_sim", n=16, es=1),
        act_dtype="float32", param_dtype="float32",
    )

    stream = make_stream(args.requests, args.seed)
    print(f"stream: {len(stream)} requests, prompt lens "
          f"{sorted(len(p) for p, _, _ in stream)}")
    params = Engine(base_cfg, key=jax.random.PRNGKey(0)).params

    matrix = [(1, 0, 0), (1, args.prefill_chunk, 0)]
    if args.spec_k:
        matrix += [(1, 0, args.spec_k), (1, args.prefill_chunk, args.spec_k)]
    if args.tp > 1:
        if len(jax.devices()) < args.tp:
            raise SystemExit(
                f"tp={args.tp} needs {args.tp} devices, found "
                f"{len(jax.devices())}; on CPU pass --force-host-devices "
                f"{max(8, args.tp)}")
        matrix += [(args.tp, 0, 0), (args.tp, args.prefill_chunk, 0)]
        if args.spec_k:
            matrix += [(args.tp, 0, args.spec_k),
                       (args.tp, args.prefill_chunk, args.spec_k)]

    rows = [bench_static(base_cfg, params, stream)]
    for tp, chunk, spec_k in matrix:
        rows.append(bench_continuous(base_cfg, params, stream,
                                     tp=tp, prefill_chunk=chunk,
                                     spec_k=spec_k))

    # trace-overhead check: the same tp=1 unchunked configuration with
    # recording disabled.  Tracing is on by default, so the delta must
    # stay well under the 5% tok/s budget at real step costs — at toy
    # CPU scale both runs are dispatch-noise-dominated, so the recorded
    # number is the honest measurement, not a pass/fail gate.
    off_row = bench_continuous(base_cfg, params, stream, trace=False)
    on_row = next(r for r in rows
                  if r["engine"] == "continuous" and r["tp"] == 1
                  and r["prefill_chunk"] == 0 and r["spec_k"] == 0)
    assert off_row["tokens"] == on_row["tokens"], (
        "disabling tracing changed the generated tokens")
    off_row.pop("tokens")
    trace_overhead = {
        "tok_per_s_trace_on": on_row["tok_per_s"],
        "tok_per_s_trace_off": off_row["tok_per_s"],
        "overhead_frac": 1.0 - on_row["tok_per_s"] / off_row["tok_per_s"],
    }

    # greedy decode must be configuration-invariant: every continuous
    # config — including the speculative ones — generates the same
    # per-request tokens (CI fails here first)
    token_sets = [r.pop("tokens") for r in rows if r["engine"] == "continuous"]
    token_identical = all(t == token_sets[0] for t in token_sets[1:])
    assert token_identical, (
        "continuous engine configurations diverged under greedy decode "
        "(tp/chunked/spec must be token-identical to tp=1 unchunked)")

    # overload scenario: arrival rate > pool capacity, preemption off vs
    # on.  Preemption joins the identity bar: every request that ran to
    # completion in both regimes emitted the same tokens, evictions and
    # recompute-resumes included (deadline-cancelled stragglers differ
    # by construction — a cancelled stream is a shorter stream).
    overload_rows = [
        bench_overload(base_cfg, params, preemption="off", seed=args.seed),
        bench_overload(base_cfg, params, preemption="recompute",
                       seed=args.seed, trace_out=args.trace_out,
                       metrics_out=args.metrics_out),
    ]
    off_toks, on_toks = [r.pop("tokens") for r in overload_rows]
    both = sorted(set(off_toks) & set(on_toks))
    assert both, "overload runs finished no common requests"
    assert all(off_toks[rid] == on_toks[rid] for rid in both), (
        "preemption changed a completed request's tokens under overload")

    # shared-system-prompt scenario: the prefix cache must leave greedy
    # tokens untouched while skipping most of the wave's prefill
    prefix_rows = [
        bench_prefix_cache(base_cfg, params, prefix_cache=False,
                           seed=args.seed),
        bench_prefix_cache(base_cfg, params, prefix_cache=True,
                           seed=args.seed),
    ]
    pc_off, pc_on = prefix_rows
    assert pc_off.pop("tokens") == pc_on.pop("tokens"), (
        "prefix cache changed a request's greedy tokens")
    assert pc_on["prefill_tokens_saved_frac"] > 0.5, (
        "shared-system-prompt wave saved less than half its prefill "
        f"tokens: {pc_on['prefill_tokens_saved_frac']:.1%}")
    assert pc_on["ttft_p95_steps"] < pc_off["ttft_p95_steps"], (
        "prefix cache did not improve TTFT p95 "
        f"({pc_on['ttft_p95_steps']} vs {pc_off['ttft_p95_steps']} steps)")
    ttft_p95_speedup = pc_off["ttft_p95_steps"] / pc_on["ttft_p95_steps"]

    hdr = (f"{'engine':<12}{'tp':>3}{'chunk':>6}{'spec':>5}{'tok/s':>10}"
           f"{'wall_s':>9}{'p50_ms':>8}{'p95_ms':>8}{'pad_waste':>11}"
           f"{'accept':>8}{'tok/vfy':>8}")
    print("\n" + hdr)
    for r in rows:
        spec_k = r.get("spec_k", 0)
        accept = f"{r['acceptance_rate']:>8.1%}" if spec_k else f"{'-':>8}"
        tpv = (f"{r['tokens_per_verify_step']:>8.2f}" if spec_k
               else f"{'-':>8}")
        print(f"{r['engine']:<12}{r['tp']:>3}{r['prefill_chunk']:>6}"
              f"{spec_k:>5}{r['tok_per_s']:>10.1f}{r['wall_s']:>9.3f}"
              f"{r['p50_step_ms']:>8.2f}{r['p95_step_ms']:>8.2f}"
              f"{r['padding_waste']:>11.1%}{accept}{tpv}")
    s, c = rows[0], rows[1]
    print(f"\npadding waste: static {s['padding_waste']:.1%} -> "
          f"continuous {c['padding_waste']:.1%}; token_identical across "
          f"{len(token_sets)} continuous configs: {token_identical}")
    print(f"trace overhead (tp=1 unchunked): "
          f"{trace_overhead['tok_per_s_trace_on']:.1f} tok/s traced vs "
          f"{trace_overhead['tok_per_s_trace_off']:.1f} untraced "
          f"({trace_overhead['overhead_frac']:+.1%})")
    print(f"per-request latency (tp=1 unchunked, traced): "
          f"ttft p50={c['req_ttft_p50_s'] * 1e3:.1f}ms "
          f"p95={c['req_ttft_p95_s'] * 1e3:.1f}ms; total "
          f"p50={c['req_total_p50_s'] * 1e3:.1f}ms "
          f"p95={c['req_total_p95_s'] * 1e3:.1f}ms")

    print(f"\n{'overload':<12}{'preempt':>10}{'short_p95':>11}{'steps':>7}"
          f"{'evict':>7}{'resume':>8}{'rsm_lat':>9}{'dl_miss':>9}")
    for r in overload_rows:
        print(f"{r['engine']:<12}{r['preemption']:>10}"
              f"{r['short_p95_latency_steps']:>11.1f}{r['steps']:>7}"
              f"{r['preemptions']:>7}{r['resumes']:>8}"
              f"{r['resume_latency_steps_mean']:>9.1f}"
              f"{r['deadline_miss_rate']:>9.1%}")

    print(f"\n{'prefix':<12}{'cache':>7}{'hit_rate':>10}{'saved':>7}"
          f"{'saved_frac':>12}{'ttft_p50':>10}{'ttft_p95':>10}")
    for r in prefix_rows:
        print(f"{r['engine']:<12}{('on' if r['prefix_cache'] else 'off'):>7}"
              f"{r['prefix_hit_rate']:>10.1%}{r['prefill_tokens_saved']:>7}"
              f"{r['prefill_tokens_saved_frac']:>12.1%}"
              f"{r['ttft_p50_steps']:>10.1f}{r['ttft_p95_steps']:>10.1f}")
    print(f"prefix cache: ttft p95 {pc_off['ttft_p95_steps']:.1f} -> "
          f"{pc_on['ttft_p95_steps']:.1f} steps "
          f"({ttft_p95_speedup:.1f}x), prefill MACs saved "
          f"{pc_on['prefill_macs_saved']:.3e}")

    if args.json:
        payload = {
            "bench": "serving",
            "requests": args.requests,
            "seed": args.seed,
            "devices": len(jax.devices()),
            "token_identical": token_identical,
            "rows": rows,
            "trace_overhead": trace_overhead,
            "overload": overload_rows,
            "prefix_cache": {
                "off": pc_off,
                "on": pc_on,
                "ttft_p95_speedup": ttft_p95_speedup,
            },
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
