"""Serving driver: batched generation under any numerics mode/policy.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --numerics-policy "default=plam_sim:16:1" --batch 4 \
      --prompt-len 16 --new-tokens 8

``--numerics-policy`` takes a per-site policy string (e.g.
``"default=plam_sim:16:1, attn=posit_quant:16:1, lm_head=f32"``) or the
path to a policy artifact saved by ``repro.numerics.calibrate``.
``--prequantized`` encodes policy-selected weights to posit patterns
once at engine build (int16 storage, PLAM sites serve through
``kernels.ops.plam_dense``).

``--continuous`` swaps the static batcher for the paged-KV
continuous-batching engine (dense/moe families), staggering request
arrivals to exercise per-step admission.  ``--tp N`` shards the
continuous engine tensor-parallel over a (data=1, model=N) mesh;
``--prefill-chunk M`` turns on chunked prefill (M must be a multiple of
the engine block size).  On CPU, ``--force-host-devices 8`` fakes an
8-device platform (sets XLA_FLAGS before jax initializes), which is how
CI exercises the sharded engine.

Engine options beyond those first-class flags are spelled ``--opt
KEY=VAL`` (repeatable), with KEY any ``repro.serving.ServeOptions``
field — e.g. ``--opt spec_k=4 --opt preemption=recompute`` or ``--opt
prefix_cache=true`` (content-addressed KV reuse across requests that
share a prompt prefix; the summary line then reports the block
hit/miss counts and prompt tokens skipped).  The old
split spellings (``--numerics``, ``--spec-k``, ``--spec-draft``,
``--preemption``, ``--priority``, ``--deadline-s``) still work but are
deprecated: using any of them emits ONE consolidated
DeprecationWarning naming the flags and their ``--opt`` replacements,
and routes through the exact same ``ServeOptions`` — behavior
identical, spelling legacy.

Observability (see docs/observability.md): tracing is on by default;
``--trace-out PATH`` writes the engine trace after the run (Chrome
trace_event JSON when PATH ends in ``.json`` — load it in Perfetto —
JSON-lines otherwise), ``--metrics-out PATH`` writes a Prometheus text
snapshot (each engine phase's host seconds among it), ``--profile``
wraps each engine phase in a ``jax.profiler`` TraceAnnotation
``serve.<phase>`` for profiler captures, and ``--profile-dir DIR`` turns
that on and captures the run with ``jax.profiler.trace(DIR)`` (open the
``.xplane.pb`` under DIR in TensorBoard or Perfetto).
"""
import argparse
import contextlib
import os
import warnings

# legacy flag -> (ServeOptions field it maps to, dest on the parsed args)
_LEGACY_FLAGS = {
    "--spec-k": ("spec_k", "spec_k"),
    "--spec-draft": ("spec_draft", "spec_draft"),
    "--preemption": ("preemption", "preemption"),
    "--priority": ("priority", "priority"),
    "--deadline-s": ("deadline_s", "deadline_s"),
}


def make_parser() -> argparse.ArgumentParser:
    """The CLI surface, importable without touching jax (tests parse
    flag spellings against it; main() keeps XLA_FLAGS ordering)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--numerics", default=None,
                    choices=["f32", "bf16", "posit_quant", "plam_sim",
                             "mitchell_f32"],
                    help="DEPRECATED sugar for --numerics-policy "
                         "'default=<mode>'")
    ap.add_argument("--numerics-policy", default=None,
                    help="per-site policy string or path to a saved policy "
                         "artifact (default: 'default=plam_sim')")
    ap.add_argument("--prequantized", action="store_true",
                    help="encode policy-selected weights to posit patterns "
                         "once at engine build (serving-time weight storage)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="paged-KV continuous batching (dense/moe)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel ways for the continuous engine")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill width (0 = whole-prompt; "
                         "must be a multiple of the block size, 8)")
    ap.add_argument("--opt", action="append", default=[], metavar="KEY=VAL",
                    help="set any repro.serving.ServeOptions field, e.g. "
                         "--opt spec_k=4 --opt preemption=recompute "
                         "(repeatable; applied after first-class flags)")
    # -- deprecated split spellings (use --opt) ---------------------------
    ap.add_argument("--spec-k", type=int, default=None,
                    help="DEPRECATED: use --opt spec_k=K")
    ap.add_argument("--spec-draft", default=None,
                    help="DEPRECATED: use --opt spec_draft=SPEC")
    ap.add_argument("--preemption", default=None,
                    choices=["off", "recompute"],
                    help="DEPRECATED: use --opt preemption=MODE")
    ap.add_argument("--priority", type=int, default=None,
                    help="DEPRECATED: use --opt priority=P")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="DEPRECATED: use --opt deadline_s=S")
    # -- observability ----------------------------------------------------
    ap.add_argument("--trace-out", default=None,
                    help="write the engine trace here after the run: Chrome "
                         "trace_event JSON when the path ends in .json "
                         "(open in Perfetto), JSON-lines otherwise")
    ap.add_argument("--metrics-out", default=None,
                    help="write a Prometheus text-format metrics snapshot "
                         "here after the run")
    ap.add_argument("--profile", action="store_true",
                    help="annotate engine phases with jax.profiler "
                         "TraceAnnotations (visible inside a profiler "
                         "capture)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture the run with jax.profiler.trace(DIR); "
                         "implies --profile")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    help="force N host (CPU) devices via XLA_FLAGS — must be "
                         "set before jax initializes, so it only works as a "
                         "flag, never from inside python")
    return ap


def _coerce(field, raw: str):
    """Parse an --opt VAL string against a ServeOptions dataclass field."""
    low = raw.lower()
    if low in ("true", "false"):
        return low == "true"
    if low in ("none", "null"):
        return None
    for conv in (int, float):
        try:
            return conv(raw)
        except ValueError:
            continue
    return raw


def options_from_args(args):
    """Build the run's ServeOptions from parsed args.

    The deprecated split flags are folded in first (emitting ONE
    consolidated DeprecationWarning naming every legacy flag used),
    then ``--opt KEY=VAL`` overrides are applied on top — so the legacy
    spelling and its --opt replacement produce identical options.
    """
    import dataclasses

    from repro.serving import ServeOptions

    legacy_used = []
    legacy_vals = {}
    for flag, (field, dest) in _LEGACY_FLAGS.items():
        val = getattr(args, dest)
        if val is not None:
            legacy_used.append(f"{flag} -> --opt {field}=...")
            legacy_vals[field] = val
    if args.numerics is not None:
        legacy_used.append(
            "--numerics -> --numerics-policy 'default=<mode>'"
        )
    if legacy_used:
        warnings.warn(
            "deprecated serve flags: " + "; ".join(sorted(legacy_used))
            + ". These spellings keep working (identical behavior via "
            "repro.serving.ServeOptions) but will be removed; switch to the "
            "replacements shown.",
            DeprecationWarning,
            stacklevel=2,
        )

    max_seq = args.prompt_len + args.new_tokens
    opts = ServeOptions(
        max_new_tokens=args.new_tokens,
        temperature=args.temperature,
        seed=args.seed,
        engine="continuous" if args.continuous else "static",
        block_size=8,
        num_blocks=4 * args.batch * (max_seq // 8 + 2),
        max_slots=args.batch,
        max_seq_len=max_seq + 8,
        tp=args.tp,
        prefill_chunk=args.prefill_chunk,
        prequantize=args.prequantized,
        profile=args.profile or bool(args.profile_dir),
        **legacy_vals,
    )
    fields = {f.name: f for f in dataclasses.fields(ServeOptions)}
    overrides = {}
    for kv in args.opt:
        key, sep, raw = kv.partition("=")
        if not sep or key not in fields:
            raise SystemExit(
                f"bad --opt {kv!r}: expected KEY=VAL with KEY a ServeOptions "
                f"field ({', '.join(sorted(fields))})"
            )
        overrides[key] = _coerce(fields[key], raw)
    return dataclasses.replace(opts, **overrides)


def main():
    args = make_parser().parse_args()

    if args.force_host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.force_host_devices}"
        )

    # deferred until after XLA_FLAGS is settled: importing repro pulls in jax
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS, get_config
    from repro.core.policy import describe, load_policy_arg, parse_policy
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import ContinuousBatchingEngine, build_engine

    enable_compile_cache()
    opts = options_from_args(args)

    if args.arch not in ARCHS:
        raise SystemExit(f"unknown arch {args.arch!r}; pick from {sorted(ARCHS)}")

    cfg = get_config(args.arch)
    if args.reduced:
        import dataclasses

        cfg = cfg.reduced()
        cfg = dataclasses.replace(cfg, param_dtype="float32", act_dtype="float32")
    if args.numerics_policy is not None:
        policy = load_policy_arg(args.numerics_policy)
    else:  # single-mode default (or deprecated --numerics sugar)
        policy = parse_policy(f"default={args.numerics or 'plam_sim'}")
    cfg = cfg.with_numerics(policy)
    numerics_label = describe(cfg.numerics)
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit("use examples/ for multimodal serving demos")

    rng = np.random.default_rng(args.seed)
    if opts.engine == "continuous":
        eng = build_engine(cfg, opts, key=jax.random.PRNGKey(args.seed))
        handles = [eng.submit(
            rng.integers(0, cfg.vocab, args.prompt_len).tolist(),
            arrival_step=i, **opts.submit_kwargs())
            for i in range(args.batch)]
        with _capture(args):
            done = eng.run()
        spec = (f" spec_k={opts.spec_k} "
                f"accept={eng.stats.acceptance_rate():.1%} "
                f"tok/verify={eng.stats.tokens_per_verify_step():.2f}"
                if opts.spec_k else "")
        if opts.preemption != "off" or opts.deadline_s is not None:
            spec += (f" preemptions={eng.stats.preemptions}"
                     f" resumes={eng.stats.resumes}"
                     f" deadline_cancelled={eng.stats.deadline_cancelled}")
        if opts.prefix_cache:
            al = eng.allocator
            spec += (f" prefix_hits={al.hits} prefix_misses={al.misses}"
                     f" prefill_tokens_saved={al.tokens_saved}"
                     f" prefix_evictions={al.evictions}")
        print(f"arch={cfg.name} numerics={numerics_label!r} engine=continuous "
              f"tp={opts.tp} prefill_chunk={opts.prefill_chunk} "
              f"steps={eng.stats.steps} pad_waste={eng.stats.padding_waste():.1%} "
              f"step_p50={eng.stats.latency_p50() * 1e3:.1f}ms "
              f"step_p95={eng.stats.latency_p95() * 1e3:.1f}ms" + spec)
        for i, h in enumerate(handles):
            print(f"req[{i}]: {done[h.rid]}")
            bd = h.breakdown()
            if bd is not None:
                print(f"  queue={bd.queue_s * 1e3:.1f}ms "
                      f"prefill={bd.prefill_s * 1e3:.1f}ms "
                      f"decode={bd.decode_s * 1e3:.1f}ms "
                      f"parked={bd.parked_s * 1e3:.1f}ms "
                      f"ttft={bd.first_token_s * 1e3:.1f}ms")
        _write_artifacts(args, eng)
        return

    if (opts.tp > 1 or opts.prefill_chunk or opts.spec_k
            or opts.preemption != "off" or opts.deadline_s is not None
            or opts.priority):
        raise SystemExit("tp / prefill_chunk / spec_k / preemption / "
                         "deadline_s / priority require --continuous")
    eng = build_engine(cfg, opts, key=jax.random.PRNGKey(args.seed))
    assert not isinstance(eng, ContinuousBatchingEngine)
    prompts = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32))}
    with _capture(args):
        out = eng.generate(prompts, opts.static())
    print(f"arch={cfg.name} numerics={numerics_label!r} "
          f"step_p50={eng.stats.latency_p50() * 1e3:.1f}ms "
          f"step_p95={eng.stats.latency_p95() * 1e3:.1f}ms")
    for i, row in enumerate(np.asarray(out)):
        print(f"batch[{i}]: {row.tolist()}")
    _write_artifacts(args, eng)


def _capture(args):
    """``jax.profiler.trace(--profile-dir)`` around the run, if asked."""
    if not args.profile_dir:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.trace(args.profile_dir)


def _write_artifacts(args, eng) -> None:
    """Honor --trace-out / --metrics-out after a run."""
    trace = getattr(eng, "trace", None)
    if args.trace_out:
        if trace is None:
            print(f"trace-out skipped: engine has no trace "
                  f"(static engine or trace=False): {args.trace_out}")
        elif args.trace_out.endswith(".json"):
            trace.to_chrome_trace(args.trace_out)
            print(f"wrote Chrome trace: {args.trace_out}")
        else:
            trace.to_jsonl(args.trace_out)
            print(f"wrote trace events: {args.trace_out}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(eng.metrics.to_prometheus_text())
        print(f"wrote metrics: {args.metrics_out}")


if __name__ == "__main__":
    main()
