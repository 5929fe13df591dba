#!/usr/bin/env python3
"""Chip benchmark: one cell of ``BENCHMARK.json``, measured end to end.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's configuration file sets the model, numerics and engine; its
traffic file sets the closed loop of clients.  From the seed the run
makes the weights on the device, builds the engine through
``repro.serving.build_engine``, runs every program the cell uses once,
fills every slot, and then measures ``--seconds`` of closed-loop
serving through ``ContinuousBatchingEngine.step``.  With ``--trace 1``
the window is traced by the JAX profiler and the per-layer metrics are
reported instead of the end-to-end ones.

The configuration's model family (``families/<family>.py``) gives the
program's ModelConfig, the weights and the reference.  A configuration
whose engine states ``tp`` > 1 is served through the engine's
tensor-parallel path on that many chips, with its weights built in the
engine's parameter shardings.  After the window
the engine is freed, and a sample of the requests that finished in it
is run through the family's plain float32 reference: every served
token's logit must lie within the configuration's ``max_logit_gap`` of
the reference's best.

The last line of standard output is one JSON object.  The run exits
non-zero without it when JAX finds no TPU or fewer chips than the cell
asks for.  JAX's compilation cache is kept in ``.jax_cache/`` at the
checkout's root (or ``$JAX_COMPILATION_CACHE_DIR``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from functools import partial  # noqa: E402

CHIP = pathlib.Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import numpy as np  # noqa: E402

import arith  # noqa: E402
from cell import Cell, load_cell, load_metric  # noqa: E402
from loop import ClosedLoop, CompileCount, Window  # noqa: E402
from traffic import Traffic, load_mix  # noqa: E402


def serve_options(conf: dict, profile: bool):
    from repro.serving import ServeOptions

    e = conf["engine"]
    return ServeOptions(
        engine="continuous", block_size=e["block_size"], num_blocks=e["num_blocks"],
        max_slots=e["max_slots"], max_seq_len=e["max_seq_len"],
        prefill_chunk=e["prefill_chunk"], use_kernel=e["use_kernel"],
        temperature=e["temperature"], cache_dtype=conf["dtypes"]["cache"],
        prequantize=conf["numerics"]["prequantize"], tp=e.get("tp", 1), profile=profile,
    )


def placement(tp: int, devices):
    """Where the weights are built: the default device at tp=1, else the
    program's parameter rule on the ``(data=1, model=tp)`` mesh over the
    first ``tp`` devices that the engine builds, so that its placement
    moves nothing."""
    if tp == 1:
        return None
    from repro.parallel.sharding import make_mesh, param_shardings

    mesh = make_mesh((1, tp), ("data", "model"), devices=devices[:tp])
    return partial(param_shardings, mesh)


def end_to_end(win: Window) -> dict:
    """tokens_per_s, itl_p95_s and ttft_p50_s over the whole window."""
    prompt = sum(s.chunk[1] for s in win.steps if s.chunk)
    generated = sum(sum(win.in_window(t) for t in r.token_t) for r in win.tracked)
    gaps = [b - a for r in win.finished() for a, b in zip(r.token_t, r.token_t[1:])
            if a >= win.t_open and win.in_window(b)]
    ttft = [r.token_t[0] - r.submit_t for r in win.tracked
            if r.token_t and win.in_window(r.token_t[0])]
    out = {"tokens_per_s": (prompt + generated) / win.seconds}
    if len(gaps) >= 20:
        out["itl_p95_s"] = float(np.percentile(gaps, 95))
    if ttft:
        out["ttft_p50_s"] = statistics.median(ttft)
    return out


def step_shape(win: Window) -> dict:
    """How the window's steps split between plain decode and chunk steps."""
    chunk_end = {s.t1 for s in win.steps if s.chunk}
    gaps = [b in chunk_end for r in win.finished() for a, b in zip(r.token_t, r.token_t[1:])
            if a >= win.t_open and win.in_window(b)]
    walls = [s.t1 - s.t0 for s in win.steps]
    return {"steps_in_window": len(win.steps), "chunk_steps": len(chunk_end),
            "gaps": len(gaps), "gap_share_with_chunk": sum(gaps) / max(len(gaps), 1),
            "step_wall_median_s": statistics.median(walls), "step_wall_max_s": max(walls)}


def choose_sample(win: Window, seed: int, min_served: int):
    """Requests finished in the window: the longest, then others drawn
    from the seed until ``min_served`` served tokens are covered."""
    done = sorted(win.finished(), key=lambda r: (len(r.prompt) + len(r.token_t),
                                                 r.client, r.index))
    if not done:
        return []
    pick, rest = [done[-1]], done[:-1]
    rng = np.random.default_rng([seed, 2])
    for i in rng.permutation(len(rest)):
        if sum(len(r.token_t) for r in pick) >= min_served:
            break
        pick.append(rest[i])
    return pick


def check(family, conf: dict, seed: int, win: Window, outputs: dict,
          control: bool) -> tuple:
    """Numbers compared, each ``(value, limit)``, and diagnostics.

    ``max_logit_gap``: over a sample of the requests finished in the
    window, the widest gap by which a served token's logit lies below
    the reference's best at its position.  With ``control`` the
    reference in float8 stands in for the program: at each position of
    the same sequences the token it puts first takes the served token's
    place, and is judged by the same limit.  The program's own reading
    is then kept as ``program_max_logit_gap``.
    """
    import reference

    finished = win.finished()
    wrong_len = sum(len(outputs[id(r)]) != r.max_new_tokens for r in finished)
    sample = choose_sample(win, seed, conf["check"]["min_served_tokens"])
    seqs = [(r.prompt, outputs[id(r)]) for r in sample]
    gap, extra = float("inf"), {"served_tokens_checked": sum(len(s) for _, s in seqs)}
    if seqs:
        logits = family.served_logits(conf, seed, seqs,
                                      controls=(False, True) if control else (False,))
        served = [t for _, out in seqs for t in out]
        gap = float(np.max(reference.served_token_gaps(logits[0], served)))
        if control:
            extra["program_max_logit_gap"] = gap
            firsts = np.asarray(logits[1]).argmax(axis=-1)
            gap = float(np.max(reference.served_token_gaps(logits[0], firsts)))
    numbers = {
        "max_logit_gap": (gap, conf["check"]["max_logit_gap"]),
        "wrong_lengths": (wrong_len, 0),
    }
    return numbers, extra


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             require_tpu: bool = True, control: bool = False,
             steps: int | None = None) -> dict:
    """One run of ``cell``; ``steps`` ends the window after that many engine
    steps instead of ``seconds`` (CPU tests, whose runs must finish the
    same requests)."""
    import jax

    from repro.serving import build_engine

    conf, family = cell.config, cell.family
    compiles = CompileCount()
    marks = {"start": time.perf_counter()}
    cfg = family.model_config(conf)
    opts = serve_options(conf, profile=trace)
    params = jax.block_until_ready(family.make_params(
        conf["model"], seed, conf["dtypes"]["param"], placement(opts.tp, devices)))
    marks["weights"] = time.perf_counter()
    eng = build_engine(cfg, opts, params=params)
    del params
    jax.block_until_ready(eng.params)
    marks["engine"] = time.perf_counter()
    traffic = Traffic(load_mix(cell.mix_path), seed, conf["model"]["vocab_size"])
    loop = ClosedLoop(eng, traffic, compiles, spans=trace)
    e = conf["engine"]
    loop.warm_up(traffic.chunk_widths(e["block_size"], e["prefill_chunk"]))
    marks["warm_up"] = time.perf_counter()
    setup_compile_s = compiles.seconds

    trace_dir = tempfile.TemporaryDirectory(prefix="chip-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    win = loop.run(seconds, steps)
    setup_s = win.t_open - T_START
    marks["fill"] = win.t_open
    names = list(marks)
    setup_split = {"process": marks["start"] - T_START, "compile_or_load": setup_compile_s,
                   **{b: marks[b] - marks[a] for a, b in zip(names, names[1:])}}
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        import trace_reduce

        reduced = trace_reduce.reduce_dir(trace_dir.name)
        trace_dir.cleanup()
    stats = [d.memory_stats() or {} for d in devices[: cell.chips]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    window_counters = (win.counters0, win.counters1)

    # free the program's state before the reference runs
    outputs = {id(r): list(r.handle.output) for r in win.tracked}
    for r in win.tracked:
        r.handle = None
    del loop, eng
    gc.collect()
    t_check = time.perf_counter()
    numbers, extra = check(family, conf, seed, win, outputs, control)
    extra["check_s"] = time.perf_counter() - t_check
    # a gap that could not be read (nothing finished, or not finite) fails
    numbers = {k: (v if np.isfinite(v) else None, lim) for k, (v, lim) in numbers.items()}
    correct = all(v is not None and v <= lim for v, lim in numbers.values())

    metrics = {}
    if not trace:
        values = end_to_end(win)
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = dict(window=win, config=conf, family=family, chips=cell.chips,
                   sites=family.layer_sites(conf["model"]), trace=reduced,
                   counters=window_counters,
                   peaks=arith.peaks(devices[0].device_kind) if require_tpu else None)
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(win.finished()),
              "failed": numbers["wrong_lengths"][0], "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s(win_span=reduced.window)
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["diagnostics"] = dict(window_s=win.seconds, compiles_in_window=win.compiles,
                                 setup_split_s=setup_split, **step_shape(win), **extra)
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, (v, lim) in numbers.items()}
    return result


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        print(f"run.py: no TPU found (platform {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    if require_tpu:
        arith.peaks(dev.device_kind)  # a device not in the table is an error
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                      require_tpu=require_tpu)
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
