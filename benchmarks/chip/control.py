#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in one process, one run of the cell as ``run.py`` makes
it (a shorter window is enough to finish and compare as many requests),
with the float8 control (the reference with every matmul operand in
float8 e4m3) put in the program's place: over the sample of finished
requests, the tokens that the control puts first are judged by the
cell's check, which has to come out not correct.  The program's own
served tokens are read over the same sample.  The limit lies between
the program's largest reading and the control's smallest.  One JSON
line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys

import run
from cell import load_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control.py: no TPU found", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(run.ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        r = run.run_cell(cell, seed, args.seconds, False, devices, control=True)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program_max_logit_gap": r["diagnostics"]["program_max_logit_gap"],
            "control_max_logit_gap": r["check"]["max_logit_gap"]["value"],
            "limit": r["check"]["max_logit_gap"]["limit"],
            "served_tokens_checked": r["diagnostics"]["served_tokens_checked"],
            "control_correct": r["correct"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
