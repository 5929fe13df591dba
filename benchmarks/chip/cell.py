"""A cell's files: its entry in BENCHMARK.json, configuration and mix.

Everything a cell needs is found by name: the workload entry names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<mix>.json``); the configuration may name its model family
(``"family"``, ``dense`` where it names none), which is
``families/<family>.py``; each per-layer metric is
``metrics/<metric>.py``.  A new cell, and a new architecture, need only
new files and entries.

The configuration states the deployment: its ``engine`` section may give
the tensor-parallel ways ``tp`` (1 where it gives none), and the
workload's ``chips`` has to be the same number.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import types

CHIP = pathlib.Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
FAMILIES = CHIP / "families"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix_path: pathlib.Path
    end_to_end: list  # BENCHMARK.json metric entries reported with --trace 0
    per_layer: list  # ... and with --trace 1
    family: types.ModuleType  # the configuration's model family (families/<family>.py)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_config(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_path=ROOT / "BENCHMARK.json") -> Cell:
    with open(bench_path) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise SystemExit(f"unknown workload {workload!r}; known: {names}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_config(ROOT / conf["file"])
    chips, tp = int(entry["chips"]), int(config["engine"].get("tp", 1))
    if chips != tp:
        raise ValueError(f"workload {workload!r} asks for {chips} chips, but configuration "
                         f"{entry['config']!r} states tp={tp}: the two must agree")
    return Cell(
        name=workload,
        chips=chips,
        config=config,
        mix_path=CHIP / "traffic" / f"{entry['traffic']}.json",
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        family=load_family(config.get("family", "dense")),
    )


def _load_module(prefix: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(name: str, directory: pathlib.Path = FAMILIES):
    """``<directory>/<name>.py``: ``model_config(conf)``,
    ``make_params(model, seed, dtype, placement)``, ``served_logits(conf,
    seed, seqs, controls)`` and ``layer_sites(model)``."""
    path = pathlib.Path(directory) / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"model family {name!r}: no file {path}")
    return _load_module("chip_family_", path)


def load_metric(name: str):
    """``metrics/<name>.py``: its ``read(ctx)`` returns a number or None."""
    return _load_module("chip_metric_", CHIP / "metrics" / f"{name}.py")
