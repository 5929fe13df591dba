"""End-to-end dry-run pipeline test at CI scale.

Runs in a subprocess with 8 virtual XLA host devices (the flag must be
set before jax initializes, and pytest's process already has 1 device),
builds a (2 data x 4 model) mesh, and lowers+compiles a sharded train
step and decode step for reduced configs of three families.  This is
the same code path as the 512-chip production dry-run.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.launch.dryrun import build_cell
from repro.launch.hlo_analysis import analyze
from repro.parallel.sharding import make_mesh, use_mesh

results = {}
mesh = make_mesh((2, 4), ("data", "model"))
for arch in ["yi-6b", "granite-moe-1b-a400m", "mamba2-780m"]:
    cfg = get_config(arch).reduced()
    shape = ShapeSpec("ci", seq_len=64, global_batch=4, kind="train")
    with use_mesh(mesh):
        step, args, shardings = build_cell(cfg, shape, mesh)
        compiled = jax.jit(step, in_shardings=shardings).lower(*args).compile()
    ana = analyze(compiled.as_text())
    results[arch] = {
        "flops": ana.flops,
        "collective_total": ana.collective_total,
        "mem": compiled.memory_analysis().temp_size_in_bytes,
    }

# decode path for the dense family
cfg = get_config("yi-6b").reduced()
shape = ShapeSpec("ci-dec", seq_len=64, global_batch=4, kind="decode")
with use_mesh(mesh):
    step, args, shardings = build_cell(cfg, shape, mesh)
    compiled = jax.jit(step, in_shardings=shardings).lower(*args).compile()
results["yi-6b-decode"] = {"ok": True}
print("RESULT " + json.dumps(results))
"""


@pytest.mark.slow
def test_dryrun_pipeline_on_8_virtual_devices():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("RESULT "))
    results = json.loads(line[len("RESULT "):])
    for arch in ["yi-6b", "granite-moe-1b-a400m", "mamba2-780m"]:
        assert results[arch]["flops"] > 0, results
        # data-parallel gradient reduction must appear
        assert results[arch]["collective_total"] > 0, results
    assert results["yi-6b-decode"]["ok"]
