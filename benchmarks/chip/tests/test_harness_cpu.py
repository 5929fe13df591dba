"""The harness's whole run on the CPU at a tiny size, with faults.

The device check is skipped (``require_tpu=False``); Pallas runs in
interpret mode.  A sound run is correct; a run whose timed path is
broken underneath, or whose program is replaced by the float8 control,
is not.  A configuration that states ``tp`` 4 runs the same way over four
forced host devices, in a child process.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

import jax

import cell
import run
from repro.serving.engine import ContinuousBatchingEngine

TESTS = pathlib.Path(__file__).resolve().parent
BENCH = json.loads((TESTS.parents[2] / "BENCHMARK.json").read_text())
SECONDS = 2.0
# windows of a fixed number of engine steps: two runs of one seed then
# finish the same requests, whatever the CPU's speed
STEPS = 200


def tiny_cell():
    return cell.Cell("tiny", 1, cell.load_config(TESTS / "tiny_config.json"),
                     TESTS / "tiny_mix.json", BENCH["end_to_end"], [],
                     cell.load_family("dense"))


def run_tiny(seed, control=False):
    return run.run_cell(tiny_cell(), seed, SECONDS, False, jax.devices(),
                        require_tpu=False, control=control, steps=STEPS)


@pytest.fixture(scope="module")
def sound():
    return run_tiny(2**31 + 21)


@pytest.fixture(scope="module")
def controlled():
    return run_tiny(2**31 + 21, control=True)


def test_sound_run_is_correct_and_reports_every_metric(sound):
    assert sound["correct"], sound["check"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"tokens_per_s", "itl_p95_s", "ttft_p50_s", "setup_s"}
    assert list(sound)[-1] == "check"
    assert sound["diagnostics"]["compiles_in_window"] == 0
    assert sound["device"]["platform"] == "cpu"


def test_the_float8_control_fails_the_limit(sound, controlled):
    limit = controlled["check"]["max_logit_gap"]["limit"]
    assert not controlled["correct"], controlled["check"]
    assert controlled["check"]["max_logit_gap"]["value"] > limit
    # the program's own reading over the same sample is the sound run's
    program = controlled["diagnostics"]["program_max_logit_gap"]
    assert program == sound["check"]["max_logit_gap"]["value"] <= limit


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    pick = ContinuousBatchingEngine._pick_one

    def altered(self, logits_row, req, token_idx):
        tok = pick(self, logits_row, req, token_idx)
        return (tok + 1) % self.cfg.vocab if token_idx == 3 else tok

    monkeypatch.setattr(ContinuousBatchingEngine, "_pick_one", altered)
    r = run_tiny(2**31 + 22)
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > r["check"]["max_logit_gap"]["limit"]


def test_a_decode_step_that_returns_its_cache_unchanged_is_caught(monkeypatch):
    init = ContinuousBatchingEngine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        decode = self._decode

        def stale(params, token, k_pool, v_pool, tables, lengths):
            logits, _ = decode(params, token, k_pool, v_pool, tables, lengths)
            return logits, (k_pool, v_pool)

        self._decode = stale

    monkeypatch.setattr(ContinuousBatchingEngine, "__init__", patched)
    r = run_tiny(2**31 + 23)
    assert not r["correct"]


def test_no_tpu_means_no_result_line(capsys):
    rc = run.main(["--workload", "yi6b-plam16.generate", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "no TPU" in out.err


def test_a_workload_whose_chips_differ_from_its_tp_is_refused(tmp_path):
    conf = tmp_path / "conf.json"
    conf.write_text((TESTS / "tiny_tp4_config.json").read_text())
    bench = dict(BENCH, configs=[{"name": "c", "file": str(conf)}],
                 workloads=[{"name": "w", "config": "c", "traffic": "generate", "chips": 1}])
    bench_path = tmp_path / "BENCHMARK.json"
    bench_path.write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="1 chips.*tp=4"):
        cell.load_cell("w", bench_path)
    bench["workloads"][0]["chips"] = 4
    bench_path.write_text(json.dumps(bench))
    assert cell.load_cell("w", bench_path).chips == 4


TP4_CHILD = textwrap.dedent("""
    import json, pathlib, sys

    import jax
    import numpy as np

    TESTS = pathlib.Path(sys.argv[1])
    sys.path[:0] = [str(TESTS.parent), str(TESTS.parents[2] / "src")]
    import cell, run
    import repro.serving
    from repro.parallel.sharding import make_mesh, param_shardings

    bench = json.loads((TESTS.parents[2] / "BENCHMARK.json").read_text())
    conf = cell.load_config(TESTS / "tiny_tp4_config.json")
    dense = cell.load_family("dense")
    tiny = cell.Cell("tiny-tp4", 4, conf, TESTS / "tiny_mix.json", bench["end_to_end"], [],
                     dense)
    out = {"devices": len(jax.devices()), "leaves": [], "tp": []}

    # the engine's own rule on the engine's own mesh
    mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    build_engine = repro.serving.build_engine

    def watched(cfg, opts, params):
        rule = param_shardings(mesh, params)
        given = [a.sharding for a in jax.tree_util.tree_leaves(params)]
        eng = build_engine(cfg, opts, params=params)
        out["tp"].append(eng.pcfg.tp)
        held = jax.tree_util.tree_flatten_with_path(eng.params)[0]
        for (path, leaf), want, came in zip(held, jax.tree_util.tree_leaves(rule), given):
            out["leaves"].append({
                "path": jax.tree_util.keystr(path),
                "held_as_rule": leaf.sharding == want, "built_as_rule": came == want,
                "sharded": not want.is_fully_replicated,
                "whole_on_a_device": any(s.data.shape == leaf.shape
                                         for s in leaf.addressable_shards)})
        return eng

    repro.serving.build_engine = watched
    seed = 2**31 + 41
    out["runs"] = [run.run_cell(tiny, seed, 2.0, False, jax.devices(), require_tpu=False,
                                control=control, steps=int(sys.argv[2]))
                   for control in (False, True)]

    # the bits do not depend on the placement
    one = dense.make_params(conf["model"], seed, "bfloat16")
    four = dense.make_params(conf["model"], seed, "bfloat16", run.placement(4, jax.devices()))
    leaves = jax.tree_util.tree_leaves
    out["same_bits"] = all(np.array_equal(np.asarray(a), np.asarray(b))
                           for a, b in zip(leaves(one), leaves(four)))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def tp4():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    proc = subprocess.run([sys.executable, "-c", TP4_CHILD, str(TESTS), str(STEPS)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_tp4_configuration_runs_correct_over_four_devices(tp4):
    sound, _ = tp4["runs"]
    assert tp4["devices"] == 4 and tp4["tp"] == [4, 4]
    assert sound["correct"], sound["check"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["device"]["count"] == 4


def test_the_tp4_float8_control_is_not_correct(tp4):
    sound, controlled = tp4["runs"]
    gap = controlled["check"]["max_logit_gap"]
    assert not controlled["correct"] and gap["value"] > gap["limit"], gap
    assert controlled["diagnostics"]["program_max_logit_gap"] == \
        sound["check"]["max_logit_gap"]["value"]


def test_tp4_weights_are_built_and_held_in_the_engines_shardings(tp4):
    leaves = tp4["leaves"]
    assert leaves and len(leaves) % 2 == 0  # both runs' engines
    assert all(leaf["held_as_rule"] and leaf["built_as_rule"] for leaf in leaves), leaves
    sharded = [leaf for leaf in leaves if leaf["sharded"]]
    assert any("wq" in leaf["path"] for leaf in sharded)
    assert not any(leaf["whole_on_a_device"] for leaf in sharded), sharded
    assert tp4["same_bits"]
