"""The harness's whole run on the CPU at a tiny size, with faults.

The device check is skipped (``require_tpu=False``); Pallas runs in
interpret mode.  A sound run is correct; a run whose timed path is
broken underneath, or whose program is replaced by the float8 control,
is not.
"""
import json
import pathlib

import pytest

import jax

import cell
import run
from repro.serving.engine import ContinuousBatchingEngine

TESTS = pathlib.Path(__file__).resolve().parent
BENCH = json.loads((TESTS.parents[2] / "BENCHMARK.json").read_text())
SECONDS = 2.0


def tiny_cell():
    return cell.Cell("tiny", 1, cell.load_config(TESTS / "tiny_config.json"),
                     TESTS / "tiny_mix.json", BENCH["end_to_end"], [],
                     cell.load_family("dense"))


def run_tiny(seed, control=False):
    return run.run_cell(tiny_cell(), seed, SECONDS, False, jax.devices(),
                        require_tpu=False, control=control)


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
