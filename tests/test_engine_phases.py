"""Host phase timing inside ``ContinuousBatchingEngine.step()``.

Every phase of a step (admission, prefill / decode / verify dispatch,
logits readback, sampling, scrub, copy-on-write) is timed on the host
into ``ServeStats.host_s`` / ``host_max_s`` and exported as
``serve_host_seconds_total{phase=...}`` / ``serve_host_max_seconds``;
with ``profile=True`` each phase is also a ``serve.<phase>`` span in a
``jax.profiler`` capture.  Phases never nest, so their seconds add up to
at most the steps' wall time.
"""
import glob

import jax
import pytest

from repro.configs.base import ModelConfig
from repro.core.modes import NumericsConfig
from repro.serving import ContinuousBatchingEngine, PagedServeConfig
from repro.serving.observability import HOST_PHASES

CFG = ModelConfig(
    name="phase-test", family="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv=1, head_dim=16, d_ff=64, vocab=64,
    numerics=NumericsConfig(mode="f32"),
    act_dtype="float32", param_dtype="float32",
)

# (prompt length, new tokens): prompts longer than the 8-token chunk are
# prefilled over several steps beside decode; a 7-token prompt ending at
# its first token leaves a padded position to scrub at retirement
MIXED = ((13, 6), (5, 3), (7, 1), (20, 4))

# case -> (engine options, phases the stream must pass through)
CASES = {
    "chunked": (
        dict(prefill_chunk=8),
        {"admit", "prefill", "decode", "readback", "sample", "scrub"},
    ),
    "whole_prompt_spec": (
        dict(spec_k=2),
        {"admit", "prefill", "verify", "readback", "sample"},
    ),
    "prefix_cache": (
        dict(prefill_chunk=8, prefix_cache=True),
        {"admit", "prefill", "decode", "readback", "sample", "cow"},
    ),
}


def _engine(profile=False, **kw):
    return ContinuousBatchingEngine(
        CFG,
        key=jax.random.PRNGKey(0),
        pcfg=PagedServeConfig(
            block_size=4, num_blocks=64, max_slots=2, max_seq_len=48,
            profile=profile, **kw,
        ),
    )


def _serve(eng, stream=MIXED):
    handles = [
        eng.submit(list(range(1, n + 1)), max_new_tokens=m, arrival_step=i)
        for i, (n, m) in enumerate(stream)
    ]
    eng.run()
    if eng.allocator.prefix_cache:
        # the same prompt again hits its own cached blocks; the last one
        # is recomputed for its logits, so it is copied before the write
        handles.append(eng.submit(list(range(1, 13)), max_new_tokens=2))
        eng.run()
    return [list(h.output) for h in handles]


@pytest.mark.parametrize("case", sorted(CASES))
def test_phase_seconds_cover_each_phase_within_step_time(case):
    opts, want = CASES[case]
    eng = _engine(**opts)
    _serve(eng)
    s = eng.stats
    assert want <= set(s.host_s) <= set(HOST_PHASES), sorted(s.host_s)
    assert set(s.host_max_s) == set(s.host_s)
    for p, total in s.host_s.items():
        assert total >= 0 and 0 <= s.host_max_s[p] <= total, p
    assert sum(s.host_s.values()) <= sum(s.step_latency_s)


def test_prometheus_exports_phase_instruments_and_no_tok_per_s():
    eng = _engine(prefill_chunk=8)
    _serve(eng)
    text = eng.metrics.to_prometheus_text()
    for phase in HOST_PHASES:
        assert f'serve_host_seconds_total{{phase="{phase}"}}' in text
        assert f'serve_host_max_seconds{{phase="{phase}"}}' in text
    assert "serve_tok_per_s" not in text
    got = eng.metrics.value("serve_host_seconds_total", phase="readback")
    assert got == eng.stats.host_s["readback"] > 0
    assert eng.metrics.value("serve_host_max_seconds", phase="sample") == (
        eng.stats.host_max_s["sample"])


@pytest.mark.parametrize("case", sorted(CASES))
def test_greedy_tokens_identical_with_profile_on_and_off(case):
    opts, _ = CASES[case]
    off = _serve(_engine(profile=False, **opts))
    on = _serve(_engine(profile=True, **opts))
    assert on == off
    assert all(out for out in off)


def test_profiler_capture_holds_phase_spans(tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(profile=True, prefill_chunk=8)
    _serve(eng, MIXED[:2])  # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    (host,) = [p for p in pd.planes if p.name == "/host:CPU"]
    names = {ev.name for line in host.lines for ev in line.events}
    assert {"serve.admit", "serve.prefill", "serve.decode", "serve.readback",
            "serve.sample"} <= names


def test_serve_launcher_profile_dir_captures_the_phases(tmp_path, monkeypatch, capsys):
    import sys

    from jax.profiler import ProfileData

    from repro.launch import serve

    argv = ["serve", "--arch", "yi-6b", "--reduced", "--continuous", "--batch", "2",
            "--prompt-len", "8", "--new-tokens", "3", "--profile-dir", str(tmp_path)]
    assert serve.options_from_args(serve.make_parser().parse_args(argv[1:])).profile
    monkeypatch.setattr(sys, "argv", argv)
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda *a, **k: None)
    serve.main()
    assert "engine=continuous" in capsys.readouterr().out
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    names = {ev.name for p in pd.planes if p.name == "/host:CPU"
             for line in p.lines for ev in line.events}
    assert {"serve.prefill", "serve.decode", "serve.readback", "serve.sample"} <= names
