"""The engine-phase metrics: device-idle milliseconds per engine step
inside the engine's ``serve.*`` spans.

``data/tiny_tpu_phases.xplane.pb``: three engine steps of a 2-layer
Yi-shaped model under prequantized ``plam_sim`` on one TPU v5e, with
both slots decoding, recorded with ``profile=True``: each step in an
``engine.step`` span inside a ``bench.window`` span, as the harness
records them, and the engine's phase spans (``serve.admit``,
``serve.decode``, ``serve.readback``, ``serve.sample``) inside.
"""
import pathlib

import numpy as np
import pytest

import trace_reduce
from cell import load_metric

DATA = pathlib.Path(__file__).resolve().parent / "data"
METRICS = ("engine.readback_ms_per_step", "engine.sample_ms_per_step",
           "engine.dispatch_ms_per_step")


def synthetic():
    """Two engine steps; the device is busy in [10, 70] and [110, 170] ns."""
    chip = trace_reduce.Chip(
        start=np.array([10.0, 30.0, 110.0]), end=np.array([40.0, 70.0, 170.0]),
        kind=["a", "b", "c"], module=np.full(3, -1), module_names=[],
        mod_start=np.zeros(0), mod_end=np.zeros(0), mod_name=[])
    spans = []
    for base in (0.0, 100.0):
        spans += [("engine.step", base, base + 100), ("serve.admit", base + 1, base + 5),
                  ("serve.decode", base + 5, base + 20),
                  ("serve.readback", base + 20, base + 80),
                  ("serve.sample", base + 80, base + 95)]
    spans.append(("serve.scrub", 96.0, 99.0))  # a second dispatch span in step 1
    return trace_reduce.Reduced([chip], sorted(spans, key=lambda s: s[1]), (0.0, 200.0))


@pytest.mark.parametrize("name,idle_ns", [
    ("engine.readback_ms_per_step", 10 + 10),  # [70, 80] and [170, 180]
    ("engine.sample_ms_per_step", 15 + 15),
    ("engine.dispatch_ms_per_step", (5 + 3) + 5),  # decode [5, 10] and the scrub; [105, 110]
])
def test_idle_inside_the_spans_per_engine_step(name, idle_ns):
    got = load_metric(name).read({"trace": synthetic()})
    assert got == pytest.approx(1e3 * idle_ns * 1e-9 / 2)


def test_the_phases_are_parts_of_the_host_time_per_step():
    ctx = {"trace": synthetic()}
    whole = load_metric("engine.host_ms_per_step").read(ctx)
    parts = sum(load_metric(n).read(ctx) for n in METRICS)
    assert 0 < parts < whole  # the rest is serve.admit's idle time


def test_a_trace_without_the_spans_reads_nothing():
    red = trace_reduce.Reduced([], [("engine.step", 0.0, 10.0)], (0.0, 10.0))
    assert all(load_metric(n).read({"trace": red}) is None for n in METRICS)
    red = trace_reduce.Reduced([], [("serve.readback", 0.0, 10.0)], (0.0, 10.0))
    assert load_metric("engine.readback_ms_per_step").read({"trace": red}) is None


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce_file(str(DATA / "tiny_tpu_phases.xplane.pb"))


def test_recorded_trace_reads_every_phase_metric(recorded):
    steps = recorded.spans_named("engine.step")
    assert len(steps) == 3 and len(recorded.chips) == 1
    ctx = {"trace": recorded}
    values = {n: load_metric(n).read(ctx) for n in METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert sum(values.values()) <= load_metric("engine.host_ms_per_step").read(ctx)


def test_recorded_idle_gaps_are_put_down_to_phases(recorded):
    gaps = recorded.breakdown()["idle_gaps"]
    labels = [label for label, _ in gaps]
    assert "engine.step end" not in labels
    assert any(label.startswith("serve.") for label in labels)
    inside = [g for label, g in gaps if label.startswith("engine.step")]
    assert sum(inside) < 0.1 * sum(g for _, g in gaps)
