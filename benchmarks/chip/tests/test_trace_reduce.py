"""The trace reduction, on a small trace recorded on one TPU v5e.

``data/tiny_tpu.xplane.pb``: four engine steps of a 2-layer Yi-shaped
model under prequantized ``plam_sim`` (the Pallas PLAM matmul and the
paged decode-attention kernel), each step in an ``engine.step`` span,
with the engine's ``serve.*`` phase spans on.
"""
import pathlib

import numpy as np
import pytest

import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data" / "tiny_tpu.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_file(str(DATA))


def raw_ops():
    """Device ops straight from the file, for an independent count."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA))
    (plane,) = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    (line,) = [ln for ln in plane.lines if ln.name == "XLA Ops"]
    return [(ev.name, ev.start_ns, ev.duration_ns) for ev in line.events]


def test_one_chip_and_the_harness_spans(red):
    assert len(red.chips) == 1
    assert len(red.spans_named("engine.step", inside_window=False)) == 4
    assert red.window_s > 0


def test_kernel_time_is_the_sum_of_its_ops(red):
    want = sum(d for n, _, d in raw_ops() if n.startswith("%plam_matmul")) * 1e-9
    assert want > 0
    assert red.kernel_s(["plam_matmul"]) == pytest.approx(want)


def test_busy_is_the_union_of_op_intervals(red):
    ops = raw_ops()
    lo, hi = red.window
    grid = np.zeros(int(hi - lo) + 2, bool)  # 1-ns resolution mask
    for _, s, d in ops:
        a, b = max(int(round(s - lo)), 0), min(int(round(s + d - lo)), len(grid) - 1)
        grid[a:b] = True
    assert red.busy_s() == pytest.approx(grid.sum() * 1e-9, rel=1e-3)
    assert 0 < red.busy_s() <= red.window_s


def test_decode_programs_are_found_by_their_kernel(red):
    calls = red.program_calls(contains_kernel="paged_decode_attention_kernel")
    # four decode steps ran; with no bench.window span the window is the
    # ops' extent, which the first program's launch precedes
    assert len(calls) == 3
    assert all(c > 0 for c in calls)


def test_busy_inside_spans(red):
    spans = red.spans_named("engine.step", inside_window=False)
    inside = red.busy_in(spans)
    assert len(inside) == 4
    for (s, e), b in zip(spans, inside):
        assert 0 <= b <= (e - s) * 1e-9
    assert sum(inside) <= red.busy_s() + 1e-12


def test_breakdown_lists_leaf_ops_and_labelled_gaps(red):
    bd = red.breakdown()
    names = [n for n, _ in bd["device_ops"]]
    assert "while" not in names  # a loop holds its body's ops
    assert {"plam_matmul", "paged_decode_attention_kernel"} <= set(names)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    gaps = [g for _, g in bd["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert all(label.startswith(("engine.", "serve.", "bench.", "host:"))
               for label, _ in bd["idle_gaps"])
    assert any(label.startswith("engine.step before serve.") for label, _ in bd["idle_gaps"])


def test_busy_intervals_merge_nested_and_overlapping_ops():
    chip = trace_reduce.Chip(
        start=np.array([0.0, 2.0, 3.0, 10.0]), end=np.array([5.0, 4.0, 7.0, 12.0]),
        kind=["while", "a", "b", "c"], module=np.full(4, -1), module_names=[],
        mod_start=np.zeros(0), mod_end=np.zeros(0), mod_name=[])
    red = trace_reduce.Reduced([chip], [("engine.step", 6.0, 11.0)], (0.0, 20.0))
    np.testing.assert_array_equal(red.busy_intervals(chip), [[0, 7], [10, 12]])
    assert red.busy_s() == pytest.approx(9e-9)
    assert red.busy_in([(6.0, 11.0)]) == [pytest.approx(2e-9)]
    assert red.idle_gaps(1) == [("host:outside-spans", pytest.approx(8e-9))]
    assert red.idle_gaps(2)[1] == ("engine.step end", pytest.approx(3e-9))
