"""Every per-layer metric's reading of the two recorded TPU traces.

Each metric file reads the recorded trace beside one fixed window of
steps, the tiny configuration and the v5e peaks; the readings are
pinned, so a change to the harness that moves what a metric reads from
the same trace shows here.  The readings are of one chip; over four, the
shares of a peak read a quarter of them.  ``data/tiny_tpu*.xplane.pb`` hold no
prefill-chunk program, so the prefill metric reads nothing from them.
"""
import json
import pathlib

import pytest

import arith
import trace_reduce
from cell import load_family, load_metric
from loop import StepWork, Window

TESTS = pathlib.Path(__file__).resolve().parent
DATA = TESTS / "data"
BENCH = json.loads((TESTS.parents[2] / "BENCHMARK.json").read_text())

READINGS = {
    "tiny_tpu": {
        "device.idle_share": 99.85357578607915,
        "engine.dispatch_ms_per_step": 89.49047999999999,
        "engine.host_ms_per_step": 91.24775600000001,
        "engine.readback_ms_per_step": None,
        "engine.sample_ms_per_step": None,
        "model_step.decode_ms": 0.065264,
        "model_step.mfu": 1.0034680203045686e-05,
        "model_step.prefill_ms_per_token": None,
        "paged_decode_attention_roofline": 0.09674269289757238,
        "plam_matmul_roofline": 1.8114613003880462,
        "scheduler.batch_occupancy": 87.5,
    },
    "tiny_tpu_phases": {
        "device.idle_share": 97.98363543507126,
        "engine.dispatch_ms_per_step": 1.0652930000000003,
        "engine.host_ms_per_step": 3.192944666666667,
        "engine.readback_ms_per_step": 2.008118,
        "engine.sample_ms_per_step": 0.03765333333333334,
        "model_step.decode_ms": 0.06513866666666666,
        "model_step.mfu": 1.0034680203045686e-05,
        "model_step.prefill_ms_per_token": None,
        "paged_decode_attention_roofline": 0.12899534013781655,
        "plam_matmul_roofline": 2.4153565474343566,
        "scheduler.batch_occupancy": 87.5,
    },
}


def window():
    steps = [StepWork(0.0, 0.1, (0, 32, 40), [21, 30]), StepWork(0.1, 0.2, (32, 8, 40), [22, 31]),
             StepWork(0.2, 0.3, None, [23, 32, 41]), StepWork(0.3, 0.4, None, [24])]
    return Window(0.0, 0.4, [], steps, dict(decode_steps=3, active_slot_steps=5),
                  dict(decode_steps=7, active_slot_steps=12), 0)


@pytest.fixture(scope="module")
def contexts():
    conf = json.loads((TESTS / "tiny_config.json").read_text())
    win = window()
    dense = load_family("dense")
    return {name: dict(window=win, config=conf, family=dense, chips=1,
                       sites=dense.layer_sites(conf["model"]),
                       trace=trace_reduce.reduce_file(str(DATA / f"{name}.xplane.pb")),
                       counters=(win.counters0, win.counters1),
                       peaks=arith.peaks("TPU v5 lite"))
            for name in READINGS}


def test_every_benchmark_metric_has_a_pinned_reading():
    assert {m["name"] for m in BENCH["per_layer"]} == set(READINGS["tiny_tpu"])


@pytest.mark.parametrize("trace,metric", [(t, m) for t in READINGS for m in READINGS[t]])
def test_metric_reads_its_pinned_value(contexts, trace, metric):
    assert load_metric(metric).read(contexts[trace]) == READINGS[trace][metric]


PEAK_SHARES = ("model_step.mfu", "plam_matmul_roofline", "paged_decode_attention_roofline")


@pytest.mark.parametrize("trace,metric", [(t, m) for t in READINGS for m in PEAK_SHARES])
def test_shares_of_a_peak_over_four_chips_read_a_quarter(contexts, trace, metric):
    four = dict(contexts[trace], chips=4)
    assert load_metric(metric).read(four) == READINGS[trace][metric] / 4
