"""The yardstick's arithmetic at Yi-6B shapes, and the peaks table."""
import json
import pathlib

import pytest

import arith
from cell import load_family

DENSE = load_family("dense")
CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
PK = arith.peaks("TPU v5 lite")


def yi(layers=32):
    model = json.loads((CONFIGS / "yi-6b-posit32.json").read_text())["model"]
    return dict(model, num_hidden_layers=layers)


def sites(model):
    return DENSE.layer_sites(model)


def test_macs_per_token_at_yi_6b_widths():
    # q and o 4096x4096, k and v 4096x512, gate/up/down 4096x11008
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert per_layer == 173_015_040
    assert arith.layer_macs(sites(yi()), yi()) == 32 * per_layer
    assert arith.layer_macs(sites(yi(8)), yi(8)) == 8 * per_layer
    assert arith.head_macs(yi()) == 4096 * 64000


def test_token_flops_count_attention_over_the_keys():
    m = yi(8)
    base = arith.token_flops(sites(m), m, 0, logits=False)
    assert base == 2 * arith.layer_macs(sites(m), m)
    # QK^T and PV: 2 FLOPs per MAC, 32 heads of 128, 8 layers
    assert arith.token_flops(sites(m), m, 100, logits=False) - base == 4 * 8 * 100 * 32 * 128
    assert arith.token_flops(sites(m), m, 0, logits=True) - base == 2 * 4096 * 64000


def test_decode_matmul_is_bound_by_weight_bytes():
    k, n = 4096, 11008
    least = arith.matmul_least_s(8, k, n, PK)
    nbytes = k * n * 2 + 8 * k * 2 + 8 * n * 4
    assert least == pytest.approx(nbytes / 819e9)
    assert least > 2 * 8 * k * n / 197e12
    # a long prefill chunk is bound by the MXU peak instead
    assert arith.matmul_least_s(4096, k, n, PK) == pytest.approx(2 * 4096 * k * n / 197e12)


def test_one_decode_step_reads_the_weights_once():
    m = yi(8)
    step = arith.program_matmul_least_s(sites(m), m, 8, 8, PK)
    weights = (arith.layer_macs(sites(m), m) + arith.head_macs(m)) * 2
    assert weights / 819e9 < step < 1.01 * weights / 819e9


def test_decode_attention_bytes_count_whole_blocks():
    m = yi(8)
    one = arith.decode_attention_bytes(m, 1, 16)
    full = arith.decode_attention_bytes(m, 16, 16)
    two = arith.decode_attention_bytes(m, 17, 16)
    assert one == full
    # one more block of K and V: 16 positions x 4 kv heads x 128 x 2 x bf16, 8 layers
    assert two - full == 8 * 16 * 4 * 128 * 2 * 2


def test_a_device_missing_from_the_peaks_table_is_an_error():
    with pytest.raises(KeyError):
        arith.peaks("TPU v9 imaginary")
    assert PK["bf16_flops_per_s"] == 197e12 and PK["hbm_bytes_per_s"] == 819e9
