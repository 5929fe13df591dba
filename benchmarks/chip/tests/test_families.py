"""Model families, found by the name a configuration gives.

The dense family keeps the bits that the Yi-shaped harness had before
families were split out of it, so that the cells measured with it read
the same: the weights, the reference's logits (and its float8
control's) and the yardstick's arithmetic on ``tiny_config.json``,
pinned here.  A second family, Minitron's squared-ReLU layer, lives
only as a file under ``data/families/`` and runs through the whole
harness on the CPU.
"""
import hashlib
import json
import pathlib

import numpy as np
import pytest

import jax

import arith
import cell
import run
from repro.configs.base import ModelConfig
from repro.core.policy import parse_cfg_spec, parse_policy

TESTS = pathlib.Path(__file__).resolve().parent
CHIP = TESTS.parent
BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
TINY = cell.load_config(TESTS / "tiny_config.json")
DENSE = cell.load_family("dense")
RELU2 = cell.load_family("relu2", TESTS / "data" / "families")
SEED = 2**31 + 7


def sha256(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def test_dense_weights_keep_their_bits():
    tree = DENSE.make_params(TINY["model"], 2**31 + 5, "bfloat16")
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == "83d2b40b6c6c58b0ca08ff90a8b722469e98084e228a41312059d3defb0d5884"


@pytest.mark.parametrize("mode,program,control", [
    ("plam_sim", "ed4ec821af54f23f8a543d558807c1d9038672117686fb04aa25e03da184a852",
     "efbbc32ebb5d37df40daf8e31ba7e718f62207a053de6e94a47cd41132cdb598"),
    ("posit_quant", "333ab8d240dd52b0724b06359c41c0ad194837e011eb2ac61b6083dc10f64da8",
     "3421bca7257a4b398f704dc3bbc816c59d793ac14f78f7cbb1604ac405fc6617"),
])
def test_dense_reference_logits_keep_their_bits(mode, program, control):
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(0, 256, 20).tolist(), rng.integers(0, 256, 6).tolist()),
            (rng.integers(0, 256, 37).tolist(), rng.integers(0, 256, 11).tolist())]
    conf = dict(TINY, numerics=dict(TINY["numerics"], mode=mode))
    logits = DENSE.served_logits(conf, SEED, seqs, controls=(False, True))
    assert [np.asarray(x).shape for x in logits] == [(17, 256), (17, 256)]
    assert [sha256([x]) for x in logits] == [program, control]


def test_dense_arithmetic_keeps_its_numbers():
    model, pk = TINY["model"], arith.peaks("TPU v5 lite")
    sites = DENSE.layer_sites(model)
    assert sites == [(64, 64), (64, 32), (64, 32), (64, 64), (64, 128), (64, 128), (128, 64)]
    assert arith.layer_macs(sites, model) == 73728
    assert arith.token_flops(sites, model, 0, False) == 147456
    assert arith.token_flops(sites, model, 37, True) == 199168
    assert arith.program_matmul_least_s(sites, model, 2, 2, pk) == 2.3787057387057388e-07
    assert arith.program_matmul_least_s(sites, model, 32, 1, pk) == 4.615189255189255e-07


@pytest.mark.parametrize("name", ["yi-6b-plam16", "yi-6b-posit32"])
def test_each_benchmark_configuration_builds_the_yi_decoder(name):
    (entry,) = [c for c in BENCH["configs"] if c["name"] == name]
    workload = next(w["name"] for w in BENCH["workloads"] if w["config"] == name)
    c = cell.load_cell(workload)
    assert "family" not in c.config  # dense by default
    m, policy = c.config["model"], c.config["numerics"]["policy"]
    want = ModelConfig(
        name=name, family="dense", n_layers=m["num_hidden_layers"], d_model=4096,
        n_heads=32, n_kv=4, head_dim=128, d_ff=11008, vocab=64000, act="silu", glu=True,
        rope_theta=5e6, tie_embeddings=False,
        numerics=parse_policy(policy) if "=" in policy else parse_cfg_spec(policy),
        param_dtype="bfloat16", act_dtype="bfloat16",
    )
    assert c.family.model_config(c.config) == want
    assert c.config == cell.load_config(CHIP.parents[1] / entry["file"])


def test_a_missing_family_is_named_with_the_file_looked_for(tmp_path):
    conf = tmp_path / "conf.json"
    conf.write_text(json.dumps(dict(TINY, family="no-such-family")))
    bench = dict(BENCH, configs=[{"name": "c", "file": str(conf)}],
                 workloads=[{"name": "w", "config": "c", "traffic": "generate", "chips": 1}])
    bench_path = tmp_path / "BENCHMARK.json"
    bench_path.write_text(json.dumps(bench))
    with pytest.raises(FileNotFoundError) as err:
        cell.load_cell("w", bench_path)
    assert str(cell.FAMILIES / "no-such-family.py") in str(err.value)


def relu2_cell():
    return cell.Cell("tiny-relu2", 1, cell.load_config(TESTS / "tiny_relu2_config.json"),
                     TESTS / "tiny_mix.json", BENCH["end_to_end"], [], RELU2)


def test_a_family_file_gives_its_own_layer():
    model = TINY["model"]
    assert RELU2.model_config(dict(TINY, name="t")).glu is False
    assert len(RELU2.layer_sites(model)) == 6
    assert (arith.layer_macs(RELU2.layer_sites(model), model)
            == arith.layer_macs(DENSE.layer_sites(model), model) - 2 * 64 * 128)
    tree = RELU2.make_params(model, 3, "bfloat16")
    assert set(tree["layers"]["mlp"]) == {"wu", "wd"}


@pytest.fixture(scope="module")
def relu2_runs():
    seed = 2**31 + 31
    return [run.run_cell(relu2_cell(), seed, 2.0, False, jax.devices(),
                         require_tpu=False, control=control) for control in (False, True)]


def test_a_family_added_as_a_file_runs_correct(relu2_runs):
    sound, _ = relu2_runs
    assert sound["correct"], sound["check"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["diagnostics"]["served_tokens_checked"] > 0


def test_its_float8_control_is_not_correct(relu2_runs):
    _, controlled = relu2_runs
    gap = controlled["check"]["max_logit_gap"]
    assert not controlled["correct"] and gap["value"] > gap["limit"], gap
    # the program's own tokens over the control's sample are still within the limit
    assert controlled["diagnostics"]["program_max_logit_gap"] <= gap["limit"]
