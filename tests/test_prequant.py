"""Prequantized posit weight storage: quantize_params -> nmatmul
pattern path -> serving engines -> checkpoint round trip."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.core.modes import NumericsConfig, nmatmul
from repro.core.prequant import dequantize_params, param_role, quantize_params
from repro.models import build
from repro.numerics import PositSpec, encode, pack16, quantize

DENSE = dict(family="dense", n_layers=2, d_model=32, n_heads=2, n_kv=2,
             head_dim=16, d_ff=64, vocab=50)
MOE = dict(family="moe", n_layers=2, d_model=32, n_heads=2, n_kv=2,
           head_dim=16, d_ff=64, vocab=50, n_experts=4, top_k=2,
           moe_d_ff=32, n_shared_experts=1)


def test_param_role_mapping():
    assert param_role("layers/attn/wq") == "attn.qkv"
    assert param_role("layers/attn/wo") == "attn.out"
    assert param_role("layers/mlp/wg") == "mlp.gate"
    assert param_role("layers/moe/router") == "moe.router"
    assert param_role("layers/moe/wd") == "moe.expert.down"
    assert param_role("layers/moe/shared/wu") == "moe.shared.up"
    assert param_role("layers/mamba/in_proj") == "ssm.proj.in"
    assert param_role("dec_layers/xattn/wq") == "attn.cross.qkv"
    assert param_role("unembed") == "lm_head"
    assert param_role("embed") is None
    assert param_role("layers/ln1/scale") is None
    assert param_role("layers/mamba/conv_w") is None


def test_pattern_nmatmul_matches_linear_paths():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    spec = PositSpec(16, 1)
    bits = pack16(encode(w, spec))
    # posit_quant: decoded patterns are exactly quantize(w) -> bit-equal
    pq = NumericsConfig(mode="posit_quant", n=16, es=1)
    assert np.array_equal(np.asarray(nmatmul(x, w, pq)),
                          np.asarray(nmatmul(x, bits, pq)))
    # plam_sim: kernel tiling reorders the f32 accumulation -> allclose
    pl = NumericsConfig(mode="plam_sim", n=16, es=1)
    a, b = np.asarray(nmatmul(x, w, pl)), np.asarray(nmatmul(x, bits, pl))
    assert np.allclose(a, b, rtol=1e-5, atol=1e-5)


def test_quantize_params_selects_posit_sites_only():
    cfg = ModelConfig(**MOE).with_numerics(
        "default=plam_sim:16:1, attn=posit_quant:16:1, lm_head=f32")
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    pq, meta = quantize_params(cfg, params)
    assert pq["layers"]["attn"]["wq"].dtype == jnp.int16
    assert pq["layers"]["moe"]["wu"].dtype == jnp.int16
    # f32 sites and non-matmul params stay linear
    assert pq["layers"]["moe"]["router"].dtype == jnp.float32
    assert pq["unembed"].dtype == jnp.float32
    assert pq["embed"].dtype == jnp.float32
    assert meta["layers/attn/wq"] == {
        "role": "attn.qkv", "mode": "posit_quant", "n": 16, "es": 1}
    # dequantize recovers the posit-grid values
    deq = dequantize_params(pq, meta)
    grid = quantize(params["layers"]["attn"]["wq"], PositSpec(16, 1))
    assert np.array_equal(np.asarray(deq["layers"]["attn"]["wq"]),
                          np.asarray(grid))


def test_layer_mixed_site_not_prequantized():
    """A site whose spec differs across layers cannot share one packed
    array: it must stay linear."""
    cfg = ModelConfig(**DENSE).with_numerics(
        "default=plam_sim:16:1, mlp@layers[0]=plam_sim:8:0")
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    pq, meta = quantize_params(cfg, params)
    assert pq["layers"]["mlp"]["wu"].dtype == jnp.float32
    assert "layers/mlp/wu" not in meta
    # attn is layer-uniform -> still quantized
    assert pq["layers"]["attn"]["wq"].dtype == jnp.int16


def test_engine_prequantize_token_identical_posit_quant():
    """posit_quant decode-of-patterns == quantize-on-read, so greedy
    generation is token-identical with and without prequantization."""
    from repro.serving.engine import Engine, ServeConfig

    cfg = ModelConfig(**DENSE, numerics=NumericsConfig(mode="posit_quant"))
    prompts = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, 50, (2, 8)).astype(np.int32))}
    scfg = ServeConfig(max_new_tokens=4)
    a = Engine(cfg, key=jax.random.PRNGKey(0)).generate(prompts, scfg)
    b = Engine(cfg, key=jax.random.PRNGKey(0), prequantize=True).generate(
        prompts, scfg)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_paged_engine_serves_prequantized_plam():
    from repro.serving.engine import ContinuousBatchingEngine, PagedServeConfig

    cfg = ModelConfig(**MOE).with_numerics("default=plam_sim:16:1, lm_head=f32")
    eng = ContinuousBatchingEngine(
        cfg, key=jax.random.PRNGKey(0),
        pcfg=PagedServeConfig(block_size=8, num_blocks=32, max_slots=2,
                              max_seq_len=32, prequantize=True))
    assert eng.params["layers"]["moe"]["wu"].dtype == jnp.int16
    assert eng.prequant_meta
    r = eng.submit(list(range(1, 9)), max_new_tokens=4)
    done = eng.run()
    assert len(done[r.rid]) == 4


def test_prequantized_checkpoint_round_trip(tmp_path):
    from repro.train import checkpoint as ckpt

    cfg = ModelConfig(**DENSE).with_numerics("default=plam_sim:16:1")
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    pq, meta = quantize_params(cfg, params)
    extra = dict(ckpt.policy_extra(cfg.numerics), prequant=meta)
    ckpt.save(str(tmp_path), 0, pq, extra=extra)
    restored, manifest = ckpt.restore(str(tmp_path), pq)
    assert manifest["extra"]["prequant"] == meta
    same = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b))
        and a.dtype == b.dtype,
        pq, restored)
    assert all(jax.tree.leaves(same))
    # restored patterns still serve
    logits, _ = api.prefill(restored, {"tokens": jnp.zeros((1, 8), jnp.int32)})
    assert np.isfinite(np.asarray(logits)).all()


_TP_PLAM_SCRIPT = textwrap.dedent("""
    import sys
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.core.modes import NumericsConfig
    from repro.serving import ContinuousBatchingEngine, PagedServeConfig

    tp = int(sys.argv[1])
    assert len(jax.devices()) >= tp, jax.devices()
    # Yi-6B's dtypes and head layout (32 q heads over 4 kv heads, so the
    # decode kernel is head-sharded at tp=4); vocab 61 is odd, so the
    # lm_head takes the replicated-weight branch
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
        n_heads=32, n_kv=4, head_dim=2, d_ff=64, vocab=61,
        numerics=NumericsConfig(mode="plam_sim", n=16, es=1),
        act_dtype="bfloat16", param_dtype="bfloat16")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 61, n).tolist() for n in (3, 9)]

    def stream(tp):
        eng = ContinuousBatchingEngine(cfg, key=jax.random.PRNGKey(0),
            pcfg=PagedServeConfig(block_size=4, num_blocks=64, max_slots=3,
                                  max_seq_len=32, tp=tp, prequantize=True))
        reqs = [eng.submit(p, max_new_tokens=5, arrival_step=i)
                for i, p in enumerate(prompts)]
        done = eng.run()
        return [done[r.rid] for r in reqs], eng

    base, _ = stream(1)
    got, eng = stream(tp)
    wo = eng.params["layers"]["attn"]["wo"]
    assert wo.dtype == jnp.int16, wo.dtype
    # kernel-served pattern weights are column-sharded, whatever their
    # Megatron role
    assert tuple(wo.sharding.spec)[-1] == "model", wo.sharding.spec
    assert base == got, f"tp{tp} prequantized plam diverged: {base} vs {got}"
    print("TP-PLAM-IDENTICAL-OK")
""")


def _run_tp_plam(tp: int):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    src_dir = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src_dir) + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _TP_PLAM_SCRIPT, str(tp)],
                          env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "TP-PLAM-IDENTICAL-OK" in proc.stdout


@pytest.mark.slow
def test_tp2_prequantized_plam_token_identical_forced_devices():
    """Prequantized plam_sim serves through the Pallas PLAM kernel, which
    is shard_mapped over output columns under tensor parallelism: tp=2
    is greedy-token-identical to tp=1 in bf16.  Runs in a subprocess
    because the forced device count must be set before jax initializes."""
    _run_tp_plam(2)


@pytest.mark.slow
def test_tp4_prequantized_plam_token_identical_forced_devices():
    """As the tp=2 case, with the decode-attention kernel head-sharded
    (kv=1 per device), the layout Yi-6B serves with on four chips."""
    _run_tp_plam(4)
