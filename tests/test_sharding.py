"""Sharding rules: parameter specs, sanitization, logical-axis mapping."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import (
    constrain,
    param_shardings,
    pspec,
    sanitize,
    spec_for_param,
    make_mesh,
    use_mesh,
)


def test_spec_rules():
    assert spec_for_param("layers/attn/wq", 3) == (None, None, "model")
    assert spec_for_param("layers/attn/wo", 3) == (None, "model", None)
    assert spec_for_param("embed", 2) == ("model", None)
    assert spec_for_param("unembed", 2) == (None, "model")
    assert spec_for_param("layers/mlp/wd", 3) == (None, "model", None)
    assert spec_for_param("layers/moe/wu", 4) == (None, None, None, "model")
    assert spec_for_param("layers/moe/router", 3) == (None, None, None)
    assert spec_for_param("layers/mamba/in_proj", 3) == (None, None, "model")
    assert spec_for_param("ln_f/scale", 1) == (None,)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_sanitize_drops_indivisible():
    mesh = make_mesh((1, 2), ("data", "model")) if len(jax.devices()) >= 2 \
        else _mesh()
    msz = mesh.shape["model"]
    dims = sanitize(mesh, ("model", None), (49155, 64))
    if msz > 1:
        assert dims == (None, None)  # 49155 % 2 != 0
    dims2 = sanitize(mesh, ("model", None), (49152, 64))
    assert dims2 == ("model", None)


def test_pspec_resolution():
    mesh = _mesh()
    assert pspec(mesh, ("batch", None, "model")) == P("data", None, "model")
    # pod axis absent from this mesh -> batch maps to data only
    assert pspec(mesh, ("seq",)) == P("data")


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = constrain(x, "batch", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_constrain_under_mesh_compiles():
    mesh = _mesh()
    with use_mesh(mesh):
        @jax.jit
        def f(x):
            return constrain(x * 2, "batch", None)

        out = f(jnp.ones((8, 8)))
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_param_shardings_tree():
    mesh = _mesh()
    params = {
        "embed": jnp.zeros((64, 16)),
        "layers": {"attn": {"wq": jnp.zeros((2, 16, 32))}},
        "ln_f": {"scale": jnp.zeros((16,))},
    }
    sh = param_shardings(mesh, params)
    assert sh["embed"].spec == P("model", None)
    assert sh["layers"]["attn"]["wq"].spec == P(None, None, "model")
    assert sh["ln_f"]["scale"].spec == P(None)


def test_zero1_optimizer_state_shardings():
    from repro.optim.optimizers import OptConfig, state_shardings

    mesh = _mesh()
    params = {"layers": {"mlp": {"wu": jnp.zeros((16, 64, 128))}}}
    sh = state_shardings(OptConfig(name="adamw"), mesh, params)
    # m/v inherit TP spec + leading (divisible) dim sharded over data
    spec = sh["m"]["layers"]["mlp"]["wu"].spec
    assert spec == P("data", None, "model")
