"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set
XLA_FLAGS before the first jax call.
"""
from __future__ import annotations

import jax

from repro.parallel.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh((data, model), ("data", "model"))


def mesh_dims(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
