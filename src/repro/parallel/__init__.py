"""Mesh construction + named-axis sharding annotations (TP/DP/EP)."""
from .sharding import (  # noqa: F401
    constrain,
    current_mesh,
    expert_parallel_rules,
    make_mesh,
    param_shardings,
    pspec,
    spec_for_param,
    use_mesh,
)
