"""Production meshes.

Functions, not module-level constants, so importing this module never
touches jax device state (per the dry-run contract)."""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_rules"]


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``NamedSharding`` and let the compiler propagate the rest (jax
    0.9 defaults to Explicit axes, which would demand sharding-typed
    operands everywhere)."""
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_rules(cfg, mesh, *, log_fallbacks: bool = False):
    """ShardingRules for a model config on a mesh (FSDP-over-pod for the
    405B-class configs, see ModelConfig.fsdp_over_pod)."""
    from repro.parallel.sharding import (ACT_RULES_LARGE, ACT_RULES_SMALL,
                                         PARAM_RULES_LARGE,
                                         PARAM_RULES_SMALL, ShardingRules)
    large = getattr(cfg, "fsdp_over_pod", False)
    act = dict(ACT_RULES_LARGE if large else ACT_RULES_SMALL)
    if getattr(cfg, "seq_shard", False):
        act["seq"] = "model"  # sequence-parallel residual activations
    return ShardingRules(
        mesh=mesh,
        act=act,
        params=PARAM_RULES_LARGE if large else PARAM_RULES_SMALL,
        log_fallbacks=log_fallbacks,
    )
