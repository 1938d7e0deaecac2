"""Mesh construction.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (multi-device tests set XLA_FLAGS before first jax init).

Every mesh is built with ``AxisType.Auto`` axes: the activation-sharding
hints (``parallel.act_sharding``) are ``with_sharding_constraint`` calls,
which only accept Auto axes, and ``jax.make_mesh`` otherwise defaults to
Explicit ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

BATCH_AXES = ("pod", "data")     # axes that shard the global batch


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (the one mesh builder)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """A tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in BATCH_AXES if a in mesh.axis_names)
