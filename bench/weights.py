"""Weights made by the benchmark from ``--seed``: one jitted call on the
device, in the dtypes the served model stores, laid out as the model's
parameter tree (its shapes come from ``jax.eval_shape`` of the model's
init).  The values are the benchmark's own, so the reference and the
program read the same numbers and neither made them.

Leaves are drawn by name:

* ``ln``, ``ln1``, ``ln2``, ``ln_f``, ``norm_w`` (RMSNorm gains):
  1 + 0.1 N(0, 1);
* ``A_log``: log U(1, 16) (Mamba2's decay rates);
* ``dt_bias``: softplus^-1 of a step size log-uniform in [1e-3, 1e-1];
* ``D``: U(0.5, 1.5);
* ``conv_w``: 0.2 N(0, 1); ``conv_b``: 0.05 N(0, 1);
* ``embed``: 0.02 N(0, 1);
* every other leaf is a matrix ``(..., in, out)``: N(0, 1) / sqrt(in).
  Leading axes are stacks: layers, and the experts of a mixture, so a
  router ``(layers, d, experts)`` and the stacked experts ``(layers,
  experts, in, out)`` are scaled by their fan-in ``shape[-2]``
  (``bench/tests/test_weights.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_GAINS = {"ln", "ln1", "ln2", "ln_f", "norm_w"}


def _leaf_name(path) -> str:
    for p in reversed(path):
        key = getattr(p, "key", None)
        if isinstance(key, str):
            return key
    raise ValueError(f"parameter leaf without a name: {path}")


def _draw(name: str, shape, dtype, key):
    f32 = jnp.float32
    if name in _GAINS:
        v = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    elif name == "A_log":
        v = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "D":
        v = jax.random.uniform(key, shape, f32, 0.5, 1.5)
    elif name == "conv_w":
        v = 0.2 * jax.random.normal(key, shape, f32)
    elif name == "conv_b":
        v = 0.05 * jax.random.normal(key, shape, f32)
    elif name == "embed":
        return (0.02 * jax.random.normal(key, shape, dtype)).astype(dtype)
    else:
        if len(shape) < 2:
            raise ValueError(f"leaf {name!r} of shape {shape} has no rule")
        return (jax.random.normal(key, shape, dtype)
                * (1.0 / math.sqrt(shape[-2]))).astype(dtype)
    return v.astype(dtype)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_params(shapes, seed: int):
    """A parameter tree shaped like ``shapes`` (a tree of
    ``jax.ShapeDtypeStruct``), filled from ``seed`` on the device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = [_draw(_leaf_name(path), sds.shape, sds.dtype,
                        jax.random.fold_in(key, i))
                  for i, (path, sds) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
