"""``bench/weights.py`` draws every leaf of the transformer trees a later
configuration brings (MLA with routed and shared experts; GQA), and
scales each matrix, the router and the stacked experts among them, by
its fan-in ``shape[-2]``."""
from __future__ import annotations

import math

import jax
import numpy as np
import pytest

import weights


def _tree(arch):
    from repro.models.registry import get_config, get_model
    cfg = get_config(arch).reduced()
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    return shapes, weights.make_params(shapes, 2**31 + 3)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "yi-9b"])
def test_every_leaf_is_drawn(arch):
    shapes, params = _tree(arch)
    want = jax.tree_util.tree_flatten_with_path(shapes)[0]
    got = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, sds), (_, leaf) in zip(want, got):
        assert leaf.shape == sds.shape and leaf.dtype == sds.dtype, path
        v = np.asarray(leaf, np.float32)
        assert np.isfinite(v).all() and v.std() > 0, path


def test_router_and_experts_scaled_by_fan_in():
    _, params = _tree("deepseek-v2-lite-16b")
    moe = params["blocks"]["moe"]
    leaves = {"router": moe["router"], "w_gate": moe["w_gate"],
              "w_up": moe["w_up"], "w_down": moe["w_down"],
              "shared.w_down": moe["shared"]["w_down"]}
    for name, leaf in leaves.items():
        v = np.asarray(leaf, np.float32)
        # (layers, d, experts) router; (layers, experts, in, out) experts
        assert v.std() * math.sqrt(leaf.shape[-2]) == pytest.approx(
            1.0, rel=0.1), name
    # the experts' fan-in is their input width, not the expert count
    assert moe["w_down"].shape[-2] != moe["w_down"].shape[-3]
