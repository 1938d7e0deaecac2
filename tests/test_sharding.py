"""Sharding rules + a miniature dry-run (subprocess, 16 fake devices)."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_host_mesh
from repro.parallel.sharding import param_spec


class FakeMesh:
    axis_names = ("data", "model")

    class _D:
        shape = (4, 2)
        size = 8
    devices = _D()


MESH = FakeMesh()


def test_param_rules_attention():
    assert param_spec("blocks/attn/wq", (8, 128, 256), MESH) == \
        P(None, "data", "model")
    assert param_spec("blocks/attn/wo", (8, 256, 128), MESH) == \
        P(None, "model", "data")


def test_param_rules_guard_indivisible():
    # 127 not divisible by 4 -> data axis dropped
    assert param_spec("blocks/attn/wq", (8, 127, 256), MESH) == \
        P(None, None, "model")


def test_param_rules_moe_experts():
    spec = param_spec("blocks/moe/w_gate", (8, 16, 128, 64), MESH)
    assert spec == P(None, "model", "data", None)
    spec = param_spec("blocks/moe/w_down", (8, 16, 64, 128), MESH)
    assert spec == P(None, "model", None, "data")


def test_param_rules_norms_replicated():
    assert param_spec("blocks/ln1", (8, 128), MESH) == P()
    assert param_spec("ln_f", (128,), MESH) == P()


def test_embed_vocab_parallel():
    assert param_spec("embed", (64000, 4096), MESH) == P("model", "data")
    assert param_spec("lm_head", (4096, 64000), MESH) == P("data", "model")


def test_cache_shardings_types():
    import jax.numpy as jnp
    from repro.models.attention import KVCache
    from repro.models.ssm import SSMCache
    from repro.parallel.sharding import cache_shardings
    mesh = make_host_mesh(model=1)
    # GQA stacked cache
    kv = KVCache(jax.ShapeDtypeStruct((4, 2, 64, 2, 16), jnp.bfloat16),
                 jax.ShapeDtypeStruct((4, 2, 64, 2, 16), jnp.bfloat16))
    ssm = SSMCache(jax.ShapeDtypeStruct((4, 2, 3, 128), jnp.bfloat16),
                   jax.ShapeDtypeStruct((4, 2, 8, 16, 16), jnp.float32))
    tree = ([kv], ssm)
    sh = cache_shardings(tree, mesh)
    assert sh[0][0].k.spec is not None
    assert sh[1].conv.spec is not None


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.models.registry import get_config, get_model, input_specs
    from repro.parallel import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.parallel.act_sharding import activation_sharding
    from repro.optim.adamw import AdamW, AdamWState
    from repro.train.train_step import make_train_step
    from repro.configs.base import ShapeConfig

    mesh = make_mesh((4, 4), ("data", "model"))
    cfg = get_config(%(arch)r).reduced(num_layers=2, d_model=256,
                                       num_heads=8, d_ff=512, head_dim=32)
    model = get_model(cfg)
    shape = ShapeConfig("t", 128, 8, %(kind)r)
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = shd.param_shardings(params_shape, mesh)
    if %(kind)r == "train":
        opt = AdamW()
        step_fn, _ = make_train_step(cfg, opt, mesh)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        opt_sh = AdamWState(shd.scalar_sharding(mesh), p_sh, p_sh)
        batch_shape = input_specs(cfg, shape)
        b_sh = shd.batch_shardings(batch_shape, mesh)
        with mesh, activation_sharding(mesh):
            c = jax.jit(step_fn, in_shardings=(p_sh, opt_sh, b_sh)
                        ).lower(params_shape, opt_shape, batch_shape).compile()
    else:
        cache_shape = jax.eval_shape(lambda: model.init_cache(8, 128))
        c_sh = shd.cache_shardings(cache_shape, mesh)
        tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
        tok_sh = shd.batch_shardings({"token": tok}, mesh)["token"]
        idx = jax.ShapeDtypeStruct((), jnp.int32)
        with mesh, activation_sharding(mesh):
            c = jax.jit(model.decode_step,
                        in_shardings=(p_sh, tok_sh, c_sh,
                                      shd.scalar_sharding(mesh))
                        ).lower(params_shape, tok, cache_shape, idx).compile()
    ca = c.cost_analysis()
    print("COMPILED", ca.get("flops", 0) > 0)
""")


@pytest.mark.slow
@pytest.mark.parametrize("arch,kind", [
    ("yi-9b", "train"), ("deepseek-v2-lite-16b", "train"),
    ("mamba2-1.3b", "train"), ("zamba2-1.2b", "decode"),
    ("yi-9b", "decode"),
])
def test_mini_dryrun_compiles(arch, kind):
    """The sharded step lowers+compiles on a 4x4 mesh for reduced configs."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = MINI_DRYRUN % {"arch": arch, "kind": kind}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=root, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COMPILED True" in r.stdout


def test_dryrun_results_valid_if_present():
    """Every completed dry-run cell has coherent roofline terms."""
    import json
    from pathlib import Path
    res = Path(__file__).resolve().parents[1] / "results" / "dryrun"
    if not res.exists():
        pytest.skip("dry-run sweep not executed yet")
    n_ok = 0
    for p in res.glob("*.json"):
        rec = json.loads(p.read_text())
        if rec.get("status") != "ok":
            continue
        n_ok += 1
        assert rec["hlo_flops"] > 0, p.name
        assert rec["compute_s"] > 0, p.name
        assert rec["dominant"] in ("compute_s", "memory_s", "collective_s")
        assert 0 <= rec["roofline_fraction"] <= 1.0001, p.name
    assert n_ok > 0
