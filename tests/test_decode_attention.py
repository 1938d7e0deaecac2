"""Sharded flash-decode attention == dense decode (multi-device subprocess)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GQA_CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.registry import get_config, get_model
    from repro.launch.mesh import make_mesh
    from repro.parallel.act_sharding import activation_sharding
    from dataclasses import replace

    mesh = make_mesh((2, 4), ("data", "model"))
    base = get_config(%(arch)r).reduced(dtype="float32", attn_impl="full")
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, base.vocab_size, (2, 8)))

    outs = {}
    for mode in ("dense", "sharded", "grouped"):
        prec = "bf16_grouped" if mode == "grouped" else "f32"
        cfg = replace(base, decode_attn="sharded" if mode == "grouped"
                      else mode, decode_attn_precision=prec)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        state = model.init_cache(2, 16)
        step = jax.jit(model.decode_step)
        seq = []
        ctx = activation_sharding(mesh) if mode != "dense" else None
        import contextlib
        with mesh, (ctx or contextlib.nullcontext()):
            for i in range(8):
                lg, state = step(params, toks[:, i:i+1], state, jnp.int32(i))
                seq.append(np.asarray(lg[:, 0], np.float32))
        outs[mode] = np.stack(seq)
    scale = np.abs(outs["dense"]).max()
    for mode in ("sharded", "grouped"):
        diff = np.abs(outs["dense"] - outs[mode]).max()
        assert diff / scale < 2e-4, (mode, diff, scale)
    print("OK")
""")


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["yi-9b", "deepseek-v2-lite-16b"])
def test_sharded_decode_matches_dense(arch):
    """Flash-decode shard_map path == dense path, teacher-forced 8 steps.

    yi-9b: GQA path; deepseek-v2-lite: MLA compressed-cache path.
    Reduced configs have kv heads < model axis -> caches are seq-sharded,
    exactly the production regime the optimization targets.
    """
    code = GQA_CODE % {"arch": arch}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


PAGED_CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.registry import get_config, get_model
    from repro.launch.mesh import make_mesh
    from repro.parallel.act_sharding import activation_sharding
    from dataclasses import replace
    import contextlib

    mesh = make_mesh((2, 4), ("data", "model"))
    base = get_config(%(arch)r).reduced(dtype="float32", attn_impl="full")
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, base.vocab_size, (2, 8)))
    # 2 rows x 4 blocks of 4 tokens; +garbage block, pool padded to a
    # multiple of the 4-way model axis
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)

    outs = {}
    for mode in ("dense", "paged", "paged_sharded"):
        cfg = replace(base, decode_attn="sharded" if mode == "paged_sharded"
                      else "dense")
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        paged = mode != "dense"
        from repro.models.common import CacheSpec
        state = (model.init_cache(2, 16, spec=CacheSpec(4, 12))
                 if paged else model.init_cache(2, 16))
        step = jax.jit(model.decode_step, static_argnames=())
        ctx = activation_sharding(mesh) if mode == "paged_sharded" else None
        seq = []
        with mesh, (ctx or contextlib.nullcontext()):
            for i in range(8):
                idx = jnp.full((2,), i, jnp.int32)
                if paged:
                    lg, state = step(params, toks[:, i:i+1], state, idx,
                                     tables=bt)
                else:
                    lg, state = step(params, toks[:, i:i+1], state, idx)
                seq.append(np.asarray(lg[:, 0], np.float32))
        outs[mode] = np.stack(seq)
    scale = np.abs(outs["dense"]).max()
    for mode in ("paged", "paged_sharded"):
        diff = np.abs(outs["dense"] - outs[mode]).max()
        assert diff / scale < 2e-4, (mode, diff, scale)
    print("OK")
""")


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["yi-9b", "deepseek-v2-lite-16b"])
def test_paged_decode_matches_dense(arch):
    """Block-table decode == dense decode, local and under the shard_map
    flash-decode path (pool block-sharded over the model axis)."""
    code = PAGED_CODE % {"arch": arch}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
