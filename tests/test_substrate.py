"""Distribution substrate: checkpoint, data, optimizer, collectives, serving."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import Checkpointer
from repro.data.synthetic import SyntheticLM
from repro.models.registry import get_config, get_model
from repro.optim.adamw import AdamW, cosine_schedule


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_synthetic_deterministic():
    d = SyntheticLM(128, 32, 4, seed=7)
    b1, b2 = d.batch_np(3), d.batch_np(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = d.batch_np(4)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    assert b1["labels"].shape == (4, 32)


def test_synthetic_learnable():
    """Bigram structure means labels correlate with chain(tokens)."""
    d = SyntheticLM(64, 64, 8, seed=0, noise=0.2)
    b = d.batch_np(0)
    pred = d.chain[b["tokens"]]
    agreement = (pred == b["labels"]).mean()
    assert agreement > 0.6


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_decreases_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.ones((4,)) * 5.0}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, m = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.5
    assert np.isfinite(float(m["grad_norm"]))


def test_grad_clipping():
    opt = AdamW(lr=1e-3, clip_norm=1.0)
    params = {"w": jnp.zeros((3,))}
    state = opt.init(params)
    huge = {"w": jnp.ones((3,)) * 1e6}
    _, state2, m = opt.update(huge, state, params)
    # post-clip m should be bounded: m = (1-b1) * clipped_grad
    assert float(jnp.abs(state2.m["w"]).max()) <= 0.1 * (1.0 + 1e-5)


def test_cosine_schedule_shape():
    sch = cosine_schedule(10, 100)
    assert float(sch(jnp.int32(0))) == 0.0
    assert float(sch(jnp.int32(10))) == pytest.approx(1.0)
    assert float(sch(jnp.int32(100))) == pytest.approx(0.1, abs=1e-5)


# ---------------------------------------------------------------------------
# checkpoint: atomic, latest, elastic
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    ck.save(5, tree, blocking=True)
    assert ck.latest_step() == 5
    shape = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         tree)
    out = ck.restore(5, shape)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_checkpoint_gc_and_latest(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, {"x": jnp.ones(2) * s}, blocking=True)
    assert ck.steps() == [3, 4]
    assert ck.latest_step() == 4


def test_checkpoint_ignores_partial(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(1, {"x": jnp.ones(2)}, blocking=True)
    # simulate a crash mid-write: tmp dir without meta
    (tmp_path / "step_9.tmp").mkdir()
    (tmp_path / "step_7").mkdir()          # no meta.json -> incomplete
    assert ck.latest_step() == 1


# ---------------------------------------------------------------------------
# trainer: loss goes down; kill -9 restart resumes
# ---------------------------------------------------------------------------

TRAIN_SNIPPET = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "src")
    import jax
    from repro.models.registry import get_config
    from repro.train.trainer import Trainer, TrainerConfig
    from repro.data.synthetic import SyntheticLM
    from repro.launch.mesh import make_host_mesh

    cfg = get_config("luna-mlp")
    tcfg = TrainerConfig(total_steps=%(steps)d, ckpt_every=5, log_every=5,
                         ckpt_dir=%(dir)r, lr=3e-3, warmup=2)
    mesh = make_host_mesh(model=2)
    data = SyntheticLM(cfg.vocab_size, 32, 8, seed=0)
    t = Trainer(cfg, tcfg, mesh)
    params, hist = t.run(data)
    print("HIST", ",".join(f"{h:.4f}" for h in hist))
""")


@pytest.mark.slow
def test_trainer_loss_decreases(tmp_path):
    code = TRAIN_SNIPPET % {"steps": 30, "dir": str(tmp_path / "ck")}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    hist_line = [ln for ln in r.stdout.splitlines()
                 if ln.startswith("HIST")][0]
    hist = [float(x) for x in hist_line[5:].split(",")]
    assert hist[-1] < hist[0] * 0.9, hist


@pytest.mark.slow
def test_trainer_restart_resumes(tmp_path):
    """Run 12 steps (ckpt@5,10), kill, rerun: must resume from step 10."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = TRAIN_SNIPPET % {"steps": 12, "dir": str(tmp_path / "ck")}
    r1 = subprocess.run([sys.executable, "-c", code], capture_output=True,
                        text=True, cwd=root, timeout=600)
    assert r1.returncode == 0, r1.stderr[-2000:]
    code2 = TRAIN_SNIPPET % {"steps": 20, "dir": str(tmp_path / "ck")}
    r2 = subprocess.run([sys.executable, "-c", code2], capture_output=True,
                        text=True, cwd=root, timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 12" in r2.stdout, r2.stdout
    hist = [ln for ln in r2.stdout.splitlines()
            if ln.startswith("HIST")][0]
    # resumed run trains only the remaining 8 steps
    assert len(hist[5:].split(",")) == 8


@pytest.mark.slow
def test_elastic_restore_different_device_count(tmp_path):
    """Checkpoint written on 4 devices restores onto 2 (elastic reshard)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = TRAIN_SNIPPET % {"steps": 6, "dir": str(tmp_path / "ck")}
    r1 = subprocess.run([sys.executable, "-c", code], capture_output=True,
                        text=True, cwd=root, timeout=600)
    assert r1.returncode == 0, r1.stderr[-2000:]
    code2 = (TRAIN_SNIPPET % {"steps": 10, "dir": str(tmp_path / "ck")}
             ).replace("device_count=4", "device_count=2"
                       ).replace("model=2", "model=1")
    r2 = subprocess.run([sys.executable, "-c", code2], capture_output=True,
                        text=True, cwd=root, timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 6" in r2.stdout


# ---------------------------------------------------------------------------
# collectives: compressed all-reduce + error feedback
# ---------------------------------------------------------------------------

def test_compress_roundtrip_small_error():
    from repro.parallel.collectives import compress_grads_int8
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))}
    gc = compress_grads_int8(g)
    rel = (np.abs(np.asarray(gc["w"] - g["w"])).max()
           / np.abs(np.asarray(g["w"])).max())
    assert rel < 0.02    # int8: ~1/127 relative error


def test_error_feedback_unbiased():
    """Error feedback: mean of compressed updates -> mean of true updates."""
    from repro.parallel.collectives import ErrorFeedback
    rng = np.random.default_rng(1)
    ef = ErrorFeedback()
    true_sum = np.zeros((16,), np.float32)
    comp_sum = np.zeros((16,), np.float32)
    for i in range(50):
        g = {"w": jnp.asarray(rng.normal(size=16).astype(np.float32))}
        true_sum += np.asarray(g["w"])
        comp_sum += np.asarray(ef.compress(g)["w"])
    # cumulative compressed mass tracks the true mass (residual is bounded)
    np.testing.assert_allclose(comp_sum, true_sum, atol=0.05)


def test_quantized_psum_multidevice():
    """shard_map int8 psum vs exact psum (subprocess with 8 host devices)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.parallel.collectives import quantized_psum
        mesh = make_mesh((8,), ("data",))
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(8, 32)).astype(np.float32))
        def f(x):
            return quantized_psum(x, "data")
        got = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                        check_vma=False)(x)
        ref = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
        rel = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
        assert rel < 0.03, rel
        print("OK", rel)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=root, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_pipeline_matches_sequential():
    """GPipe-over-pods == running stages sequentially (2 'pods')."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.parallel.pipeline import pipeline_apply
        mesh = make_mesh((2,), ("pod",))
        rng = np.random.default_rng(0)
        W = jnp.asarray(rng.normal(size=(2, 16, 16)).astype(np.float32)) * 0.3
        xs = jnp.asarray(rng.normal(size=(4, 3, 16)).astype(np.float32))
        def stage(w, x):
            return jnp.tanh(x @ w)
        got = pipeline_apply(stage, W, xs, mesh=mesh)
        ref = jnp.stack([stage(W[1], stage(W[0], x)) for x in xs])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=root, timeout=300)
    assert r.returncode == 0, (r.stderr[-2000:], r.stdout[-500:])
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_serves_batched_requests():
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Engine, Request
    cfg = get_config("yi-9b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, EngineConfig(max_batch=4, max_seq=64))
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new=5)
            for i in range(6)]   # 6 requests > 4 slots: tests slot reuse
    stats = eng.serve(reqs)
    assert stats["done"]
    for r in reqs:
        assert len(r.out) == 5
        assert all(0 <= t < cfg.vocab_size for t in r.out)


def test_engine_decode_consistency():
    """Engine slab decode == single-request decode for the same prompt."""
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Engine, Request
    cfg = get_config("yi-9b").reduced(dtype="float32", attn_impl="full")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng = Engine(cfg, params, EngineConfig(max_batch=2, max_seq=32))
    r1 = Request(rid=0, prompt=[5, 6, 7], max_new=4)
    eng.serve([r1])
    eng2 = Engine(cfg, params, EngineConfig(max_batch=1, max_seq=32))
    r2 = Request(rid=1, prompt=[5, 6, 7], max_new=4)
    eng2.serve([r2])
    assert r1.out == r2.out
