"""MoE dispatch: routing correctness, held-expert shares, no-drop serving,
capacity semantics of the training path."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.moe import _capacity, held_load, init_moe, moe_ffn


def _cfg(e=8, k=2, cap=8.0, shared=0, d=16, dff=8, **moe):
    # capacity_factor chosen high so nothing drops unless the test wants it
    return ModelConfig(
        name="t", family="moe", num_layers=2, d_model=d, num_heads=2,
        num_kv_heads=2, d_ff=dff, vocab_size=64, head_dim=8,
        moe=MoEConfig(num_experts=e, num_shared=shared, top_k=k,
                      d_expert=dff, capacity_factor=cap, **moe))


def _naive(params, x, cfg):
    """Every token through its top-k experts, the held ones only (the
    router's index e is held expert e - first_held), plus the shared
    experts."""
    mc = cfg.moe
    xt = x.reshape(-1, cfg.d_model).astype(jnp.float32)
    probs = jax.nn.softmax(xt @ params["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, mc.top_k)
    ref = np.zeros(xt.shape, np.float32)
    for t in range(xt.shape[0]):
        for j in range(mc.top_k):
            e = int(top_e[t, j]) - mc.first_held
            if not 0 <= e < mc.num_experts:
                continue
            h = (jax.nn.silu(xt[t] @ params["w_gate"][e])
                 * (xt[t] @ params["w_up"][e]))
            ref[t] += np.asarray(top_p[t, j] * (h @ params["w_down"][e]))
    if mc.num_shared:
        sp = params["shared"]
        ref += np.asarray((jax.nn.silu(xt @ sp["w_gate"]) * (xt @ sp["w_up"]))
                          @ sp["w_down"])
    return ref


@pytest.mark.parametrize("training", [False, True],
                         ids=["serving", "training"])
def test_moe_matches_dense_expert_sum(training):
    """With capacity high enough to route everything, both dispatches
    equal the naive 'every token through its top-k experts' computation."""
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    params = init_moe(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)
    out, aux, top_e = moe_ffn(params, x, cfg, training=training)
    np.testing.assert_allclose(np.asarray(out.reshape(-1, cfg.d_model)),
                               _naive(params, x, cfg), rtol=2e-4, atol=2e-4)
    assert float(aux) >= 0
    assert top_e.shape == (2, 8, cfg.moe.top_k) and top_e.dtype == jnp.int32


def test_moe_capacity_drops_overflow():
    """With capacity_factor ~0, most tokens drop -> output ~ only shared
    (the training dispatch)."""
    cfg = _cfg(cap=0.01, shared=1)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    out, _, _ = moe_ffn(params, x, cfg, training=True)
    # shared-expert-only reference
    sp = params["shared"]
    xt = x.reshape(-1, cfg.d_model)
    shared_out = (jax.nn.silu(xt @ sp["w_gate"]) * (xt @ sp["w_up"])
                  ) @ sp["w_down"]
    # a few tokens still fit in the minimal capacity, so compare loosely:
    diff = np.abs(np.asarray(out.reshape(-1, cfg.d_model) - shared_out))
    routed_rows = (diff.max(axis=1) > 1e-6).sum()
    cap = _capacity(64, cfg)
    assert routed_rows <= cfg.moe.num_experts * cap


def test_moe_decode_single_group():
    """A decode step (s == 1) serves each row as it would alone: no row's
    output depends on how many others the batch holds."""
    cfg = _cfg(e=8, k=2)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 1, cfg.d_model))
    out, _, _ = moe_ffn(params, x, cfg)
    assert out.shape == (16, 1, cfg.d_model)
    alone = jnp.concatenate([moe_ffn(params, x[i:i + 1], cfg)[0]
                             for i in range(16)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(alone),
                               rtol=1e-5, atol=1e-6)


def test_moe_shares_sum_to_uncut_layer():
    """Eight shares of a 64-expert layer (experts 0-7, 8-15, ...), each
    routing over all 64 at the published top-6 and computing its own
    experts' part, with the shared experts counted once, add up to the
    uncut layer."""
    cfg = _cfg(e=64, k=6, shared=2)
    params = init_moe(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, cfg.d_model))
    whole, _, _ = moe_ffn(params, x, cfg)
    no_shared = {k: v for k, v in params.items() if k != "shared"}
    parts = []
    for i in range(8):
        mc = replace(cfg.moe, num_experts=8, router_width=64,
                     first_held=8 * i, num_shared=0)
        share = dict(no_shared, **{k: params[k][8 * i:8 * i + 8]
                                   for k in ("w_gate", "w_up", "w_down")})
        parts.append(moe_ffn(share, x, replace(cfg, moe=mc))[0])
    sp = params["shared"]
    shared = (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [(1, 64), (2, 32), (16, 1), (4, 3)],
                         ids=["prefill", "chunk", "decode", "verify"])
def test_moe_skewed_routing_drops_nothing(shape):
    """Every token routed to one held expert: the serving dispatch of a
    prefill, a prefill chunk, a decode step and a verify window gives
    every token its whole top-k, as the naive sum does (the training
    dispatch would keep at most its capacity)."""
    cfg = _cfg(e=4, k=2, shared=1, router_width=16, first_held=4)
    params = init_moe(jax.random.PRNGKey(5), cfg)
    # expert 5 (held 1) first and 6 (held 2) second for every token
    params["router"] = jnp.zeros_like(params["router"]).at[:, 5].set(
        4.0).at[:, 6].set(2.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6),
                                  shape + (cfg.d_model,))) + 0.5
    out, _, top_e = moe_ffn(params, x, cfg)
    assert (np.asarray(top_e[..., 0]) == 5).all()
    np.testing.assert_allclose(np.asarray(out.reshape(-1, cfg.d_model)),
                               _naive(params, x, cfg), rtol=2e-4, atol=2e-4)
    load = held_load(top_e[None], jnp.ones((shape[0],), bool), cfg.moe)
    assert load.tolist() == [[0, shape[0] * shape[1], shape[0] * shape[1],
                              0]]


def test_held_load_counts_active_rows_only():
    mc = _cfg(e=2, k=2, router_width=8, first_held=2).moe
    top_e = jnp.asarray([[[[2, 3]], [[3, 7]], [[2, 0]]]])    # (1, 3, 1, 2)
    load = held_load(top_e, jnp.asarray([True, True, False]), mc)
    assert load.tolist() == [[1, 2]]


def test_moe_aux_loss_balanced_vs_collapsed():
    """Fully collapsed routing hits the E*coef ceiling of the Switch aux
    loss and exceeds whatever a random router produces (random init on a
    small d_model is only ROUGHLY balanced, so the old fixed 1.5x margin
    against it was flaky — the collapse ceiling is exact)."""
    cfg = _cfg(e=4, k=1)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    # positive activations so a positive router column collapses routing
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3),
                                  (4, 32, cfg.d_model))) + 0.1
    _, aux_norm, _ = moe_ffn(params, x, cfg, training=True)
    collapsed = dict(params)
    collapsed["router"] = jnp.zeros_like(params["router"]).at[:, 0].set(10.0)
    _, aux_coll, _ = moe_ffn(collapsed, x, cfg, training=True)
    e, coef = cfg.moe.num_experts, cfg.moe.aux_loss_coef
    np.testing.assert_allclose(float(aux_coll), e * coef, rtol=1e-3)
    assert float(aux_norm) < float(aux_coll)
    # any routing is at least the balanced optimum, coef (= E * (1/E)^2 * E)
    assert float(aux_norm) >= coef * 0.99


def test_moe_gradients_flow_to_router_and_experts():
    cfg = _cfg()
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))

    def loss(p):
        out, aux, _ = moe_ffn(p, x, cfg, training=True)
        return jnp.sum(out ** 2) + aux

    g = jax.grad(loss)(params)
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["w_gate"]).sum()) > 0
    assert float(jnp.abs(g["w_down"]).sum()) > 0


@pytest.mark.parametrize("share", [False, True], ids=["all_held", "share"])
def test_engine_counts_expert_load(share):
    """The MoE decode program returns the held experts' load beside its
    tokens: the engine counts every routed pair of the active rows, the
    held ones among them, and the busiest held expert once a step."""
    from repro.models.registry import get_config, get_model
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Engine, Request
    cfg = get_config("deepseek-v2-lite-16b").reduced(dtype="float32")
    if share:
        cfg = replace(cfg, moe=replace(cfg.moe, router_width=32,
                                       first_held=8))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, EngineConfig(max_batch=3, max_seq=32))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size,
                                               n).tolist(), max_new=5)
            for i, n in enumerate((3, 7, 4))]
    eng.serve(reqs)
    reg = eng.registry
    n_moe = cfg.num_layers - cfg.moe.first_dense
    routed = reg.counter("engine_expert_pairs_routed_total").value()
    held = reg.counter("engine_expert_pairs_held_total").value()
    assert routed == eng.metrics.decode_tokens * cfg.moe.top_k * n_moe
    assert (0 <= held < routed) if share else held == routed
    busiest = reg.histogram("engine_expert_busiest_tokens")
    assert busiest.count() == eng.metrics.ticks
