"""Token-choice top-k MoE with shared experts (DeepSeek-V2 style), told
which routed experts it holds.

The router scores all ``cfg.moe.routed`` experts (softmax, greedy top-k,
no renormalisation of the kept probabilities).  This device holds
``num_experts`` of them, starting at ``first_held``: a token routed to a
held expert adds its gate times that expert's output, and a token routed
elsewhere adds nothing here — that part lies on the chips that hold the
other experts, and on one chip the layer runs without their exchange.
The shared experts see every token.

Two dispatches:

* serving (``training=False``, every prefill, decode and verify path)
  drops no token: every held expert runs on every token of the call and
  the result is weighted by the token's gate for it, zero where the
  token was not routed there.  The capacity is the whole call, so the
  result of a token never depends on the others in its batch.  On a
  frozen decode tree the held experts go through the LUT kernel over the
  stack of experts (``kernels.lut_gemm.ops.quantized_expert_matmul``).
* training (``training=True``) keeps the group-local expert-choice
  dispatch with a capacity of ``capacity_factor`` (standard semantics:
  overflow drops, the shared experts carry the residual) and the
  Switch-style load-balance loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.layers import quant_matmul
from repro.core.quant import QuantizedWeight
from repro.models.common import dense_init


def init_moe(key, cfg):
    mc = cfg.moe
    d, ff = cfg.d_model, mc.d_expert
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 7)
    e = mc.num_experts
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, mc.routed, jnp.float32),
        # stacked weights of the held experts: (E, d, ff) / (E, ff, d)
        "w_gate": (jax.random.normal(ks[1], (e, d, ff)) * scale).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e, d, ff)) * scale).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, ff, d)) *
                   (1.0 / jnp.sqrt(ff))).astype(dt),
    }
    if mc.num_shared:
        sff = ff * mc.num_shared
        p["shared"] = {
            "w_gate": dense_init(ks[4], d, sff, dt),
            "w_up": dense_init(ks[5], d, sff, dt),
            "w_down": dense_init(ks[6], sff, d, dt),
        }
    return p


def _capacity(group_tokens: int, cfg) -> int:
    mc = cfg.moe
    cap = int(group_tokens * mc.top_k * mc.capacity_factor / mc.routed)
    return min(group_tokens, max(4, (cap + 3) // 4 * 4))


def _route(params, x: jax.Array, cfg):
    """x: (..., D) -> (probs (..., routed), top_e (..., K), held gates
    (..., E)): softmax over every routed expert, greedy top-k, and each
    token's gate for each expert held here (0 where not routed)."""
    mc = cfg.moe
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, mc.top_k)
    held = mc.first_held + jnp.arange(mc.num_experts)
    gates = jnp.sum(jnp.where(top_e[..., None] == held, top_p[..., None],
                              0.0), axis=-2)
    return probs, top_e, gates


def held_load(top_e: jax.Array, active: jax.Array, mc) -> jax.Array:
    """Tokens of the ``active`` rows routed to each held expert:
    top_e (L, B, S, K), active (B,) bool -> (L, E) int32."""
    held = top_e - mc.first_held
    hit = ((held[..., None] == jnp.arange(mc.num_experts))
           & active[None, :, None, None, None])
    return hit.sum((1, 2, 3), dtype=jnp.int32)


def _held_experts(params, x: jax.Array) -> jax.Array:
    """Every held expert on every token: x (N, D) -> (E, N, D)."""
    if isinstance(params["w_gate"], QuantizedWeight):
        from repro.kernels.lut_gemm import ops as lut_ops  # lazy: no cycle
        mm = lut_ops.quantized_expert_matmul
        xe = jnp.broadcast_to(x, (params["w_gate"].codes.shape[0],) + x.shape)
        h = jax.nn.silu(mm(xe, params["w_gate"])) * mm(xe, params["w_up"])
        return mm(h, params["w_down"])
    h = (jax.nn.silu(jnp.einsum("nd,edf->enf", x, params["w_gate"]))
         * jnp.einsum("nd,edf->enf", x, params["w_up"]))
    return jnp.einsum("enf,efd->end", h, params["w_down"])


def _capacity_dispatch(params, x: jax.Array, cfg, probs, top_e, gates):
    """Training: group-local expert choice among the held experts, at most
    ``_capacity`` tokens an expert a row; returns (out, aux loss)."""
    mc = cfg.moe
    b, s, d = x.shape
    e = mc.num_experts
    cap = _capacity(s, cfg)
    # Switch-style load-balance aux loss over the router's experts
    importance = probs.mean((0, 1))
    load = jax.nn.one_hot(top_e[..., 0], mc.routed,
                          dtype=jnp.float32).mean((0, 1))
    aux = mc.routed * jnp.sum(importance * load) * mc.aux_loss_coef
    # expert-choice among routed tokens: (B, E, C)
    sel_gate, sel_idx = jax.lax.top_k(gates.transpose(0, 2, 1), cap)
    valid = (sel_gate > 0.0).astype(jnp.float32)

    def gather_g(xs, idx):                                      # (S,D),(E,C)
        return xs[idx.reshape(-1)].reshape(e, cap, d)

    xg = jax.vmap(gather_g)(x, sel_idx)                         # (B, E, C, D)
    xg = xg * valid[..., None].astype(xg.dtype)
    gate_h = jnp.einsum("gecd,edf->gecf", xg, params["w_gate"])
    up_h = jnp.einsum("gecd,edf->gecf", xg, params["w_up"])
    h = jax.nn.silu(gate_h) * up_h
    yg = jnp.einsum("gecf,efd->gecd", h, params["w_down"])      # (B, E, C, D)
    yg = yg * (sel_gate * valid)[..., None].astype(yg.dtype)

    def scatter_g(ys, idx):                                     # (E,C,D),(E,C)
        return jnp.zeros((s, d), ys.dtype).at[idx.reshape(-1)].add(
            ys.reshape(-1, d))

    return jax.vmap(scatter_g)(yg, sel_idx), aux


def moe_ffn(params, x: jax.Array, cfg, *, training: bool = False):
    """x: (B, S, D) -> (out, aux_loss, top_e (B, S, K) int32).

    Serving (the default) drops no token; ``training=True`` takes the
    capacity-limited dispatch and computes the load-balance loss (0 when
    serving)."""
    mc = cfg.moe
    b, s, d = x.shape
    with jax.named_scope("moe.router"):
        probs, top_e, gates = _route(params, x, cfg)
    with jax.named_scope("moe.routed"):
        if training:
            out, aux = _capacity_dispatch(params, x, cfg, probs, top_e,
                                          gates)
        else:
            y = _held_experts(params, x.reshape(b * s, d))      # (E, N, D)
            out = jnp.einsum("ne,end->nd", gates.reshape(b * s, -1), y,
                             preferred_element_type=jnp.float32
                             ).reshape(b, s, d)
            aux = jnp.zeros((), jnp.float32)
    if mc.num_shared:
        with jax.named_scope("moe.shared"):
            sp = params["shared"]
            gate = quant_matmul(x, sp["w_gate"], cfg.quant, "moe")
            up = quant_matmul(x, sp["w_up"], cfg.quant, "moe")
            out = out + quant_matmul(jax.nn.silu(gate) * up, sp["w_down"],
                                     cfg.quant, "moe")
    return out.astype(x.dtype), aux, top_e.astype(jnp.int32)
