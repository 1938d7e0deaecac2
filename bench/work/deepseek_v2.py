"""The decode step of DeepSeek-V2 (family ``moe``) as one device's share
of expert parallelism, counted from the configuration's shapes.

A decode step of ``rows`` active rows reads every weight this device
holds once: per layer MLA's projections (``wq``, ``w_dkv``, ``wo`` and
the absorbed ``w_uk``/``w_uv``), then the dense FFN (the first
``first_dense`` layers) or the router, the shared experts and the
``num_experts`` held routed experts.  It reads each row's latent cache
(``kv_lora_rank + qk_rope_dim`` values a key a layer, bf16) up to its
position and writes one more.  FLOPs count the projections, the
absorbed scores and context over the keys, and the routed experts for
the tokens routed to the experts held here: ``rows * top_k *
num_experts / router_width`` tokens a layer (a routed expert's weights
are read once a step whatever the number of its tokens).
"""
from __future__ import annotations

from dataclasses import dataclass, field

from work import BF16, F32, Work, head, matrix_bytes


@dataclass(frozen=True)
class StepWork(Work):
    """A step's work and its parts by layer kind (``"attention"``,
    ``"dense_ffn"``, ``"router"``, ``"shared_experts"``,
    ``"routed_experts"``, ``"head"``)."""
    parts: dict = field(default_factory=dict)


def _ffn(d: int, f: int, rows: float, frozen) -> Work:
    """A SwiGLU of width ``f`` on ``rows`` tokens."""
    w = (matrix_bytes(d, f, "w_gate" in frozen)
         + matrix_bytes(d, f, "w_up" in frozen)
         + matrix_bytes(f, d, "w_down" in frozen))
    return Work(rows * 2 * 3 * d * f, w)


def mla_layer(model: dict, rows: float, keys: float,
              frozen=frozenset()) -> Work:
    """One MLA layer in its absorbed decode form: ``rows`` rows attending
    to ``keys`` keys summed over the rows."""
    m = model["mla"]
    d, h = model["d_model"], model["num_heads"]
    r, dn, dr, dv = (m["kv_lora_rank"], m["qk_nope_dim"], m["qk_rope_dim"],
                     m["v_dim"])
    mats = {"wq": (d, h * (dn + dr)), "w_dkv": (d, r + dr),
            "wo": (h * dv, d)}
    w = sum(matrix_bytes(k, n, name in frozen)
            for name, (k, n) in mats.items())
    w += 2 * matrix_bytes(r, h * dn, False)           # w_uk, w_uv: bf16
    w += r * F32 + 2 * d * F32                        # kv_norm, ln1, ln2
    proj = sum(2 * k * n for k, n in mats.values())
    absorb = 2 * h * dn * r + 2 * h * r * dv          # q @ w_uk, ctx @ w_uv
    attend = 2 * h * (r + dr) + 2 * h * r             # scores, context
    cache = (keys + rows) * (r + dr) * BF16           # read all, write one
    return Work(rows * (proj + absorb) + keys * attend, w + cache)


def decode_step(model: dict, family: str, rows: float, keys: float,
                frozen=frozenset()) -> StepWork:
    """A whole decode step: ``rows`` active rows, ``keys`` the sum over
    them of their positions + 1 (keys each attends to)."""
    mc = model["moe"]
    d, n_layers = model["d_model"], model["num_layers"]
    n_dense = mc["first_dense"]
    n_moe = n_layers - n_dense
    held = mc["num_experts"]
    routed_tokens = rows * mc["top_k"] * held / mc["router_width"]
    expert = _ffn(d, mc["d_expert"], routed_tokens / held, frozen)
    parts = {
        "attention": mla_layer(model, rows, keys, frozen).scale(n_layers),
        "dense_ffn": _ffn(d, mc["dense_ff"], rows, frozen).scale(n_dense),
        "router": Work(rows * 2 * d * mc["router_width"],
                       d * mc["router_width"] * F32).scale(n_moe),
        "shared_experts": _ffn(d, mc["num_shared"] * mc["d_expert"], rows,
                               frozen).scale(n_moe),
        "routed_experts": expert.scale(held * n_moe),
        "head": head(model, rows),
    }
    total = sum(parts.values(), Work(0.0, 0.0))
    return StepWork(total.flops, total.bytes, parts)
