"""Feed-forward blocks: SwiGLU (llama family) and GELU (starcoder2/whisper).

Every projection (``w_up``/``w_gate``/``w_down``) routes through
``core.layers.quant_matmul``, so these leaves participate in BOTH
quantization surfaces: model-level ``QuantConfig`` (dynamic, every call)
and engine-level ``EngineConfig(quant="lut4"|"nf4"|"nf4p")``, where the serving
backend freezes them to 4-bit ``QuantizedWeight`` containers for the
decode hot path (prefill keeps the float tree).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.layers import quant_matmul
from repro.models.common import dense_init


def init_mlp(key, cfg, d_model=None, d_ff=None, mlp_type=None, dtype=None):
    d = d_model or cfg.d_model
    ff = d_ff or cfg.d_ff
    mt = mlp_type or cfg.mlp_type
    dt = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d, ff, dt),
         "w_down": dense_init(ks[1], ff, d, dt)}
    if mt == "swiglu":
        p["w_gate"] = dense_init(ks[2], d, ff, dt)
    return p


def mlp(params, x: jax.Array, cfg, mlp_type=None) -> jax.Array:
    mt = mlp_type or cfg.mlp_type
    up = quant_matmul(x, params["w_up"], cfg.quant, "mlp")
    if mt == "swiglu":
        gate = quant_matmul(x, params["w_gate"], cfg.quant, "mlp")
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    return quant_matmul(h, params["w_down"], cfg.quant, "mlp")
