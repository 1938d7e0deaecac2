"""LunaDense — the paper's technique as a first-class, composable layer.

Every projection in every architecture routes through :func:`quant_matmul`,
so a single ``--quant`` flag turns any assigned architecture into a
LUNA-quantized model.  Modes:

  bf16              — no quantization (roofline baseline)
  int8              — symmetric int8 dynamic quantization (MXU int8 path)
  int4_dequant      — weight-only uniform int4, dequant then bf16 matmul
                      (the "conventional math" baseline the paper argues against)
  luna_conventional — full-LUT LUNA (exact; paper Fig 1 semantics)
  luna_dc           — exact D&C LUNA (paper Figs 2/3; optimized table)
  luna_approx       — ApproxD&C, Z_LSB := 0 (paper Fig 9)
  luna_approx2      — ApproxD&C2, Z_LSB := W (paper Fig 10)
  lut_nf4           — beyond-paper: NF4 codebook weights evaluated through the
                      paper's mux tree (programmable LUT)

Training uses the STE wrapper (forward = bit-exact integer path).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import lut
from repro.core.luna import LunaMode
from repro.core.quant import (QuantizedWeight, calibrate, dequantize,
                              quantize, ste_luna_matmul)

LUNA_MODE_OF = {
    "luna_conventional": LunaMode.CONVENTIONAL,
    "luna_dc": LunaMode.OPT_DC,
    "luna_approx": LunaMode.APPROX_DC,
    "luna_approx2": LunaMode.APPROX_DC2,
}

QUANT_MODES = ("bf16", "int8", "int4_dequant", "lut_nf4", *LUNA_MODE_OF)


@dataclass(frozen=True)
class QuantConfig:
    mode: str = "bf16"
    bits: int = 4
    use_pallas: bool = False   # route LUNA modes through the Pallas kernel
    # which projection groups to quantize (router/embeddings stay full-prec)
    targets: tuple = ("attn", "mlp", "moe")

    def __post_init__(self):
        if self.mode not in QUANT_MODES:
            raise ValueError(f"unknown quant mode {self.mode!r}; one of {QUANT_MODES}")

    def applies(self, group: str) -> bool:
        return self.mode != "bf16" and group in self.targets


def _int8_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    xq = calibrate(x, 8, axis=None, symmetric=True)
    wq = calibrate(w, 8, axis=-1, symmetric=True)
    qx = (quantize(x, xq) - xq.zero_point).astype(jnp.int8)
    qw = (quantize(w, wq) - wq.zero_point).astype(jnp.int8)
    acc = jax.lax.dot_general(qx, qw, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (xq.scale * wq.scale)


def _int4_dequant_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    wq = calibrate(w, 4, axis=-1)
    w_hat = dequantize(quantize(w, wq), wq).astype(x.dtype)
    return x @ w_hat


def _nf4_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Weight-only NF4 through the mux tree (beyond-paper programmable LUT)."""
    absmax = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8)     # per-channel
    w_norm = w / absmax
    cb = jnp.asarray(lut.NF4_CODEBOOK)
    # nearest codebook entry (quantize)
    codes = jnp.argmin(jnp.abs(w_norm[..., None] - cb), axis=-1).astype(jnp.int32)
    w_hat = lut.codebook_dequant(codes, cb) * absmax
    return x @ w_hat.astype(x.dtype)


def quant_matmul(x: jax.Array, w: jax.Array, cfg: QuantConfig | None,
                 group: str = "mlp") -> jax.Array:
    """``x @ w`` under the configured quantization mode.

    ``x``: (..., K); ``w``: (K, N).  Output dtype follows ``x``.

    ``w`` may also be a frozen :class:`~repro.core.quant.QuantizedWeight`
    (the engine's ``EngineConfig(quant=...)`` decode path substitutes them
    at construction); those route through the LUT GEMM over the weight's
    16-entry table (``lut4``, ``nf4`` or ``nf4p``) regardless of ``cfg``:
    the model-level ``cfg`` quantizes *dynamically* per call, engine-level
    quantization froze the weight once.
    """
    if isinstance(w, QuantizedWeight):
        from repro.kernels.lut_gemm import ops as lut_ops  # lazy: avoid cycle
        return lut_ops.quantized_matmul(x, w)
    if cfg is None or not cfg.applies(group):
        return x @ w
    if cfg.mode == "int8":
        return _int8_matmul(x, w).astype(x.dtype)
    if cfg.mode == "int4_dequant":
        return _int4_dequant_matmul(x, w)
    if cfg.mode == "lut_nf4":
        return _nf4_matmul(x, w)
    mode = LUNA_MODE_OF[cfg.mode]
    if cfg.use_pallas:
        from repro.kernels.luna_mm import ops as luna_ops  # lazy: avoid cycle
        return luna_ops.luna_matmul_f32_kernel(
            x.astype(jnp.float32), w.astype(jnp.float32), mode=mode.value,
            bits=cfg.bits).astype(x.dtype)
    return ste_luna_matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                           mode.value, cfg.bits).astype(x.dtype)
