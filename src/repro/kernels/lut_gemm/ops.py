"""Public wrappers: codebook quantize + LUT GEMM (weight-only 4-bit).

Four entry points over the LUT kernels:

* :func:`nf4_matmul_kernel` — NF4 codebook weights through the full-table
  Pallas kernel (paper Fig 1 select tree, programmable codebook).
* :func:`lut4_matmul_kernel` — uniform-int4 weights through the D&C
  sub-table Pallas kernel (paper Figs 2/3: two 4-entry tables, 6 selects).
* :func:`nf4dc_matmul_kernel` — NF4 weights through the residual-corrected
  D&C Pallas kernel (6-select mux + per-code residual epilogue — the
  non-affine extension; a prune threshold reproduces ``quant="nf4p"``).
* :func:`quantized_matmul` — the serving decode hot path: a frozen
  :class:`~repro.core.quant.QuantizedWeight` evaluated with jnp primitives
  (jit-compatible on every backend; the Pallas kernels above implement the
  same math for TPU).  Dispatches on the container's static ``kernel`` tag:
  ``"lut_dc"`` reconstructs the weight by summing the two D&C sub-table
  selects through ``core.lut.mux_tree_select`` (3 + 3 muxes — the paper's
  area argument); ``"dequant"`` is the conventional-math baseline
  ``(q - z_w) * s_w`` (both reconstruct the identical affine grid, so
  engine tokens match bit-for-bit between ``quant="lut4"`` and ``"int4"``);
  ``"nf4_dc"`` adds the per-code residual gather to the D&C sum (non-affine
  NF4, exact up to float rounding with the full residual, bounded-error
  with a pruned one); ``"nf4_dequant"`` is the direct full-table NF4
  lookup the residual path is pinned against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.lut import NF4_CODEBOOK, codebook_dequant
from repro.core.quant import QuantizedWeight, dequantize, quantize_weight
from repro.kernels.lut_gemm.lut_gemm import (lut_gemm, lut_gemm_dc,
                                             lut_gemm_dc_res)


def codebook_quantize(w: jax.Array, codebook: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """Per-output-channel absmax normalize + nearest-codebook-entry encode."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8)
    wn = w / scale
    codes = jnp.argmin(jnp.abs(wn[..., None] - codebook), axis=-1)
    return codes.astype(jnp.int8), scale.astype(jnp.float32)


def quantized_matmul(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    """``x @ dequant(qw)`` — the engine's quantized decode-step matmul.

    ``x``: (..., K) float; ``qw.codes``: (K, N) (scan-stacked leaves are
    sliced to 2-D before reaching here).  Output dtype follows ``x``.
    Dispatches on the container's static ``kernel`` tag: the affine pair
    (``lut_dc`` / ``dequant``) reconstructs one identical grid; the NF4
    pair evaluates the non-affine codebook either as the 6-select D&C sum
    plus a per-code residual gather (``nf4_dc`` — the residual is the
    least-squares correction of ``core.lut.dc_decompose_codebook``, zeroed
    at pruned codes under ``quant="nf4p"``) or as the conventional
    full-table lookup (``nf4_dequant``, the 15-select oracle).
    """
    assert qw.codes.ndim == 2, (
        f"quantized_matmul expects a sliced 2-D weight, got "
        f"{qw.codes.shape}; scan-stacked leaves are sliced by lax.scan")
    q = qw.codes.astype(jnp.int32)
    if qw.kernel == "lut_dc":
        w_q = (codebook_dequant(q >> 2, qw.hi_tab)
               + codebook_dequant(q & 3, qw.lo_tab))
        w = (w_q - qw.zero_point[None, :]) * qw.scale[None, :]
    elif qw.kernel == "nf4_dc":
        w_q = (codebook_dequant(q >> 2, qw.hi_tab)
               + codebook_dequant(q & 3, qw.lo_tab)
               + codebook_dequant(q, qw.residual))
        w = (w_q - qw.zero_point[None, :]) * qw.scale[None, :]
    elif qw.kernel == "nf4_dequant":        # full-table oracle (15 selects)
        w = codebook_dequant(q, jnp.asarray(NF4_CODEBOOK)) * qw.scale[None, :]
    else:                                   # "dequant": conventional math
        w = dequantize(q, qw.qparams)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def nf4_matmul_kernel(x: jax.Array, w: jax.Array,
                      interpret: bool = False) -> jax.Array:
    """Float GEMM with NF4 codebook weights through the Pallas LUT kernel."""
    cb = jnp.asarray(NF4_CODEBOOK)
    codes, scale = codebook_quantize(w, cb)
    m, k = x.shape
    n = w.shape[1]
    bm, bn, bk = gemm_blocks(m, k, n)
    xp = jnp.pad(x, [(0, (-m) % bm), (0, (-k) % bk)])
    cp = jnp.pad(codes, [(0, (-k) % bk), (0, (-n) % bn)])
    sp = jnp.pad(scale, [(0, (-n) % bn)])
    out = lut_gemm(xp, cp, cb, sp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def lut4_matmul_kernel(x: jax.Array, w: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """Float GEMM with uniform-int4 weights through the D&C Pallas kernel.

    Quantizes ``w`` with :func:`~repro.core.quant.quantize_weight` (the same
    calibration the engine freezes at construction) and evaluates through
    the six-select sub-table kernel.  Pads every dim to the fitted block.
    """
    qw = quantize_weight(w, kernel="lut_dc")
    m, k = x.shape
    n = w.shape[1]
    bm, bn, bk = gemm_blocks(m, k, n)
    xp = jnp.pad(x, [(0, (-m) % bm), (0, (-k) % bk)])
    cp = jnp.pad(qw.codes, [(0, (-k) % bk), (0, (-n) % bn)])
    zp = jnp.pad(qw.zero_point, [(0, (-n) % bn)])
    sp = jnp.pad(qw.scale, [(0, (-n) % bn)])
    out = lut_gemm_dc(xp, cp, qw.hi_tab, qw.lo_tab, zp, sp,
                      bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("prune_threshold", "interpret"))
def nf4dc_matmul_kernel(x: jax.Array, w: jax.Array,
                        prune_threshold: float | None = None,
                        interpret: bool = False) -> jax.Array:
    """Float GEMM with NF4 weights through the residual-corrected D&C
    Pallas kernel (6-select mux + per-code residual epilogue).

    Quantizes ``w`` with :func:`~repro.core.quant.quantize_weight` in
    ``nf4_dc`` mode (the same transform ``EngineConfig(quant="nf4")``
    freezes at engine construction; a ``prune_threshold`` reproduces
    ``"nf4p"``) and evaluates through :func:`lut_gemm_dc_res`.  Pads every
    dim to the fitted block.
    """
    qw = quantize_weight(w, kernel="nf4_dc", prune_threshold=prune_threshold)
    m, k = x.shape
    n = w.shape[1]
    bm, bn, bk = gemm_blocks(m, k, n)
    xp = jnp.pad(x, [(0, (-m) % bm), (0, (-k) % bk)])
    cp = jnp.pad(qw.codes, [(0, (-k) % bk), (0, (-n) % bn)])
    zp = jnp.pad(qw.zero_point, [(0, (-n) % bn)])
    sp = jnp.pad(qw.scale, [(0, (-n) % bn)])
    out = lut_gemm_dc_res(xp, cp, qw.hi_tab, qw.lo_tab, qw.residual, zp, sp,
                          bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


def gemm_blocks(m: int, k: int, n: int) -> tuple[int, int, int]:
    """``(bm, bn, bk)`` for an (m, k) @ (k, n) LUT GEMM; callers pad up
    to them.  Powers of two up to 256, never below one TPU tile: 8
    sublanes for ``bm``, 128 lanes for ``bn`` and ``bk`` (``bk`` is also
    the int8 code tile's sublane dim, which needs 32)."""
    return _fit(m, 8), _fit(n, 128), _fit(k, 128)


def _fit(d: int, base: int) -> int:
    b = base
    while b * 2 <= d and b < 256:
        b *= 2
    return b
