"""Public wrappers: codebook quantize + LUT GEMM (weight-only 4-bit).

The entry points over the LUT kernels:

* :func:`quantized_matmul` — the serving decode hot path: a frozen
  :class:`~repro.core.quant.QuantizedWeight`, dispatched on the
  container's static ``kernel`` tag.  ``"lut_dc"`` reconstructs the
  weight by summing the two D&C sub-table selects through
  ``core.lut.mux_tree_select`` (3 + 3 muxes — the paper's area
  argument); ``"dequant"`` is the conventional-math baseline
  ``(q - z_w) * s_w`` (both reconstruct the identical affine grid, so
  engine tokens match bit-for-bit between ``quant="lut4"`` and
  ``"int4"``); ``"nf4_dc"`` adds the per-code residual to the D&C sum
  (non-affine NF4, exact up to float rounding with the full residual,
  bounded-error with a pruned one); ``"nf4_dequant"`` is the direct
  full-table NF4 lookup the residual path is pinned against.  All four
  run as ``jnp`` primitives, which rebuild the weight in f32 — except
  ``"nf4_dc"`` lowered for the TPU, which runs :func:`nf4_dc_matmul`.
  The ``jnp`` branch is the path on every other platform and the
  kernel's reference.
* :func:`nf4_dc_matmul` — an ``nf4_dc`` weight through the fused
  ``lut_gemm_dc_res`` Pallas kernel: codes dequantized in VMEM, tiles
  chosen from the shapes.
* :func:`quantized_expert_matmul` — :func:`quantized_matmul` over a
  stack of experts (an MoE layer's frozen routed experts): on the TPU an
  ``nf4_dc`` stack runs :func:`nf4_dc_expert_matmul`, one
  ``lut_gemm_dc_res_experts`` call for every expert; elsewhere the
  ``jnp`` path of each expert.
* :func:`nf4_matmul_kernel` — NF4 codebook weights through the full-table
  Pallas kernel (paper Fig 1 select tree, programmable codebook).
* :func:`lut4_matmul_kernel` — uniform-int4 weights through the D&C
  sub-table Pallas kernel (paper Figs 2/3: two 4-entry tables, 6 selects).
* :func:`nf4dc_matmul_kernel` — quantizes a float weight to ``nf4_dc``
  and runs :func:`nf4_dc_matmul` (a prune threshold reproduces
  ``quant="nf4p"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.lut import NF4_CODEBOOK, codebook_dequant
from repro.core.quant import QuantizedWeight, dequantize, quantize_weight
from repro.kernels.lut_gemm.lut_gemm import (lut_gemm, lut_gemm_dc,
                                             lut_gemm_dc_res,
                                             lut_gemm_dc_res_experts)


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

    Lowered for the TPU, ``nf4_dc`` runs the fused Pallas kernel
    (:func:`nf4_dc_matmul`), which dequantizes code tiles in VMEM; on
    every other platform, where that kernel cannot lower, it takes the
    ``jnp`` path, which is also its reference.
    """
    assert qw.codes.ndim == 2, (
        f"quantized_matmul expects a sliced 2-D weight, got "
        f"{qw.codes.shape}; scan-stacked leaves are sliced by lax.scan")
    if qw.kernel == "nf4_dc":
        return jax.lax.platform_dependent(x, qw, tpu=nf4_dc_matmul,
                                          default=_jnp_matmul)
    return _jnp_matmul(x, qw)


def _jnp_matmul(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    """:func:`quantized_matmul` in ``jnp``: rebuild the weight in f32
    through ``core.lut.mux_tree_select``, then one f32 matmul."""
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


def quantized_expert_matmul(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    """``out[e] = x[e] @ dequant(qw[e])`` for a stack of experts.

    ``x``: (E, M, K) float; ``qw.codes``: (E, K, N) (a layer's slice of
    the stacked routed experts).  Returns (E, M, N) in ``x``'s dtype.
    Lowered for the TPU, ``nf4_dc`` runs :func:`nf4_dc_expert_matmul`;
    otherwise each expert takes the ``jnp`` path, its reference."""
    assert qw.codes.ndim == 3 and x.ndim == 3, (x.shape, qw.codes.shape)
    if qw.kernel == "nf4_dc":
        return jax.lax.platform_dependent(x, qw, tpu=nf4_dc_expert_matmul,
                                          default=_jnp_expert_matmul)
    return _jnp_expert_matmul(x, qw)


def _jnp_expert_matmul(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    return jax.vmap(_jnp_matmul)(x, qw)


#: code bytes in one block of :func:`nf4_dc_matmul`'s grid: large enough
#: that a decode projection takes a few grid steps (each costs about
#: 0.35 us), small enough that the block's DMA, exposed before the first
#: step, stays a few microseconds
DC_RES_BLOCK_BYTES = 2 * 2 ** 20
#: code rows dequantized per loop iteration inside a block
DC_RES_BK = 256


def nf4_dc_matmul(x: jax.Array, qw: QuantizedWeight, *,
                  interpret: bool = False) -> jax.Array:
    """``x @ dequant(qw)`` for an ``nf4_dc`` weight through
    :func:`lut_gemm_dc_res`: the codes are read once, dequantized in VMEM,
    and no float weight is written to HBM.

    ``x``: (..., K) float, flattened to (M, K); ``qw.codes``: (K, N).
    Tiles come from the shapes: every row in one row block (M rounded up
    to 16, at most 256), the whole K extent per block, and ``bn`` columns
    so that a block holds about :data:`DC_RES_BLOCK_BYTES` of codes.
    Ragged edge blocks are masked, so the wrapper pads nothing, and the
    result comes out in ``x``'s dtype.
    """
    k, n = qw.codes.shape
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm = min(-(-m // 16) * 16, 256)
    cap = max(128, DC_RES_BLOCK_BYTES // k // 128 * 128)
    bn = n if n <= cap else cap
    bk = DC_RES_BK if k % DC_RES_BK == 0 else k
    out = lut_gemm_dc_res(x2, qw.codes, qw.hi_tab, qw.lo_tab, qw.residual,
                          qw.zero_point, qw.scale, bm=bm, bn=bn, bk=bk,
                          interpret=interpret)
    return out.reshape(*x.shape[:-1], n)


def nf4_dc_expert_matmul(x: jax.Array, qw: QuantizedWeight, *,
                         interpret: bool = False) -> jax.Array:
    """``out[e] = x[e] @ dequant(qw[e])`` for a stack of ``nf4_dc``
    experts through one :func:`lut_gemm_dc_res_experts` call.

    ``x``: (E, M, K); ``qw.codes``: (E, K, N).  Tiles as in
    :func:`nf4_dc_matmul`, per expert, except that ``bk`` is the largest
    of 256 and 128 that divides K (an expert's 1408-wide down-projection
    takes 128), so that no step dequantizes the whole K extent at once.
    """
    e, k, n = qw.codes.shape
    m = x.shape[1]
    bm = min(-(-m // 16) * 16, 256)
    cap = max(128, DC_RES_BLOCK_BYTES // k // 128 * 128)
    bn = n if n <= cap else cap
    bk = next((b for b in (DC_RES_BK, 128) if k % b == 0), k)
    return lut_gemm_dc_res_experts(x, qw.codes, qw.hi_tab, qw.lo_tab,
                                   qw.residual, qw.zero_point, qw.scale,
                                   bm=bm, bn=bn, bk=bk, interpret=interpret)


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
    ``"nf4p"``) and evaluates through :func:`nf4_dc_matmul`.
    """
    qw = quantize_weight(w, kernel="nf4_dc", prune_threshold=prune_threshold)
    return nf4_dc_matmul(x, qw, interpret=interpret)


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
