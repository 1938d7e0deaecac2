"""Public wrappers: LUT GEMM over frozen 4-bit weights.

The entry points over the LUT kernel:

* :func:`quantized_matmul` — the serving decode hot path: ``x`` times a
  frozen :class:`~repro.core.quant.QuantizedWeight`, whose weight is
  ``(table[q] - zero_point) * scale`` whatever mode froze it.  Lowered
  for the TPU it runs :func:`lut_matmul`; on every other platform, where
  that kernel cannot lower, the ``jnp`` path, which rebuilds the weight
  in f32 through ``core.lut.codebook_dequant`` (the paper's 15-select mux
  tree) and is also the kernel's reference.
* :func:`lut_matmul` — a frozen weight through the fused
  ``lut_gemm_dc_res`` Pallas kernel: codes dequantized in VMEM, tiles
  chosen from the shapes.
* :func:`quantized_expert_matmul` — :func:`quantized_matmul` over a
  stack of experts (an MoE layer's frozen routed experts): on the TPU
  :func:`lut_expert_matmul`, one ``lut_gemm_dc_res_experts`` call for
  every expert; elsewhere the ``jnp`` path of each expert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.lut import codebook_dequant
from repro.core.quant import QuantizedWeight
from repro.kernels.lut_gemm.lut_gemm import (lut_gemm_dc_res,
                                             lut_gemm_dc_res_experts)


def quantized_matmul(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    """``x @ dequant(qw)`` — the engine's quantized decode-step matmul.

    ``x``: (..., K) float; ``qw.codes``: (K, N) (scan-stacked leaves are
    sliced to 2-D before reaching here).  Output dtype follows ``x``.
    Lowered for the TPU it runs the fused Pallas kernel
    (:func:`lut_matmul`), which dequantizes code tiles in VMEM; on every
    other platform the ``jnp`` path.
    """
    assert qw.codes.ndim == 2, (
        f"quantized_matmul expects a sliced 2-D weight, got "
        f"{qw.codes.shape}; scan-stacked leaves are sliced by lax.scan")
    return jax.lax.platform_dependent(x, qw, tpu=lut_matmul,
                                      default=_jnp_matmul)


def _jnp_matmul(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    """:func:`quantized_matmul` in ``jnp``: rebuild the weight in f32
    through ``core.lut.codebook_dequant``, then one f32 matmul."""
    w_q = codebook_dequant(qw.codes.astype(jnp.int32), qw.table)
    w = (w_q - qw.zero_point[None, :]) * qw.scale[None, :]
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def quantized_expert_matmul(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    """``out[e] = x[e] @ dequant(qw[e])`` for a stack of experts.

    ``x``: (E, M, K) float; ``qw.codes``: (E, K, N) (a layer's slice of
    the stacked routed experts).  Returns (E, M, N) in ``x``'s dtype.
    Lowered for the TPU it runs :func:`lut_expert_matmul`; otherwise each
    expert takes the ``jnp`` path, its reference."""
    assert qw.codes.ndim == 3 and x.ndim == 3, (x.shape, qw.codes.shape)
    return jax.lax.platform_dependent(x, qw, tpu=lut_expert_matmul,
                                      default=_jnp_expert_matmul)


def _jnp_expert_matmul(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    return jax.vmap(_jnp_matmul)(x, qw)


#: code bytes in one block of :func:`lut_matmul`'s grid: large enough
#: that a decode projection takes a few grid steps (each costs about
#: 0.35 us), small enough that the block's DMA, exposed before the first
#: step, stays a few microseconds
DC_RES_BLOCK_BYTES = 2 * 2 ** 20
#: code rows dequantized per loop iteration inside a block
DC_RES_BK = 256


def lut_matmul(x: jax.Array, qw: QuantizedWeight, *,
               interpret: bool = False) -> jax.Array:
    """``x @ dequant(qw)`` through :func:`lut_gemm_dc_res`: the codes are
    read once, dequantized in VMEM, and no float weight is written to HBM.

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
    out = lut_gemm_dc_res(x2, qw.codes, qw.table, qw.zero_point, qw.scale,
                          bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out.reshape(*x.shape[:-1], n)


def lut_expert_matmul(x: jax.Array, qw: QuantizedWeight, *,
                      interpret: bool = False) -> jax.Array:
    """``out[e] = x[e] @ dequant(qw[e])`` for a stack of experts through
    one :func:`lut_gemm_dc_res_experts` call.

    ``x``: (E, M, K); ``qw.codes``: (E, K, N).  Tiles as in
    :func:`lut_matmul`, per expert, except that ``bk`` is the largest of
    256 and 128 that divides K (an expert's 1408-wide down-projection
    takes 128), so that no step dequantizes the whole K extent at once.
    """
    e, k, n = qw.codes.shape
    m = x.shape[1]
    bm = min(-(-m // 16) * 16, 256)
    cap = max(128, DC_RES_BLOCK_BYTES // k // 128 * 128)
    bn = n if n <= cap else cap
    bk = next((b for b in (DC_RES_BK, 128) if k % b == 0), k)
    return lut_gemm_dc_res_experts(x, qw.codes, qw.table, qw.zero_point,
                                   qw.scale, bm=bm, bn=bn, bk=bk,
                                   interpret=interpret)
