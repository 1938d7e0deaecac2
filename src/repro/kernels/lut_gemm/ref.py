"""Pure-jnp oracles for the codebook LUT GEMMs.

Two reference semantics, matching the two Pallas kernels:

* :func:`lut_gemm_ref` — full-table evaluation: each 4-bit code indexes a
  16-entry codebook directly (paper Fig 1, the conventional LUT whose
  hardware cost is fifteen 2:1 muxes per output bit).
* :func:`lut_gemm_dc_ref` — divide-and-conquer evaluation (paper Figs 2/3):
  the code splits into 2-bit digits ``q = 4*q_hi + q_lo`` and the table
  value is the SUM of two 4-entry sub-table selects, six muxes total —
  the decomposition behind the paper's ~3.7x LUT-area saving.  With the
  affine sub-tables produced by ``core.quant.quantize_weight`` the two
  references reconstruct identical weights.
* :func:`lut_gemm_dc_res_ref` — residual-corrected D&C (non-affine NF4):
  the 6-select sum plus a per-code residual gather.  Unlike the affine
  refs (which fold the scale into the weight before the matmul — the
  order of ``ops.quantized_matmul``'s jnp path), this one follows the
  Pallas kernel's epilogue order (zero-point pre-matmul, scale after), so
  kernel and reference build the same weights bit for bit and differ only
  in the dot's summation order: they agree to f32 rounding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lut_gemm_ref(x: jax.Array, w_codes: jax.Array, codebook: jax.Array,
                 scale: jax.Array) -> jax.Array:
    w = codebook[w_codes.astype(jnp.int32)] * scale[None, :]
    return (x.astype(jnp.float32) @ w).astype(jnp.float32)


def lut_gemm_dc_ref(x: jax.Array, w_codes: jax.Array, hi_tab: jax.Array,
                    lo_tab: jax.Array, zero_point: jax.Array,
                    scale: jax.Array) -> jax.Array:
    """``x @ ((HI[q>>2] + LO[q&3] - zp) * scale)`` — D&C sub-table dequant.

    ``w_codes``: (K, N) int8 codes in [0, 16); ``hi_tab``/``lo_tab``: (4,)
    code-space sub-tables; ``zero_point``/``scale``: (N,) per-channel
    affine params.  Returns (M, N) f32.
    """
    q = w_codes.astype(jnp.int32)
    w_q = hi_tab[q >> 2] + lo_tab[q & 3]
    w = (w_q - zero_point[None, :]) * scale[None, :]
    return (x.astype(jnp.float32) @ w).astype(jnp.float32)


def lut_gemm_dc_res_ref(x: jax.Array, w_codes: jax.Array, hi_tab: jax.Array,
                        lo_tab: jax.Array, residual: jax.Array,
                        zero_point: jax.Array, scale: jax.Array
                        ) -> jax.Array:
    """``x @ (HI[q>>2] + LO[q&3] + RES[q] - zp)`` scaled in the epilogue —
    the residual-corrected D&C dequant (non-affine NF4).

    ``w_codes``: (K, N) int8 codes in [0, 16); ``hi_tab``/``lo_tab``: (4,)
    least-squares sub-tables; ``residual``: (16,) per-code correction
    (zeros at pruned codes); ``zero_point``/``scale``: (N,) per-channel.
    The weights and the epilogue order are those of
    ``lut_gemm.lut_gemm_dc_res`` (see its docstring); the kernel's blocked
    dot sums in another order, so the two agree to f32 rounding.
    Returns (M, N) f32.
    """
    q = w_codes.astype(jnp.int32)
    w_q = (hi_tab[q >> 2] + lo_tab[q & 3]) + residual[q]
    w = w_q - zero_point[None, :]
    acc = jax.lax.dot_general(x.astype(jnp.float32), w,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return acc * scale[None, :]
