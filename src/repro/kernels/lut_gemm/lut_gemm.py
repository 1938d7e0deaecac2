"""Pallas TPU kernels: programmable-LUT (codebook) weight-only GEMM.

The "programmable" half of LUNA-CIM: weights are 4-bit *codes* into an
arbitrary 16-entry codebook (uniform int4, NF4, or any learned table).  Two
kernels implement the paper's two select-tree organizations:

* :func:`lut_gemm` — full-table (paper Fig 1): each (bk, bn) weight tile is
  dequantized in VMEM through a binary mux tree of ``2**b - 1 = 15`` vector
  selects on the code bits, the exact analogue of the paper's fifteen 2:1
  muxes, then fed to the MXU.
* :func:`lut_gemm_dc` — divide-and-conquer (paper Figs 2/3): the 4-bit code
  splits into 2-bit digits ``q = 4*q_hi + q_lo`` and the table value is the
  sum of two 4-entry sub-table selects — ``2 * (2**2 - 1) = 6`` muxes
  instead of 15, the select-tree shrink behind the paper's ~3.7x LUT-area
  saving.  Per-channel zero-points are subtracted pre-MXU (the ``z_w``
  correction term of the integer-GEMM identity in ``core.quant``), scales
  applied in the epilogue.
* :func:`lut_gemm_dc_res` — residual-corrected D&C for NON-AFFINE
  codebooks (NF4): the 6-select sum only spans separable tables, so the
  least-squares residual of ``core.lut.dc_decompose_codebook`` is gathered
  per code and added after the mux tree.  With the full residual the
  reconstruction is exact up to float rounding; with a pruned residual
  (``quant="nf4p"``) dropped codes fall through to the pure HI+LO sum and
  the table trades capacity for a bounded accuracy cost.

Memory layout per grid step: x tile (bm, bk) bf16/f32, packed codes tile
(bk, bn) int8, dequantized tile (bk, bn) f32 (transient), accumulator
(bm, bn) f32 in VMEM scratch; the lookup tables whole in SMEM.
Per-output-channel scales are applied in the epilogue on the final K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 256

#: lookup tables (codebook, D&C sub-tables, residual) live whole in SMEM:
#: the mux trees read them as scalar leaves, which VMEM cannot serve
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _mux_tree_dequant(codes: jax.Array, cb_ref) -> jax.Array:
    """Paper's mux tree: 15 binary selects on the 4 code bits.

    ``codes``: (bk, bn) int32 in [0, 16) (widened from the int8 tile: the
    TPU vector unit has no int8 shifts); ``cb_ref``: (1, 16) codebook in
    SMEM, read as scalar leaves.
    """
    leaves = [cb_ref[0, j] for j in range(16)]   # scalar leaves
    bits = [((codes >> b) & 1) != 0 for b in range(4)]
    level = leaves
    for b in range(4):                            # 8 + 4 + 2 + 1 = 15 selects
        level = [jnp.where(bits[b], level[2 * i + 1], level[2 * i])
                 for i in range(len(level) // 2)]
    return level[0]


def _lut_gemm_kernel(x_ref, codes_ref, cb_ref, scale_ref, o_ref, acc_ref, *,
                     nk: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _mux_tree_dequant(codes_ref[...].astype(jnp.int32), cb_ref)
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k_step == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...] * scale_ref[...]         # (1, bn) broadcast


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lut_gemm(x: jax.Array, w_codes: jax.Array, codebook: jax.Array,
             scale: jax.Array, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
             bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """``x @ (codebook[w_codes] * scale)`` with in-VMEM LUT dequant.

    x: (M, K) float; w_codes: (K, N) int8; codebook: (16,) f32;
    scale: (N,) f32 per-output-channel.  Returns (M, N) f32.
    """
    m, k = x.shape
    k2, n = w_codes.shape
    assert k == k2 and codebook.shape == (16,)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    nk = k // bk

    return pl.pallas_call(
        functools.partial(_lut_gemm_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            _SMEM,
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_codes, codebook.reshape(1, 16), scale.reshape(1, n))


def _dc_mux_dequant(codes: jax.Array, hi_ref, lo_ref) -> jax.Array:
    """Paper's D&C select tree: 3 + 3 binary selects on the 2-bit digits.

    ``codes``: (bk, bn) int32 in [0, 16); ``hi_ref``/``lo_ref``: (1, 4)
    code-space sub-tables in SMEM.  Returns ``HI[codes >> 2] + LO[codes & 3]``.
    """
    def sel4(idx, tab_ref):
        leaves = [tab_ref[0, j] for j in range(4)]
        b0 = (idx & 1) != 0
        b1 = ((idx >> 1) & 1) != 0
        lo = jnp.where(b0, leaves[1], leaves[0])
        hi = jnp.where(b0, leaves[3], leaves[2])
        return jnp.where(b1, hi, lo)

    return sel4((codes >> 2) & 3, hi_ref) + sel4(codes & 3, lo_ref)


def _lut_gemm_dc_kernel(x_ref, codes_ref, hi_ref, lo_ref, zp_ref, scale_ref,
                        o_ref, acc_ref, *, nk: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_q = _dc_mux_dequant(codes_ref[...].astype(jnp.int32), hi_ref, lo_ref)
    w = w_q - zp_ref[...]                                   # (1, bn) bcast
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k_step == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...] * scale_ref[...]          # (1, bn) bcast


def _lut_gemm_dc_res_kernel(x_ref, codes_ref, hi_ref, lo_ref, res_ref,
                            zp_ref, scale_ref, o_ref, acc_ref, *, nk: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = codes_ref[...].astype(jnp.int32)
    # 6-select D&C mux, then the per-code residual gather (a 16:1 select
    # on the residual table — narrow storage in CIM, zeros where pruned)
    w_q = (_dc_mux_dequant(codes, hi_ref, lo_ref)
           + _mux_tree_dequant(codes, res_ref))          # (bk, bn) f32
    w = w_q - zp_ref[...]                                # (1, bn) bcast
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k_step == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...] * scale_ref[...]       # (1, bn) bcast


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lut_gemm_dc_res(x: jax.Array, w_codes: jax.Array, hi_tab: jax.Array,
                    lo_tab: jax.Array, residual: jax.Array,
                    zero_point: jax.Array, scale: jax.Array, *,
                    bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                    bk: int = DEFAULT_BK, interpret: bool = False
                    ) -> jax.Array:
    """``x @ ((HI[q>>2] + LO[q&3] + RES[q] - zp) * scale)`` — the
    residual-corrected D&C dequant for NON-AFFINE codebooks (NF4).

    x: (M, K) float; w_codes: (K, N) int8; hi_tab/lo_tab: (4,) f32
    least-squares sub-tables; residual: (16,) f32 per-code correction
    (zeros at pruned codes); zero_point/scale: (N,) f32 per-output-channel.
    Returns (M, N) f32.  The epilogue order (residual add after the
    6-select mux, zero-point pre-MXU, scale on the final K step) is the
    contract :func:`repro.kernels.lut_gemm.ref.lut_gemm_dc_res_ref`
    mirrors operation-for-operation, so kernel and reference agree
    bitwise on single-K-block shapes.
    """
    m, k = x.shape
    k2, n = w_codes.shape
    assert k == k2 and hi_tab.shape == (4,) and lo_tab.shape == (4,)
    assert residual.shape == (16,)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    nk = k // bk

    return pl.pallas_call(
        functools.partial(_lut_gemm_dc_res_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            _SMEM,
            _SMEM,
            _SMEM,
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_codes, hi_tab.reshape(1, 4), lo_tab.reshape(1, 4),
      residual.reshape(1, 16), zero_point.reshape(1, n), scale.reshape(1, n))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lut_gemm_dc(x: jax.Array, w_codes: jax.Array, hi_tab: jax.Array,
                lo_tab: jax.Array, zero_point: jax.Array, scale: jax.Array,
                *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """``x @ ((HI[q>>2] + LO[q&3] - zp) * scale)`` with D&C in-VMEM dequant.

    x: (M, K) float; w_codes: (K, N) int8; hi_tab/lo_tab: (4,) f32 code-space
    sub-tables; zero_point/scale: (N,) f32 per-output-channel.  Returns
    (M, N) f32.  Six selects per tile vs fifteen in :func:`lut_gemm`.
    """
    m, k = x.shape
    k2, n = w_codes.shape
    assert k == k2 and hi_tab.shape == (4,) and lo_tab.shape == (4,)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    nk = k // bk

    return pl.pallas_call(
        functools.partial(_lut_gemm_dc_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            _SMEM,
            _SMEM,
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_codes, hi_tab.reshape(1, 4), lo_tab.reshape(1, 4),
      zero_point.reshape(1, n), scale.reshape(1, n))
