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
  least-squares residual of ``core.lut.dc_decompose_codebook`` is added
  per code.  With the full residual the reconstruction is exact up to
  float rounding; with a pruned residual (``quant="nf4p"``) dropped codes
  fall through to the pure HI+LO sum and the table trades capacity for a
  bounded accuracy cost.  The kernel folds HI, LO and the residual into
  one 16-entry table on the scalar unit (bitwise the per-element values)
  and evaluates it with one 15-select tree.  It is the TPU path of the
  engine's ``nf4_dc`` decode matmul (``ops.nf4_dc_matmul``).
* :func:`lut_gemm_dc_res_experts` — the same block over a stack of
  experts in one call (an expert axis in the grid), the TPU path of an
  MoE layer's frozen routed experts (``ops.nf4_dc_expert_matmul``).

Memory layout per grid step of ``lut_gemm`` and ``lut_gemm_dc``: x tile
(bm, bk) bf16/f32, codes tile (bk, bn) int8, dequantized tile (bk, bn)
f32 (transient), accumulator (bm, bn) f32 in VMEM scratch; the lookup
tables whole in SMEM.  Per-output-channel scales are applied in the
epilogue on the final K step.  ``lut_gemm_dc_res`` holds the whole K
extent per step instead and loops over it in VMEM (see its docstring).
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


def _mux_tree_dequant(codes: jax.Array, leaves) -> jax.Array:
    """Paper's mux tree: 15 binary selects on the 4 code bits.

    ``codes``: int32 in [0, 16) (widened from the int8 tile: the TPU
    vector unit has no int8 shifts); ``leaves``: the 16 table entries as
    scalars (read from SMEM).  Depth first, so at most one partial result
    per tree level is live.
    """
    bits = [(codes & (1 << b)) != 0 for b in range(4)]

    def tree(lo: int, b: int):                    # leaves[lo : lo + 2**(b+1)]
        if b < 0:
            return leaves[lo]
        return jnp.where(bits[b], tree(lo + (1 << b), b - 1), tree(lo, b - 1))

    return tree(0, 3)                             # 8 + 4 + 2 + 1 = 15 selects


def _lut_gemm_kernel(x_ref, codes_ref, cb_ref, scale_ref, o_ref, acc_ref, *,
                     nk: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _mux_tree_dequant(codes_ref[...].astype(jnp.int32),
                          [cb_ref[0, j] for j in range(16)])
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
                            zp_ref, scale_ref, o_ref, *, bk: int):
    _dc_res_block(x_ref, codes_ref, hi_ref, lo_ref, res_ref, 0, zp_ref,
                  scale_ref, o_ref, bk=bk)


def _lut_gemm_dc_res_experts_kernel(x_ref, codes_ref, hi_ref, lo_ref,
                                    res_ref, zp_ref, scale_ref, o_ref, *,
                                    bk: int):
    # grid axis 0 walks the experts; each holds its own tables in SMEM
    _dc_res_block(x_ref, codes_ref, hi_ref, lo_ref, res_ref,
                  pl.program_id(0), zp_ref, scale_ref, o_ref, bk=bk)


def _dc_res_block(x_ref, codes_ref, hi_ref, lo_ref, res_ref, t, zp_ref,
                  scale_ref, o_ref, *, bk: int):
    """One (bm, bn) output block of the residual-corrected D&C GEMM, with
    row ``t`` of the (T, 4) / (T, 16) tables in SMEM."""
    # the 6-select D&C sum and the per-code residual gather, folded into
    # one 16-entry table on the scalar unit in the per-element order
    # (HI[q>>2] + LO[q&3]) + RES[q]: the same values bit for bit, for one
    # 15-select tree per element instead of 6 + 15 selects and two adds
    table = [(hi_ref[t, q >> 2] + lo_ref[t, q & 3]) + res_ref[t, q]
             for q in range(16)]
    zp = zp_ref[...]                                     # (1, bn)

    def k_chunk(rows, acc):
        w = _mux_tree_dequant(codes_ref[rows, :].astype(jnp.int32),
                              table) - zp                # (bk, bn) f32
        x = x_ref[:, rows].astype(jnp.float32)
        return acc + jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    zero = jnp.zeros(o_ref.shape, jnp.float32)
    nk = codes_ref.shape[0] // bk
    if nk == 1:
        # one chunk is the whole K extent: read it whole, since a K that
        # is no multiple of 128 (DeepSeek-V2-Lite's dense 10944) cannot be
        # sliced at a lane offset the loop computes
        acc = k_chunk(slice(None), zero)
    else:
        acc = jax.lax.fori_loop(
            0, nk, lambda c, a: k_chunk(
                pl.ds(pl.multiple_of(c * bk, bk), bk), a), zero)
    o_ref[...] = (acc * scale_ref[...]).astype(o_ref.dtype)  # (1, bn) bcast


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
    Returns (M, N) in ``x``'s dtype, rounded once from the f32 result.

    Grid ``(cdiv(M, bm), cdiv(N, bn))``: each step holds the whole K
    extent of its x and code blocks and dequantizes ``bk`` code rows at a
    time in VMEM, so the codes are read from HBM once per row block.
    Neither M nor N need divide by its block: an edge block's rows or
    columns past the array read whatever VMEM holds and feed only output
    rows or columns that are never written back (one output row reads
    one x row; one output column, one code column).
    The epilogue order (residual after the D&C sum, zero-point pre-MXU,
    scale after the K loop) is the one
    :func:`repro.kernels.lut_gemm.ref.lut_gemm_dc_res_ref` follows; the
    two differ only in how the dot sums (see ``docs/kernels.md``).
    """
    m, k = x.shape
    k2, n = w_codes.shape
    assert k == k2 and hi_tab.shape == (4,) and lo_tab.shape == (4,)
    assert residual.shape == (16,)
    assert k % bk == 0, (k, bk)

    return pl.pallas_call(
        functools.partial(_lut_gemm_dc_res_kernel, bk=bk),
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            _SMEM,
            _SMEM,
            _SMEM,
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_bytes(bm, k, bn, bk)),
        name="lut_gemm_dc_res",
        interpret=interpret,
    )(x, w_codes, hi_tab.reshape(1, 4), lo_tab.reshape(1, 4),
      residual.reshape(1, 16), zero_point.reshape(1, n), scale.reshape(1, n))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lut_gemm_dc_res_experts(x: jax.Array, w_codes: jax.Array,
                            hi_tab: jax.Array, lo_tab: jax.Array,
                            residual: jax.Array, zero_point: jax.Array,
                            scale: jax.Array, *, bm: int = DEFAULT_BM,
                            bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                            interpret: bool = False) -> jax.Array:
    """:func:`lut_gemm_dc_res` over a stack of experts in one call:
    ``out[e] = x[e] @ ((HI_e[q>>2] + LO_e[q&3] + RES_e[q] - zp_e) *
    scale_e)`` with ``q = w_codes[e]``.

    x: (E, M, K) float; w_codes: (E, K, N) int8; hi_tab/lo_tab: (E, 4);
    residual: (E, 16); zero_point/scale: (E, N).  Returns (E, M, N) in
    ``x``'s dtype.  Grid ``(E, cdiv(M, bm), cdiv(N, bn))``: the expert
    axis outermost, each step the block of :func:`lut_gemm_dc_res` with
    that expert's tables, so one expert's codes are read once per row
    block.  Named ``lut_gemm_dc_res_experts`` in a profiler trace.
    """
    e, m, k = x.shape
    e2, k2, n = w_codes.shape
    assert e == e2 and k == k2, (x.shape, w_codes.shape)
    assert hi_tab.shape == (e, 4) and lo_tab.shape == (e, 4)
    assert residual.shape == (e, 16)
    assert k % bk == 0, (k, bk)

    return pl.pallas_call(
        functools.partial(_lut_gemm_dc_res_experts_kernel, bk=bk),
        grid=(e, pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((None, bm, k), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, k, bn), lambda g, i, j: (g, 0, j)),
            _SMEM,
            _SMEM,
            _SMEM,
            pl.BlockSpec((None, 1, bn), lambda g, i, j: (g, 0, j)),
            pl.BlockSpec((None, 1, bn), lambda g, i, j: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn), lambda g, i, j: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_bytes(bm, k, bn, bk)),
        name="lut_gemm_dc_res_experts",
        interpret=interpret,
    )(x, w_codes, hi_tab, lo_tab, residual, zero_point.reshape(e, 1, n),
      scale.reshape(e, 1, n))


def _vmem_bytes(bm: int, k: int, bn: int, bk: int) -> int:
    """Scoped VMEM for one :func:`lut_gemm_dc_res` step: double-buffered
    x (f32 at most), code and output blocks, room for a few (bk, bn) f32
    transients of the select tree, and 8 MiB spare."""
    blocks = 2 * (bm * k * 4 + k * bn + bm * bn * 4)
    return blocks + 8 * bk * bn * 4 + 8 * 2 ** 20


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
