"""Pallas TPU kernel: programmable-LUT (codebook) weight-only GEMM.

The "programmable" half of LUNA-CIM: weights are 4-bit *codes* into an
arbitrary 16-entry table (the affine grid, NF4 with a full or pruned D&C
residual, or any learned table — see ``core.quant.decode_table``).  One
block body, :func:`_lut_block`, dequantizes each code tile in VMEM
through a binary mux tree of ``2**b - 1 = 15`` vector selects on the code
bits (the exact analogue of the paper's fifteen 2:1 muxes, reading the
table's 16 entries as scalars from SMEM), subtracts the per-channel zero
point before the MXU (the ``z_w`` term of the integer-GEMM identity in
``core.quant``) and applies the scale in the epilogue.  Two entry points:

* :func:`lut_gemm_dc_res` — one weight, the TPU path of every frozen
  decode projection (``ops.lut_matmul``);
* :func:`lut_gemm_dc_res_experts` — a stack of experts in one call (an
  expert axis in the grid), the TPU path of an MoE layer's frozen routed
  experts (``ops.lut_expert_matmul``).

Both keep the names they had when they took the D&C sub-tables and the
residual separately and folded them per block: profiler traces name a
Pallas call by them, and the benchmark's readers and its ledger key on
those names.

Each grid step holds the whole K extent of its x and code blocks and
dequantizes ``bk`` code rows at a time in VMEM, so the codes are read
from HBM once per row block.
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

#: the lookup tables live whole in SMEM: the mux tree reads them as
#: scalar leaves, which VMEM cannot serve
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


def _lut_gemm_kernel(x_ref, codes_ref, table_ref, zp_ref, scale_ref, o_ref,
                     *, bk: int):
    _lut_block(x_ref, codes_ref, table_ref, 0, zp_ref, scale_ref, o_ref,
               bk=bk)


def _lut_gemm_experts_kernel(x_ref, codes_ref, table_ref, zp_ref, scale_ref,
                             o_ref, *, bk: int):
    # grid axis 0 walks the experts; each reads its own table row in SMEM
    _lut_block(x_ref, codes_ref, table_ref, pl.program_id(0), zp_ref,
               scale_ref, o_ref, bk=bk)


def _lut_block(x_ref, codes_ref, table_ref, t, zp_ref, scale_ref, o_ref, *,
               bk: int):
    """One (bm, bn) output block, with row ``t`` of the (T, 16) table in
    SMEM."""
    table = [table_ref[t, q] for q in range(16)]
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
def lut_gemm_dc_res(x: jax.Array, w_codes: jax.Array, table: jax.Array,
                    zero_point: jax.Array, scale: jax.Array, *,
                    bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                    bk: int = DEFAULT_BK, interpret: bool = False
                    ) -> jax.Array:
    """``x @ ((table[q] - zp) * scale)`` with in-VMEM LUT dequant.

    x: (M, K) float; w_codes: (K, N) int8; table: (16,) f32 code-space
    values; zero_point/scale: (N,) f32 per-output-channel.  Returns (M, N)
    in ``x``'s dtype, rounded once from the f32 result.

    Grid ``(cdiv(M, bm), cdiv(N, bn))``.  Neither M nor N need divide by
    its block: an edge block's rows or columns past the array read
    whatever VMEM holds and feed only output rows or columns that are
    never written back (one output row reads one x row; one output
    column, one code column).  The epilogue order (zero point pre-MXU,
    scale after the K loop) is the one
    :func:`repro.kernels.lut_gemm.ref.lut_gemm_dc_res_ref` follows; the
    two differ only in how the dot sums (see ``docs/kernels.md``).
    """
    m, k = x.shape
    k2, n = w_codes.shape
    assert k == k2 and table.shape == (16,), (x.shape, w_codes.shape)
    assert k % bk == 0, (k, bk)

    return pl.pallas_call(
        functools.partial(_lut_gemm_kernel, bk=bk),
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
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
    )(x, w_codes, table.reshape(1, 16), zero_point.reshape(1, n),
      scale.reshape(1, n))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lut_gemm_dc_res_experts(x: jax.Array, w_codes: jax.Array,
                            table: jax.Array, zero_point: jax.Array,
                            scale: jax.Array, *, bm: int = DEFAULT_BM,
                            bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                            interpret: bool = False) -> jax.Array:
    """:func:`lut_gemm_dc_res` over a stack of experts in one call:
    ``out[e] = x[e] @ ((table_e[q] - zp_e) * scale_e)`` with
    ``q = w_codes[e]``.

    x: (E, M, K) float; w_codes: (E, K, N) int8; table: (E, 16);
    zero_point/scale: (E, N).  Returns (E, M, N) in ``x``'s dtype.  Grid
    ``(E, cdiv(M, bm), cdiv(N, bn))``: the expert axis outermost, each
    step the block of :func:`lut_gemm_dc_res` with that expert's table,
    so one expert's codes are read once per row block.
    """
    e, m, k = x.shape
    e2, k2, n = w_codes.shape
    assert e == e2 and k == k2, (x.shape, w_codes.shape)
    assert table.shape == (e, 16), table.shape
    assert k % bk == 0, (k, bk)

    return pl.pallas_call(
        functools.partial(_lut_gemm_experts_kernel, bk=bk),
        grid=(e, pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((None, bm, k), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, k, bn), lambda g, i, j: (g, 0, j)),
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
    )(x, w_codes, table, zero_point.reshape(e, 1, n),
      scale.reshape(e, 1, n))


def _vmem_bytes(bm: int, k: int, bn: int, bk: int) -> int:
    """Scoped VMEM for one :func:`lut_gemm_dc_res` step: double-buffered
    x (f32 at most), code and output blocks, room for a few (bk, bn) f32
    transients of the select tree, and 8 MiB spare."""
    blocks = 2 * (bm * k * 4 + k * bn + bm * bn * 4)
    return blocks + 8 * bk * bn * 4 + 8 * 2 ** 20
