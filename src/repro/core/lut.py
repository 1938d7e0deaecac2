"""Lookup-table machinery: product tables, codebooks, and mux-tree selection.

The paper's select logic is a binary tree of 2:1 muxes (15 of them for a
16-entry table).  The TPU-native analogue is a binary tree of ``jnp.where``
selects on the index bits — ``2**b - 1`` selects for a ``2**b``-entry table,
exactly the paper's mux count.  This is what makes the LUT *programmable*:
the same tree evaluates any codebook (uniform int4, NF4, arbitrary 16-value
tables), which is the beyond-paper generalization used by ``kernels.lut_gemm``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# The NF4 codebook (QLoRA, Dettmers et al. 2023) — a non-linear 16-entry LUT
# that the paper's mux-tree evaluates at identical hardware cost to uniform
# int4.  Demonstrates LUNA "programmability" beyond uniform quantization.
NF4_CODEBOOK = np.array([
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0,
], dtype=np.float32)


def product_table(w_codes: jax.Array, bits: int = 4) -> jax.Array:
    """Conventional-LUT product table (paper Fig 1): entry ``j = j * W``.

    Returns shape ``(2**bits, *w_codes.shape)`` int32.
    """
    idx = jnp.arange(1 << bits, dtype=jnp.int32)
    return idx.reshape((-1,) + (1,) * w_codes.ndim) * w_codes.astype(jnp.int32)[None]


def dc_table(w_codes: jax.Array, digit_bits: int = 2) -> jax.Array:
    """D&C sub-multiplier table {0, W, 2W, 3W} (paper Figs 2/3)."""
    return product_table(w_codes, digit_bits)


def mux_tree_select(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Select ``table[idx]`` with a binary tree of 2:1 selects on idx bits.

    ``table``: ``(2**b, *S)`` where ``S`` broadcasts against ``idx.shape``.
    Uses ``2**b - 1`` vector selects — the paper's mux-tree, vectorized.
    Works under Pallas (no gather required).
    """
    n = table.shape[0]
    b = n.bit_length() - 1
    assert n == 1 << b, f"table size {n} not a power of two"
    level = table
    for bit in range(b):
        sel = ((idx >> bit) & 1).astype(bool)
        lo, hi = level[0::2], level[1::2]
        # broadcast sel against entry shape
        sel_b = jnp.broadcast_to(sel, jnp.broadcast_shapes(sel.shape, lo.shape[1:]))
        level = jnp.where(sel_b[None], hi, lo)
    return level[0]


def mux_count(table_size: int, out_bits: int) -> int:
    """Paper's 1-bit 2:1 mux count for a ``table_size``:1 mux of ``out_bits``."""
    return (table_size - 1) * out_bits


def codebook_dequant(codes: jax.Array, codebook: jax.Array) -> jax.Array:
    """Dequantize integer codes through an arbitrary codebook via mux tree."""
    return mux_tree_select(codebook.reshape(-1, *([1] * codes.ndim)), codes)


def dc_decompose_codebook(codebook: jax.Array, digit_bits: int = 2
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Least-squares additive D&C split of a ``2**(2*digit_bits)``-entry LUT.

    The paper's Figs 2/3 decompose the 4-bit multiply LUT into two 2-bit
    sub-tables summed after selection: ``T[q] ~= HI[q >> digit_bits] +
    LO[q & (2**digit_bits - 1)]``.  For any *affine* codebook (uniform
    int4) the split is exact; for non-linear tables (NF4) this returns the
    least-squares-optimal additive pair (row/column means of the table
    viewed as a ``(2**digit_bits, 2**digit_bits)`` grid, grand mean folded
    into HI) plus the per-entry residual — the price of evaluating a
    programmable LUT with ``2 * (2**digit_bits - 1)`` muxes instead of
    ``2**(2*digit_bits) - 1`` (6 vs 15: the select tree behind the paper's
    ~3.7x area figure).

    Returns ``(hi_tab, lo_tab, residual)`` with ``hi_tab``/``lo_tab`` of
    shape ``(2**digit_bits,)`` and ``residual`` of ``codebook.shape``.
    """
    d = 1 << digit_bits
    grid = jnp.asarray(codebook, jnp.float32).reshape(d, d)  # [hi, lo]
    mean = jnp.mean(grid)
    hi_tab = jnp.mean(grid, axis=1)               # row means (grand mean kept)
    lo_tab = jnp.mean(grid, axis=0) - mean        # column means, centered
    residual = (grid - hi_tab[:, None] - lo_tab[None, :]).reshape(-1)
    return hi_tab, lo_tab, residual


def prune_residual(residual: jax.Array, threshold: float
                   ) -> tuple[jax.Array, jax.Array]:
    """Sparsify a D&C residual table: keep entries with ``|r| >= threshold``.

    The LUT-pruning tradeoff (PAPERS.md, Zhu et al.): residual entries
    below the threshold contribute less to reconstruction than they cost
    in table capacity, so they are dropped and only the kept set is
    stored.  Returns ``(kept_idx, kept_val)`` — int32 code indices and
    their residual values, the sparse representation a pruned sub-table
    stores (each kept entry costs one value plus a 1-byte code index
    instead of a dense slot for every code).
    """
    res = jnp.asarray(residual, jnp.float32)
    keep = np.flatnonzero(np.abs(np.asarray(res)) >= threshold)
    kept_idx = jnp.asarray(keep, jnp.int32)
    return kept_idx, res[kept_idx]


def dc_decompose_pruned(codebook: jax.Array, threshold: float,
                        digit_bits: int = 2
                        ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """D&C split of a LUT whose residual is pruned at ``threshold``.

    The kept codes are those of :func:`prune_residual` on the full split's
    residual, and they read their exact table value.  HI/LO are then the
    least-squares fit over the codes that fall through to the pure
    ``HI + LO`` sum only: fitting them to every code, kept ones included,
    spends the split's freedom on values the residual corrects anyway
    (NF4 at 0.05: worst fall-through error 0.047, a third of a code step,
    against 0.0021 refit).  Returns ``(hi_tab, lo_tab, kept_idx,
    kept_val)``; ``kept_val`` is each kept code's exact residual.
    """
    d = 1 << digit_bits
    cb = jnp.asarray(codebook, jnp.float32)
    _, _, residual = dc_decompose_codebook(cb, digit_bits)
    kept_idx, _ = prune_residual(residual, threshold)
    drop = np.ones(d * d, bool)
    drop[np.asarray(kept_idx)] = False
    q = np.arange(d * d)
    design = np.zeros((d * d, 2 * d))
    design[q, q >> digit_bits] = 1.0
    design[q, d + (q & (d - 1))] = 1.0
    fit = np.linalg.lstsq(design[drop], np.asarray(cb, np.float64)[drop],
                          rcond=None)[0]
    hi_tab = jnp.asarray(fit[:d], jnp.float32)
    lo_tab = jnp.asarray(fit[d:], jnp.float32)
    res = cb - (hi_tab[:, None] + lo_tab[None, :]).reshape(-1)
    return hi_tab, lo_tab, kept_idx, res[kept_idx]


def scatter_residual(kept_idx: jax.Array, kept_val: jax.Array,
                     size: int = 16) -> jax.Array:
    """Densify a pruned residual for evaluation: dropped codes read 0.

    The sparse gather semantics of a pruned sub-table — a code either hits
    a kept entry or falls through to the pure ``HI + LO`` sum — expressed
    as one scatter into a zero table so the select tree stays uniform.
    """
    return jnp.zeros((size,), jnp.float32).at[kept_idx].set(kept_val)


def residual_table_bytes(n_kept: int, n_codes: int = 16,
                         value_bytes: int = 4, index_bytes: int = 1
                         ) -> tuple[int, int]:
    """(dense, pruned) storage bytes of a residual sub-table.

    Dense stores one value per code; the pruned form stores only the kept
    ``(index, value)`` pairs.  Used by the benches to report the capacity
    side of the LUT-pruning accuracy tradeoff.
    """
    dense = n_codes * value_bytes
    pruned = n_kept * (value_bytes + index_bytes)
    return dense, pruned
