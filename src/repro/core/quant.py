"""Quantization substrate: affine quantizers, calibration, and the real-valued
LUNA matmul (integer core + zero-point corrections + STE for QAT).

The paper's operands are unsigned 4-bit codes.  Real tensors are mapped to
unsigned codes with asymmetric affine quantization::

    x ~= s_x * (q_x - z_x),   q_x in [0, 2**bits)

and the matmul identity (standard integer-GEMM algebra) recovers the real
product from the code-space LUNA accumulation::

    x @ w ~= s_x s_w [ L(q_x, q_w) - z_x colsum(q_w) - rowsum(q_x) z_w
                       + K z_x z_w ]

where ``L`` is ``luna_matmul`` in any mode.  For approx modes the paper's
code-space error flows through the same identity scaled by ``s_x s_w`` —
which is exactly how the paper's Fig 13 NN-level MAE arises.

Serving-side weight-only quantization (this module's second half) applies
the same algebra statically: :class:`QuantizedWeight` freezes a projection
into 4-bit codes + per-channel :class:`QParams` at engine construction, and
:func:`quantize_decode_params` walks a model param tree replacing every
decode-projection leaf.  The D&C sub-tables stored alongside the codes are
the paper's Fig 2/3 decomposition of the 16-entry code LUT: a 4-bit code
``q`` splits into 2-bit digits ``q = 4*q_hi + q_lo``, so the 16-entry table
is evaluated as the sum of two 4-entry sub-tables (``HI[i] = 4i``,
``LO[j] = j``) — 2 × (2**2 − 1) = 6 mux selects instead of 15, the select
cost behind the paper's ~3.7× area saving.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.luna import LunaMode, luna_matmul


class QParams(NamedTuple):
    scale: jax.Array       # per-tensor () or per-channel (N,)
    zero_point: jax.Array  # same shape as scale, unsigned-code zero point
    bits: int


def calibrate(x: jax.Array, bits: int = 4, axis: int | None = None,
              symmetric: bool = False) -> QParams:
    """Min/max affine calibration to unsigned codes.

    ``axis``: reduction keeps this axis (per-channel); None = per-tensor.
    ``symmetric``: centers the range on 0 (zero_point at mid-code).
    """
    qmax = (1 << bits) - 1
    if axis is None:
        lo = jnp.min(x)
        hi = jnp.max(x)
    else:
        red = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
        lo = jnp.min(x, axis=red)
        hi = jnp.max(x, axis=red)
    if symmetric:
        amax = jnp.maximum(jnp.abs(lo), jnp.abs(hi))
        lo, hi = -amax, amax
    scale = jnp.maximum(hi - lo, 1e-8) / qmax
    zp = jnp.clip(jnp.round(-lo / scale), 0, qmax)
    return QParams(scale.astype(jnp.float32), zp.astype(jnp.float32), bits)


def quantize(x: jax.Array, qp: QParams) -> jax.Array:
    """Real -> unsigned integer codes (int32 carrier)."""
    qmax = (1 << qp.bits) - 1
    codes = jnp.round(x / qp.scale + qp.zero_point)
    return jnp.clip(codes, 0, qmax).astype(jnp.int32)


def dequantize(codes: jax.Array, qp: QParams) -> jax.Array:
    return (codes.astype(jnp.float32) - qp.zero_point) * qp.scale


def quant_error(x: jax.Array, qp: QParams) -> jax.Array:
    return dequantize(quantize(x, qp), qp) - x


# ---------------------------------------------------------------------------
# Real-valued LUNA matmul
# ---------------------------------------------------------------------------

def luna_matmul_f32(x: jax.Array, w: jax.Array, mode: LunaMode | str,
                    bits: int = 4, x_qp: QParams | None = None,
                    w_qp: QParams | None = None) -> jax.Array:
    """Float-in/float-out matmul with LUNA integer arithmetic inside.

    ``x``: (..., K); ``w``: (K, N).  Dynamic per-tensor activation quant,
    per-output-channel weight quant unless QParams are provided (static PTQ).
    """
    mode = LunaMode(mode)
    x_qp = x_qp or calibrate(x, bits, axis=None)
    w_qp = w_qp or calibrate(w, bits, axis=-1)
    qx = quantize(x, x_qp)
    qw = quantize(w, w_qp)
    k = x.shape[-1]

    acc = luna_matmul(qx, qw, bits=bits, mode=mode).astype(jnp.float32)
    colsum_qw = jnp.sum(qw, axis=0).astype(jnp.float32)           # (N,)
    rowsum_qx = jnp.sum(qx, axis=-1, keepdims=True).astype(jnp.float32)
    zx, zw = x_qp.zero_point, w_qp.zero_point
    corrected = (acc
                 - zx * colsum_qw
                 - rowsum_qx * zw
                 + k * zx * zw)
    return (x_qp.scale * w_qp.scale) * corrected


# ---------------------------------------------------------------------------
# QAT: straight-through estimator — forward runs the exact LUNA integer path,
# backward pretends it was a plain matmul.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def ste_luna_matmul(x: jax.Array, w: jax.Array, mode: str, bits: int = 4):
    return luna_matmul_f32(x, w, mode, bits)


def _ste_fwd(x, w, mode, bits):
    return luna_matmul_f32(x, w, mode, bits), (x, w)


def _ste_bwd(mode, bits, res, g):
    x, w = res
    gx = jnp.einsum("...n,kn->...k", g, w)
    batch = x.reshape(-1, x.shape[-1])
    gw = batch.T @ g.reshape(-1, g.shape[-1])
    return gx.astype(x.dtype), gw.astype(w.dtype)


ste_luna_matmul.defvjp(_ste_fwd, _ste_bwd)


# ---------------------------------------------------------------------------
# Serving-side weight-only quantization: frozen 4-bit decode weights.
# ---------------------------------------------------------------------------

#: evaluation strategies for a frozen 4-bit weight (EngineConfig(quant=...)):
#: "lut_dc" sums the paper's two 2-bit D&C sub-tables through the mux tree;
#: "dequant" is the conventional-math baseline (direct affine dequant).
#: Both reconstruct the identical affine grid — tokens match bit-for-bit.
#: "nf4_dc" evaluates the NON-AFFINE NF4 codebook as HI + LO + a per-code
#: residual correction (the least-squares D&C split of core.lut, possibly
#: pruned); "nf4_dequant" is its conventional baseline (direct 16-entry
#: codebook lookup — the oracle the residual path is pinned against).
WEIGHT_KERNELS = ("lut_dc", "dequant", "nf4_dc", "nf4_dequant")

#: default |residual| magnitude threshold for pruned sub-tables
#: (quant="nf4p"): keeps exactly half the NF4 residual table's 16 entries
#: — the capacity/accuracy operating point reported in the benches.
NF4P_PRUNE_THRESHOLD = 0.05


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class QuantizedWeight:
    """A projection weight frozen to unsigned 4-bit codes (paper Sec. III).

    ``codes``: (..., K, N) int8 codes in [0, 16); ``scale``/``zero_point``:
    (..., N) per-output-channel affine params from :func:`calibrate`;
    ``hi_tab``/``lo_tab``: (..., 4) D&C sub-tables in code space
    (``q = hi_tab[q >> 2] + lo_tab[q & 3]`` exactly for the affine kernels
    — the Fig 2/3 split of the 16-entry LUT into two 4-entry tables).
    ``residual``: ``None`` for affine kernels (the split is exact); for the
    non-affine NF4 kernels a (..., 16) per-code correction table
    (``cb[q] ~= hi_tab[q >> 2] + lo_tab[q & 3] + residual[q]``), dense or
    pruned-to-zero below the magnitude threshold (see
    :func:`repro.core.lut.prune_residual`).  ``kernel`` is static pytree
    aux data selecting the evaluation strategy (see ``WEIGHT_KERNELS``).

    Registered as a pytree so a stacked instance (leading layer axis on
    every array child) slices cleanly under ``jax.lax.scan`` and traces
    through ``jax.jit`` like any other param leaf (a ``None`` residual is
    an empty subtree, so affine instances flatten exactly as before).
    """
    codes: jax.Array
    scale: jax.Array
    zero_point: jax.Array
    hi_tab: jax.Array
    lo_tab: jax.Array
    residual: jax.Array | None = None
    kernel: str = "lut_dc"

    def tree_flatten(self):
        return ((self.codes, self.scale, self.zero_point,
                 self.hi_tab, self.lo_tab, self.residual), self.kernel)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, kernel=aux)

    @property
    def qparams(self) -> QParams:
        return QParams(self.scale, self.zero_point, 4)


def _nf4_dc_tables(prune_threshold: float | None):
    """(hi, lo, residual) least-squares D&C split of the NF4 codebook,
    residual optionally pruned to the kept-set sparse gather (dropped
    codes read 0 and fall through to the pure HI + LO sum)."""
    from repro.core.lut import (NF4_CODEBOOK, dc_decompose_codebook,
                                prune_residual, scatter_residual)
    hi_tab, lo_tab, residual = dc_decompose_codebook(jnp.asarray(NF4_CODEBOOK))
    if prune_threshold is not None:
        kept_idx, kept_val = prune_residual(residual, prune_threshold)
        residual = scatter_residual(kept_idx, kept_val)
    return hi_tab, lo_tab, residual


def quantize_weight(w: jax.Array, kernel: str = "lut_dc",
                    prune_threshold: float | None = None) -> QuantizedWeight:
    """Freeze a (…, K, N) float weight to a :class:`QuantizedWeight`.

    Affine kernels (``"lut_dc"`` / ``"dequant"``) calibrate per output
    channel over the K axis (the paper's operands are unsigned codes; see
    the module docstring identity) and carry the exact code-space split
    ``HI[i] = 4i``, ``LO[j] = j`` with no residual.  The NF4 kernels
    (``"nf4_dc"`` / ``"nf4_dequant"``) encode against the non-affine NF4
    codebook with per-output-channel absmax scaling (the codebook is
    symmetric on [-1, 1], so ``zero_point`` is 0) and carry the
    least-squares D&C split of the codebook plus its per-code residual —
    pruned below ``prune_threshold`` when given (``quant="nf4p"``).

    Leaves with extra leading axes (scan-stacked layers) are quantized
    one slice at a time and stacked, so every array child carries the same
    leading axes and the container remains ``jax.lax.scan``-sliceable.
    """
    if kernel not in WEIGHT_KERNELS:
        raise ValueError(f"unknown weight kernel {kernel!r}; "
                         f"one of {WEIGHT_KERNELS}")
    if w.ndim > 2:
        # sequential on purpose: the NF4 encode's (K, N, 16) f32 distance
        # temporary exists for one slice only (a vmap over 38 full-width
        # Mamba2 w_in slices would need 42 GB), and each slice is bitwise
        # the 2-D encode (a lax.map under jit moves scales by an ulp)
        slices = [quantize_weight(w[i], kernel, prune_threshold)
                  for i in range(w.shape[0])]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
    wf = w.astype(jnp.float32)
    if kernel in ("nf4_dc", "nf4_dequant"):
        from repro.core.lut import NF4_CODEBOOK
        cb = jnp.asarray(NF4_CODEBOOK)
        scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=0), 1e-8)
        wn = wf / scale[None, :]
        codes = jnp.argmin(jnp.abs(wn[..., None] - cb), axis=-1)
        hi_tab, lo_tab, residual = _nf4_dc_tables(prune_threshold)
        return QuantizedWeight(codes.astype(jnp.int8),
                               scale.astype(jnp.float32),
                               jnp.zeros_like(scale, jnp.float32),
                               hi_tab, lo_tab, residual=residual,
                               kernel=kernel)
    qp = calibrate(wf, bits=4, axis=-1)
    codes = quantize(wf, qp).astype(jnp.int8)
    # D&C sub-tables (code space): q = HI[q>>2] + LO[q&3], HI[i]=4i, LO[j]=j.
    hi_tab = (4.0 * jnp.arange(4, dtype=jnp.float32))
    lo_tab = jnp.arange(4, dtype=jnp.float32)
    return QuantizedWeight(codes, qp.scale, qp.zero_point,
                           hi_tab, lo_tab, kernel=kernel)


#: decode-projection leaf names eligible for engine-level quantization.
#: Everything here is consumed through ``core.layers.quant_matmul`` or,
#: for the stacked routed experts of an MoE layer, through
#: ``kernels.lut_gemm.ops.quantized_expert_matmul``; leaves used directly
#: (MLA's w_uk/w_uv reshapes, routers, norms, embeddings, lm_head) are
#: deliberately absent.
DECODE_QUANT_TARGETS = frozenset({
    "wq", "wk", "wv", "wo", "w_dq", "w_uq", "w_dkv",      # attention
    "w_up", "w_gate", "w_down",                            # mlp / moe experts
    "w_in", "w_out",                                       # mamba2 mixer
})

#: dict keys whose subtrees hold frozen projections.  MoE routed experts
#: live directly under "moe" as stacked (E, in, out) leaves sharing the
#: mlp leaf NAMES: each expert's matrix is frozen with its own
#: per-output-column scale (``quantize_weight`` encodes stacked leaves
#: slice by slice); the router beside them is no target.
_QUANT_PARENT_KEYS = frozenset({"attn", "mlp", "m", "shared", "moe"})


#: EngineConfig(quant=...) mode -> (weight kernel, residual prune threshold).
#: ``nf4_direct`` is not an engine mode: it is the conventional full-table
#: NF4 dequant oracle the residual-corrected ``nf4`` path is pinned against
#: in tests and the fig13 harness.
DECODE_QUANT_KERNELS = {
    "lut4": ("lut_dc", None),
    "int4": ("dequant", None),
    "nf4": ("nf4_dc", None),
    "nf4p": ("nf4_dc", NF4P_PRUNE_THRESHOLD),
    "nf4_direct": ("nf4_dequant", None),
}


def quantize_decode_params(params, quant: str):
    """Walk a model param tree, freezing every decode projection to 4-bit.

    ``quant``: ``"lut4"`` (affine D&C sub-table LUT evaluation), ``"int4"``
    (direct-dequant baseline), ``"nf4"`` (non-affine NF4 codebook, D&C
    sub-tables + per-code residual correction), ``"nf4p"`` (same with the
    residual pruned below ``NF4P_PRUNE_THRESHOLD``), or ``"nf4_direct"``
    (full-table NF4 dequant — the test oracle, not an engine mode).  A
    leaf is quantized iff its dict key is in ``DECODE_QUANT_TARGETS``, some
    ancestor key is in the quant-parent set, and it is a float matrix —
    everything else (norms, embeddings, routers, MLA w_uk/w_uv) passes
    through untouched.
    """
    kernel, prune = DECODE_QUANT_KERNELS[quant]

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            sub = [walk(v, path) for v in node]
            return type(node)(sub)
        if (path and path[-1] in DECODE_QUANT_TARGETS
                and any(p in _QUANT_PARENT_KEYS for p in path[:-1])
                and hasattr(node, "ndim") and node.ndim >= 2
                and jnp.issubdtype(node.dtype, jnp.floating)):
            return quantize_weight(node, kernel, prune)
        return node

    return walk(params, ())


#: the draft-weight mode for self-speculative decoding: the pruned-LUT NF4
#: tree is the cheapest decode path the engine owns, and LoCalut's
#: capacity-computation tradeoff says that is exactly where to spend the
#: draft budget — table bytes for draft throughput, full precision verifies.
SPEC_DRAFT_QUANT = "nf4p"


def quantize_draft_params(params, quant: str = SPEC_DRAFT_QUANT):
    """Draft-model weights for self-speculative decoding.

    The drafter is the SAME model with its decode projections frozen in
    their pruned-LUT form (default :data:`SPEC_DRAFT_QUANT`): no second
    set of trained weights, no separate cache layout — the draft step runs
    ``decode_step`` over this tree against a throwaway copy of the live
    caches while the full-precision tree scores the drafted window in one
    batched verify pass.  When the engine already decodes at the draft
    mode (``EngineConfig(quant="nf4p")``) the engine aliases its decode
    tree instead of calling this twice.
    """
    return quantize_decode_params(params, quant)
