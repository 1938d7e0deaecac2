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
into 4-bit codes, per-channel scale and zero point, and one 16-entry
code-space table at engine construction, and
:func:`quantize_decode_params` walks a model param tree replacing every
decode-projection leaf.  The table is derived through the paper's Fig 2/3
decomposition of the 16-entry code LUT: a 4-bit code ``q`` splits into
2-bit digits ``q = 4*q_hi + q_lo`` and the entry is the sum of two 4-entry
sub-tables (``HI[i] = 4i``, ``LO[j] = j`` for the affine grid; a
least-squares split plus a per-code residual for NF4) — folded once at
the freeze, so the kernel reads one table.
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

#: |residual| magnitude threshold of the pruned NF4 table (quant="nf4p"):
#: keeps exactly half the NF4 residual table's 16 entries.
NF4P_PRUNE_THRESHOLD = 0.05

#: the modes a weight freezes to: the engine's ``EngineConfig.quant``
#: modes plus ``nf4_direct``, the full-table NF4 oracle that tests and the
#: fig13 harness pin ``nf4`` against (not an engine mode).
DECODE_QUANT_MODES = ("lut4", "nf4", "nf4p", "nf4_direct")


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class QuantizedWeight:
    """A projection weight frozen to unsigned 4-bit codes (paper Sec. III).

    ``codes``: (..., K, N) int8 codes in [0, 16); ``scale``/``zero_point``:
    (..., N) per-output-channel params; ``table``: (..., 16) f32 values in
    code space.  The weight is ``(table[q] - zero_point) * scale``: every
    mode is one 16-entry table read through the paper's mux tree (see
    :func:`decode_table` for how each mode derives its table).

    Registered as a pytree so a stacked instance (leading layer axis on
    every array child) slices cleanly under ``jax.lax.scan`` and traces
    through ``jax.jit`` like any other param leaf.
    """
    codes: jax.Array
    scale: jax.Array
    zero_point: jax.Array
    table: jax.Array

    def tree_flatten(self):
        return (self.codes, self.scale, self.zero_point, self.table), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def decode_table(quant: str) -> jax.Array:
    """The (16,) f32 code-space table a weight frozen at ``quant`` carries.

    The affine and NF4 tables are the paper's D&C split (Figs 2/3) folded
    once: ``(HI[q >> 2] + LO[q & 3]) + RES[q]``.

    * ``lut4``: ``HI[i] = 4i``, ``LO[j] = j``, no residual — exactly ``q``.
    * ``nf4``: the least-squares split of the NF4 codebook
      (:func:`repro.core.lut.dc_decompose_codebook`) plus its full
      per-code residual.
    * ``nf4p``: the split refit with its residual pruned at
      :data:`NF4P_PRUNE_THRESHOLD` (:func:`repro.core.lut.dc_decompose_pruned`):
      dropped codes read the pure ``HI + LO`` sum.
    * ``nf4_direct``: the NF4 codebook itself, the oracle.
    """
    from repro.core.lut import (NF4_CODEBOOK, dc_decompose_codebook,
                                dc_decompose_pruned, scatter_residual)
    if quant not in DECODE_QUANT_MODES:
        raise ValueError(f"unknown decode quant mode {quant!r}; "
                         f"one of {DECODE_QUANT_MODES}")
    # a one-off constant: evaluated now even when the freeze is traced
    with jax.ensure_compile_time_eval():
        cb = jnp.asarray(NF4_CODEBOOK)
        if quant == "nf4_direct":
            return cb
        if quant == "lut4":
            hi_tab = 4.0 * jnp.arange(4, dtype=jnp.float32)
            lo_tab = jnp.arange(4, dtype=jnp.float32)
            residual = jnp.zeros((16,), jnp.float32)
        elif quant == "nf4":
            hi_tab, lo_tab, residual = dc_decompose_codebook(cb)
        else:
            hi_tab, lo_tab, kept_idx, kept_val = dc_decompose_pruned(
                cb, NF4P_PRUNE_THRESHOLD)
            residual = scatter_residual(kept_idx, kept_val)
        return (hi_tab[:, None] + lo_tab[None, :]).reshape(16) + residual


def quantize_weight(w: jax.Array, quant: str = "lut4") -> QuantizedWeight:
    """Freeze a (…, K, N) float weight to a :class:`QuantizedWeight`.

    ``lut4`` calibrates per output channel over the K axis (the paper's
    operands are unsigned codes; see the module docstring identity).  The
    NF4 modes encode against the non-affine NF4 codebook with
    per-output-channel absmax scaling (the codebook is symmetric on
    [-1, 1], so ``zero_point`` is 0).  The table is :func:`decode_table`.

    Leaves with extra leading axes (scan-stacked layers) are quantized
    one slice at a time and stacked, so every array child carries the same
    leading axes and the container remains ``jax.lax.scan``-sliceable.
    """
    return _freeze(w, quant, decode_table(quant))


def _freeze(w: jax.Array, quant: str, table: jax.Array) -> QuantizedWeight:
    if w.ndim > 2:
        # sequential on purpose: the NF4 encode's (K, N, 16) f32 distance
        # temporary exists for one slice only (a vmap over 38 full-width
        # Mamba2 w_in slices would need 42 GB), and each slice is bitwise
        # the 2-D encode (a lax.map under jit moves scales by an ulp).
        # Each slice is waited for: dispatch is asynchronous, and slices
        # queued ahead of the device would each hold their temporary.
        slices = [jax.block_until_ready(_freeze(w[i], quant, table))
                  for i in range(w.shape[0])]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
    wf = w.astype(jnp.float32)
    if quant == "lut4":
        qp = calibrate(wf, bits=4, axis=-1)
        return QuantizedWeight(quantize(wf, qp).astype(jnp.int8), qp.scale,
                               qp.zero_point, table)
    from repro.core.lut import NF4_CODEBOOK
    cb = jnp.asarray(NF4_CODEBOOK)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=0), 1e-8)
    wn = wf / scale[None, :]
    codes = jnp.argmin(jnp.abs(wn[..., None] - cb), axis=-1)
    return QuantizedWeight(codes.astype(jnp.int8), scale.astype(jnp.float32),
                           jnp.zeros_like(scale, jnp.float32), table)


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


def quantize_decode_params(params, quant: str):
    """Walk a model param tree, freezing every decode projection to 4-bit.

    ``quant``: one of :data:`DECODE_QUANT_MODES` (``"lut4"`` affine,
    ``"nf4"``/``"nf4p"`` NF4 with the full or pruned residual,
    ``"nf4_direct"`` the test oracle); :func:`decode_table` says what
    each freezes to.  A leaf is quantized iff its dict key is in
    ``DECODE_QUANT_TARGETS``, some ancestor key is in the quant-parent
    set, and it is a float matrix — everything else (norms, embeddings,
    routers, MLA w_uk/w_uv) passes through untouched.
    """
    table = decode_table(quant)

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
            return _freeze(node, quant, table)
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
