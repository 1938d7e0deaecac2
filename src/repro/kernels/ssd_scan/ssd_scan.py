"""Pallas TPU kernel: Mamba2 SSD chunk scan (single head-stream per grid row).

Grid: ``(B*H, n_chunks)`` with the chunk dim innermost — the (P, N) state
lives in VMEM scratch and persists across the sequential chunk steps (the
same pattern as a matmul accumulator).  Per chunk the kernel does the three
SSD pieces entirely in VMEM:

  intra:   Y  = (C B^T ⊙ L) (x·dt)        two (Q,Q)x(Q,·) MXU matmuls
  inter:   Y += seg_start · (C S_prev)     (Q,N)x(N,P)
  state:   S  = decay·S_prev + (seg_end·B)^T (x·dt)   (N,Q)x(Q,P)

Q defaults to 128 (MXU-aligned); the (Q,Q) decay mask is built with iota.
This turns the per-layer SSD from ~7 jnp einsums with HBM round-trips into
one VMEM-resident kernel — the hot loop of mamba2-1.3b / zamba2-1.2b.

The scan is RESUMABLE: an optional (BH, N, P) ``initial_state`` seeds the
VMEM state at chunk 0 (instead of zeros) and the continued final state is
returned, and an optional (BH, S) validity ``mask`` makes right-padded
positions inert — together these let chunked/bucketed prefill feed a prompt
in pieces with exact state carry (see ``serve.engine``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_EXACT = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, fs_ref,
                state_ref, *, nc: int, q: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (Q, 1)
    bmat = b_ref[0].astype(jnp.float32)       # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)       # (Q, N)
    a = a_ref[pl.program_id(0)]               # scalar A (negative)

    # inclusive prefix sum of dt*A as a lower-triangular matmul (the TPU
    # kernel lowering has no cumsum), once as a column and once as a row
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = rows >= cols
    tri = causal.astype(jnp.float32)
    dta = dt * a                                        # (Q, 1)
    da_cum = jax.lax.dot_general(
        tri, dta, (((1,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)             # (Q, 1)
    da_row = jax.lax.dot_general(
        dta, tri, (((0,), (1,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)             # (1, Q)
    da_last = da_cum[q - 1:q, :]                        # (1, 1)
    seg_start = jnp.exp(da_cum)                         # (Q, 1)
    seg_end = jnp.exp(da_last - da_cum)                 # (Q, 1)
    # the whole-chunk decay as a (1, P) row (the lowering cannot broadcast
    # a (1, 1) value over both axes of the (N, P) state)
    da_total = jax.lax.dot_general(
        dta, jnp.ones_like(x), (((0,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)             # (1, P)
    chunk_decay = jnp.exp(da_total)
    xdt = x * dt                                        # (Q, P)

    # intra-chunk: L[i,j] = exp(da_cum[i]-da_cum[j]) for i >= j
    L = jnp.exp(jnp.where(causal, da_cum - da_row, -1e30))
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    y = jax.lax.dot_general(cb * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)

    # inter-chunk from carried state (N, P)
    state = state_ref[...]
    y += seg_start * jax.lax.dot_general(
        cmat, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update
    state = chunk_decay * state + jax.lax.dot_general(
        bmat * seg_end, xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (N, P)
    state_ref[...] = state
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        fs_ref[0] = state.astype(fs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 128,
             interpret: bool = False, initial_state=None, mask=None):
    """SSD over flattened head-streams.

    x: (BH, S, P); dt: (BH, S); a: (BH,) negative decay rates;
    b/c: (BH, S, N).  ``initial_state``: optional (BH, N, P) carried state
    to continue from (zeros when None); ``mask``: optional (BH, S) validity
    mask — invalid positions are inert (dt is zeroed: the state freezes
    through them), so right-padded streams carry exactly their real tokens.
    Returns (y (BH, S, P) f32, final_state (BH, N, P)).
    """
    bh, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if mask is not None:
        dt = jnp.where(mask, dt, 0.0)
    if initial_state is None:
        initial_state = jnp.zeros((bh, n, p), jnp.float32)

    y, fs = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc, q=chunk),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ic: (i, ic, 0)),
            # every stream's scalar decay rate, whole in SMEM
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n, p), lambda i, ic: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, n, p), lambda i, ic: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x, dt[..., None], b, c, a,
      initial_state.astype(jnp.float32))
    return y, fs
