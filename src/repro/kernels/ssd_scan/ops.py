"""Model-facing wrapper: (B, S, H, P) tensors -> flattened head-streams."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ssd_scan import ssd_scan


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked_kernel(x, dt, a, b, c, *, chunk: int = 128,
                       interpret: bool = False, initial_state=None,
                       mask=None):
    """x: (B,S,H,P); dt: (B,S,H); a: (H,); b/c: (B,S,G,N) with G|H.

    ``initial_state``: optional (B,H,P,N) carried state to continue from;
    ``mask``: optional (B,S) validity mask (pad columns are inert).
    Returns (y (B,S,H,P) f32, final_state (B,H,P,N)) matching
    ``repro.models.ssm._ssd_chunked``.
    """
    bb, s, h, p = x.shape
    g = b.shape[2]
    hg = h // g
    n = b.shape[-1]
    xf = x.transpose(0, 2, 1, 3).reshape(bb * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(bb * h, s)
    bh_b = jnp.repeat(b, hg, axis=2).transpose(0, 2, 1, 3).reshape(
        bb * h, s, n)
    ch_c = jnp.repeat(c, hg, axis=2).transpose(0, 2, 1, 3).reshape(
        bb * h, s, n)
    af = jnp.tile(a, bb)
    s0 = None
    if initial_state is not None:                        # (B,H,P,N)->(BH,N,P)
        s0 = initial_state.transpose(0, 1, 3, 2).reshape(bb * h, n, p)
    mf = None
    if mask is not None:                                 # (B,S)->(BH,S)
        mf = jnp.broadcast_to(mask[:, None, :], (bb, h, s)).reshape(
            bb * h, s)
    y, fs = ssd_scan(xf, dtf, af, bh_b, ch_c, chunk=chunk,
                     interpret=interpret, initial_state=s0, mask=mf)
    y = y.reshape(bb, h, s, p).transpose(0, 2, 1, 3)
    fs = fs.reshape(bb, h, n, p).transpose(0, 1, 3, 2)   # (B,H,P,N)
    return y, fs
