"""NF4 weight quantization, written from its published definition.

The 16 NormalFloat levels are those of QLoRA (Dettmers et al. 2023,
arXiv:2305.14314, Appendix E).  A weight matrix ``(K, N)`` is scaled per
output column by its largest magnitude, each entry takes the nearest
level, and the dequantized weight is ``level * scale``.
"""
from __future__ import annotations

import jax.numpy as jnp

NF4_LEVELS = (
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0,
)


def nf4_dequant(w):
    """``w`` (K, N) float32 -> its NF4 round trip, float32."""
    levels = jnp.asarray(NF4_LEVELS, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8)
    wn = w / scale[None, :]
    mids = (levels[1:] + levels[:-1]) / 2
    code = jnp.sum(wn[..., None] > mids, axis=-1)
    return levels[code] * scale[None, :]
