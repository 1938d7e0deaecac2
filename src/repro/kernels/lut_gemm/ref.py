"""Pure-jnp oracle for the codebook LUT GEMM.

:func:`lut_gemm_dc_res_ref` follows the Pallas kernel's order: the
weight is ``table[q] - zp`` (the zero point subtracted before the
matmul), the scale is applied after it.  Kernel and reference build the
same weights bit for bit and differ only in the dot's summation order, so
they agree to f32 rounding.  (``ops.quantized_matmul``'s ``jnp`` path
folds the scale into the weight before the matmul instead.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lut_gemm_dc_res_ref(x: jax.Array, w_codes: jax.Array, table: jax.Array,
                        zero_point: jax.Array, scale: jax.Array
                        ) -> jax.Array:
    """``x @ (table[q] - zp)`` scaled in the epilogue.

    ``w_codes``: (K, N) int8 codes in [0, 16); ``table``: (16,) f32
    code-space values; ``zero_point``/``scale``: (N,) per-channel.
    Returns (M, N) f32.
    """
    w = table[w_codes.astype(jnp.int32)] - zero_point[None, :]
    acc = jax.lax.dot_general(x.astype(jnp.float32), w,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return acc * scale[None, :]
