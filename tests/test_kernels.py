"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -e .[dev])")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core.quant import quantize_weight
from repro.kernels.flash_attention.ops import mha
from repro.kernels.luna_mm.ops import luna_matmul_f32_kernel, luna_mm_codes
from repro.kernels.luna_mm.ref import luna_mm_ref
from repro.kernels.lut_gemm.lut_gemm import lut_gemm_dc_res
from repro.kernels.lut_gemm.ops import lut_matmul
from repro.kernels.lut_gemm.ref import lut_gemm_dc_res_ref

MODES = ["conventional", "dc", "opt_dc", "approx_dc", "approx_dc2"]


# ---------------------------------------------------------------------------
# luna_mm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("shape", [(8, 8, 8), (64, 96, 40), (128, 256, 128),
                                   (33, 17, 9), (1, 300, 5)])
def test_luna_mm_shapes(mode, shape):
    m, k, n = shape
    rng = np.random.default_rng(hash((mode, shape)) % 2**32)
    y = jnp.asarray(rng.integers(0, 16, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(0, 16, (k, n)), jnp.int8)
    got = luna_mm_codes(y, w, mode=mode, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(luna_mm_ref(y, w, mode)))


@given(m=st.integers(1, 40), k=st.integers(1, 80), n=st.integers(1, 24),
       mode=st.sampled_from(MODES), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_luna_mm_property(m, k, n, mode, seed):
    rng = np.random.default_rng(seed)
    y = jnp.asarray(rng.integers(0, 16, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(0, 16, (k, n)), jnp.int8)
    got = luna_mm_codes(y, w, mode=mode, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(luna_mm_ref(y, w, mode)))


@pytest.mark.parametrize("mode", ["opt_dc", "approx_dc", "approx_dc2"])
def test_luna_mm_f32_wrapper_matches_library(mode):
    """Kernel float path == core library float path (same quant algebra)."""
    from repro.core.quant import luna_matmul_f32
    from repro.core.luna import LunaMode
    lm = {"opt_dc": LunaMode.OPT_DC, "approx_dc": LunaMode.APPROX_DC,
          "approx_dc2": LunaMode.APPROX_DC2}[mode]
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(24, 48)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(48, 16)), jnp.float32)
    got = luna_matmul_f32_kernel(x, w, mode=mode, interpret=True)
    ref = luna_matmul_f32(x, w, lm, bits=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_luna_mm_approx_halves_matmul_work():
    """ApproxD&C's TPU payoff: the kernel does 1 digit-plane matmul not 2.

    Verified structurally: approx == exact with the low plane zeroed."""
    rng = np.random.default_rng(11)
    y = jnp.asarray(rng.integers(0, 16, (32, 64)), jnp.int8)
    w = jnp.asarray(rng.integers(0, 16, (64, 16)), jnp.int8)
    approx = luna_mm_codes(y, w, mode="approx_dc", interpret=True)
    y_hi_only = jnp.asarray((np.asarray(y) >> 2) << 2, jnp.int8)
    exact_hi = luna_mm_codes(y_hi_only, w, mode="opt_dc", interpret=True)
    np.testing.assert_array_equal(np.asarray(approx), np.asarray(exact_hi))


# ---------------------------------------------------------------------------
# lut_gemm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(16, 32, 16), (48, 96, 33), (8, 8, 8),
                                   (128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lut_gemm_shapes_dtypes(shape, dtype):
    """An NF4 weight through the LUT kernel at the tiles ``lut_matmul``
    picks (ragged M and N, K below one block) against the reference; an
    ``x`` given in its own dtype is read as the same f32 values and the
    f32 result rounded once to that dtype."""
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % 2**32)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    qw = quantize_weight(w, "nf4")
    got = lut_matmul(x.astype(jnp.float32), qw, interpret=True)
    ref = lut_gemm_dc_res_ref(x.astype(jnp.float32), qw.codes, qw.table,
                              qw.zero_point, qw.scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    got_x = lut_matmul(x, qw, interpret=True)
    assert got_x.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got_x),
                                  np.asarray(got.astype(dtype)))


def test_lut_gemm_arbitrary_codebook():
    """Programmability: any 16-entry table, not just NF4/uniform, is the
    kernel's own input (with any per-channel zero point)."""
    rng = np.random.default_rng(3)
    cb = jnp.asarray(np.sort(rng.normal(size=16)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, 16, (64, 32)), jnp.int8)
    zp = jnp.asarray(rng.normal(size=32), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 2.0, 32), jnp.float32)
    x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    got = lut_gemm_dc_res(x, codes, cb, zp, scale, bm=16, bn=32, bk=64,
                          interpret=True)
    ref = lut_gemm_dc_res_ref(x, codes, cb, zp, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,s,h,hkv,d", [
    (1, 128, 2, 2, 16), (2, 256, 4, 2, 32), (1, 512, 8, 1, 64),
])
def test_flash_vs_ref(b, s, h, hkv, d, causal):
    rng = np.random.default_rng(hash((b, s, h, hkv, d, causal)) % 2**32)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    sm = 1.0 / np.sqrt(d)
    got = mha(q, k, v, sm_scale=sm, causal=causal, use_flash=True,
              interpret=True)
    ref = mha(q, k, v, sm_scale=sm, causal=causal, use_flash=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_flash_bf16():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 32)), jnp.bfloat16)
    got = mha(q, k, v, sm_scale=0.17, use_flash=True, interpret=True)
    ref = mha(q.astype(jnp.float32), k.astype(jnp.float32),
              v.astype(jnp.float32), sm_scale=0.17, use_flash=False)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)
