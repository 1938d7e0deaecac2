"""LUT-quantized decode hot path (EngineConfig(quant=...)).

The load-bearing pins:
  * quant=None is token-identical to the pre-quant engine (the decode tree
    IS the prefill tree — same object);
  * the D&C Pallas kernel, its jnp ref, and the engine's jnp decode path
    agree bit-for-bit on the same frozen weights;
  * quant="lut4" and quant="int4" emit identical tokens (two evaluation
    strategies of one affine grid — the paper's D&C argument);
  * quant="nf4" (non-affine: least-squares D&C + per-code residual
    correction) emits tokens identical to the direct full-table NF4
    dequant oracle; its Pallas kernel, the path it takes on the TPU,
    agrees with the jnp ref and the jnp decode path to f32 rounding;
  * quant="nf4p" (pruned residual sub-table) saves table bytes and stays
    above the documented token-agreement threshold vs unpruned nf4;
  * dc_decompose_codebook is least-squares-optimal (property test);
  * quantized greedy decode stays within the documented accuracy bound on
    the fig13 harness, and agrees with bf16 decode above threshold;
  * quant composes with paged=True + prefix_cache (warm == cold tokens).
"""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lut import (NF4_CODEBOOK, dc_decompose_codebook,
                            prune_residual, residual_table_bytes,
                            scatter_residual)
from repro.core.quant import (NF4P_PRUNE_THRESHOLD, QuantizedWeight,
                              quantize_decode_params, quantize_weight)
from repro.kernels.lut_gemm import ops as lut_ops
from repro.kernels.lut_gemm.lut_gemm import lut_gemm_dc_res
from repro.kernels.lut_gemm.ops import lut4_matmul_kernel, quantized_matmul
from repro.kernels.lut_gemm.ref import lut_gemm_dc_ref, lut_gemm_dc_res_ref
from repro.models.registry import get_config, get_model
from repro.serve.config import EngineConfig
from repro.serve.engine import Engine, Request

MIXED_LENS = (3, 9, 5)


def _setup(arch="yi-9b", **over):
    cfg = get_config(arch).reduced(dtype="float32", attn_impl="full", **over)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, params


def _prompts(cfg, lens=MIXED_LENS, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


def _serve(cfg, params, prompts, max_new=8, **conf):
    eng = Engine(cfg, params,
                 EngineConfig(max_batch=len(prompts), max_seq=48, **conf))
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    assert eng.serve(reqs)["done"]
    return [r.out for r in reqs], eng


# ---------------------------------------------------------------------------
# quant=None token identity
# ---------------------------------------------------------------------------

def test_quant_none_is_token_identical_and_aliases_params():
    """Acceptance pin: the default engine and an explicit quant=None engine
    emit the same tokens, and the decode tree IS the param tree (no copy,
    no transform — the strongest possible identity guarantee)."""
    cfg, params = _setup()
    prompts = _prompts(cfg)
    base, eng_default = _serve(cfg, params, prompts)
    none, eng_none = _serve(cfg, params, prompts, quant=None)
    assert base == none
    assert eng_default.decode_params is eng_default.params
    assert eng_none.decode_params is eng_none.params


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

def test_lut_gemm_dc_pallas_matches_ref_and_jnp_path():
    """The D&C Pallas kernel (interpret), the jnp oracle, and the engine's
    decode-path matmul agree on identical frozen weights."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    qw = quantize_weight(w, "lut_dc")
    ref = lut_gemm_dc_ref(x, qw.codes, qw.hi_tab, qw.lo_tab,
                          qw.zero_point, qw.scale)
    pallas = lut4_matmul_kernel(x, w, interpret=True)
    jnp_path = quantized_matmul(x, qw)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(jnp_path), np.asarray(ref))


def test_dc_decomposition_exact_for_affine_free_for_nf4():
    """Paper Figs 2/3: an affine 16-entry LUT splits EXACTLY into two
    4-entry sub-tables; the non-linear NF4 table pays a nonzero residual —
    the capacity cost of the 6-vs-15-select area saving."""
    uniform = jnp.arange(16, dtype=jnp.float32) * 0.37 - 2.1
    hi, lo, res = dc_decompose_codebook(uniform)
    assert float(jnp.max(jnp.abs(res))) < 1e-5
    rebuilt = hi[:, None] + lo[None, :]
    np.testing.assert_allclose(np.asarray(rebuilt.reshape(-1)),
                               np.asarray(uniform), rtol=1e-5, atol=1e-5)
    _, _, res_nf4 = dc_decompose_codebook(jnp.asarray(NF4_CODEBOOK))
    assert float(jnp.max(jnp.abs(res_nf4))) > 0.05


def test_nf4_dc_res_pallas_bitwise_equals_ref():
    """On the SAME frozen tables (quantize once, eagerly — the engine's
    freeze-at-construction discipline) the residual-corrected D&C Pallas
    kernel and its jnp ref agree to f32 rounding at every tiling.  They
    build the same weights (one folded 16-entry table gives the values of
    the 6-select sum plus the residual gather bit for bit) and apply the
    zero-point and scale in the same places; only the dot's summation
    order differs: a (K, bn) block's dot sums in another order than the
    whole (K, N) dot on XLA's CPU backend (up to 5.7e-6 apart at bn=8)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    qw = quantize_weight(w, "nf4_dc")
    ref = lut_gemm_dc_res_ref(x, qw.codes, qw.hi_tab, qw.lo_tab,
                              qw.residual, qw.zero_point, qw.scale)
    for bn, bk in ((8, 64), (16, 32), (48, 64), (32, 16)):
        pallas = lut_gemm_dc_res(x, qw.codes, qw.hi_tab, qw.lo_tab,
                                 qw.residual, qw.zero_point, qw.scale,
                                 bm=8, bn=bn, bk=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(pallas), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"bn={bn} bk={bk}")
    # and the engine's jnp decode path lands within float-rounding of both
    jnp_path = quantized_matmul(x, qw)
    np.testing.assert_allclose(np.asarray(jnp_path), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _scan_quantized(x, qs, matmul):
    """Each layer of a scan-stacked leaf applied to ``x`` under lax.scan."""
    return jax.lax.scan(lambda c, qwi: (c, matmul(x, qwi)), 0, qs)[1]


@pytest.mark.parametrize("x_shape,k,n,prune,stacked", [
    ((12, 256), 256, 200, None, False),       # M = 12, ragged N edge block
    ((1, 256), 256, 200, None, False),        # M = 1
    ((12, 1, 256), 256, 200, None, False),    # (B, 1, K) decode input
    ((3, 2, 256), 256, 96, None, False),      # (B, W, K) verify window
    ((12, 256), 256, 200, NF4P_PRUNE_THRESHOLD, False),   # nf4p residual
    ((12, 256), 256, 200, None, True),        # sliced under lax.scan
], ids=["m12", "m1", "b1k", "bwk", "nf4p", "scan"])
def test_nf4_dc_tpu_route_matches_jnp_path(monkeypatch, x_shape, k, n,
                                            prune, stacked):
    """The TPU route of ``quantized_matmul`` (``nf4_dc_matmul``, here in
    interpret mode) against its jnp path, the reference on every other
    platform: equal to f32 rounding.  Code blocks are capped at 128
    columns so that N = 200 ends in a masked, ragged block."""
    monkeypatch.setattr(lut_ops, "DC_RES_BLOCK_BYTES", k * 128)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=x_shape), jnp.float32)
    w_shape = (3, k, n) if stacked else (k, n)
    w = jnp.asarray(rng.normal(size=w_shape) * 0.05, jnp.float32)
    qw = quantize_weight(w, "nf4_dc", prune)
    kernel = functools.partial(lut_ops.nf4_dc_matmul, interpret=True)
    if stacked:
        got = _scan_quantized(x, qw, kernel)
        want = _scan_quantized(x, qw, quantized_matmul)
    else:
        got, want = kernel(x, qw), quantized_matmul(x, qw)
    assert got.shape == want.shape == (*w_shape[:-2], *x_shape[:-1], n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m", [5, 32])
def test_nf4_dc_expert_route_matches_jnp_path(m):
    """The TPU route of ``quantized_expert_matmul`` (one
    ``lut_gemm_dc_res_experts`` call over the stack, in interpret mode)
    against each expert's jnp path: equal to f32 rounding.  A K of 384
    takes ``bk`` = 128; N = 200 ends in a ragged block."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(3, m, 384)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 384, 200)) * 0.05, jnp.float32)
    qw = quantize_weight(w, "nf4_dc")
    got = lut_ops.nf4_dc_expert_matmul(x, qw, interpret=True)
    want = lut_ops.quantized_expert_matmul(x, qw)
    per_expert = jnp.stack([
        quantized_matmul(x[i], jax.tree.map(lambda a: a[i], qw))
        for i in range(3)])
    assert got.shape == (3, m, 200)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(per_expert))


def test_nf4_dc_matches_direct_dequant_weights():
    """Residual-corrected D&C reconstructs the NF4 codebook exactly up to
    float rounding: the nf4_dc and nf4_dequant kernels produce the same
    effective weights (and the pruned variant's error is bounded)."""
    rng = np.random.default_rng(6)
    w = jnp.asarray(rng.normal(size=(96, 32)), jnp.float32)
    eye = jnp.eye(96, dtype=jnp.float32)
    w_dc = quantized_matmul(eye, quantize_weight(w, "nf4_dc"))
    w_direct = quantized_matmul(eye, quantize_weight(w, "nf4_dequant"))
    np.testing.assert_allclose(np.asarray(w_dc), np.asarray(w_direct),
                               rtol=1e-5, atol=1e-5)
    w_p = quantized_matmul(
        eye, quantize_weight(w, "nf4_dc", NF4P_PRUNE_THRESHOLD))
    mae = float(jnp.abs(w_p - w_dc).mean())
    assert 0 < mae < 0.05, mae   # pruning costs something, but bounded


def test_quantized_weight_slices_under_scan():
    """Scan-stacked containers: every array child carries the leading L
    axis and lax.scan slices them per layer like float leaves."""
    rng = np.random.default_rng(4)
    ws = jnp.asarray(rng.normal(size=(3, 32, 16)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
    qs = quantize_weight(ws, "lut_dc")
    assert qs.codes.shape == (3, 32, 16) and qs.scale.shape == (3, 16)
    assert qs.hi_tab.shape == (3, 4)

    def body(c, qwi):
        return c, quantized_matmul(x, qwi)

    _, ys = jax.lax.scan(body, 0, qs)
    per_layer = jnp.stack([
        quantized_matmul(x, jax.tree.map(lambda a: a[i], qs))
        for i in range(3)])
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(per_layer))


@pytest.mark.parametrize("kernel", ["lut_dc", "nf4_dc"])
def test_stacked_encode_equals_per_slice_encode(kernel):
    """A stacked (L, K, N) leaf encodes slice by slice: every child is
    bitwise the stack of the 2-D encode of each slice, and of the
    whole-stack vmap encode it replaced."""
    rng = np.random.default_rng(5)
    ws = jnp.asarray(rng.normal(size=(4, 48, 40)), jnp.float32)
    qs = quantize_weight(ws, kernel)
    per_slice = [quantize_weight(ws[i], kernel) for i in range(4)]
    vmapped = jax.vmap(lambda wi: quantize_weight(wi, kernel))(ws)
    assert qs.kernel == kernel
    for name in ("codes", "scale", "zero_point", "hi_tab", "lo_tab",
                 "residual"):
        got = getattr(qs, name)
        if got is None:
            assert kernel == "lut_dc" and per_slice[0].residual is None
            continue
        want = np.stack([np.asarray(getattr(q, name)) for q in per_slice])
        assert got.dtype == want.dtype, name
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=name)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(getattr(vmapped, name)),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# engine behavior under quant
# ---------------------------------------------------------------------------

def test_lut4_and_int4_tokens_identical():
    """Two evaluation strategies of the same affine grid: the D&C
    sub-table LUT and direct dequant must emit identical tokens."""
    cfg, params = _setup()
    prompts = _prompts(cfg)
    lut, _ = _serve(cfg, params, prompts, quant="lut4")
    i4, _ = _serve(cfg, params, prompts, quant="int4")
    assert lut == i4


def test_quantized_greedy_agreement_above_threshold():
    """Accuracy bound on served tokens: prefill is full precision so every
    request's FIRST token matches bf16 exactly; overall greedy agreement
    stays above threshold (random-init reduced model — trained weights
    agree far more, see docs/quantization.md and the fig13 bound)."""
    cfg, params = _setup()
    prompts = _prompts(cfg)
    base, _ = _serve(cfg, params, prompts)
    lut, _ = _serve(cfg, params, prompts, quant="lut4")
    for b, q in zip(base, lut):
        assert b[0] == q[0]                       # prefill token: exact
    agree = sum(a == b for o1, o2 in zip(base, lut)
                for a, b in zip(o1, o2))
    total = sum(len(o) for o in base)
    assert agree / total >= 0.5, (agree, total)


@pytest.mark.parametrize("arch", ["yi-9b", "deepseek-v2-lite-16b"])
def test_nf4_tokens_identical_to_direct_dequant_oracle(arch):
    """Acceptance pin: an nf4 engine (6-select D&C + residual correction)
    emits exactly the tokens of an engine whose decode tree is the direct
    full-table NF4 dequant oracle (15 selects) — the D&C split plus
    residual loses nothing, on dense projections and (deepseek) on the
    frozen routed experts too.  Prefill stays full precision, so the
    first token also matches bf16 exactly."""
    cfg, params = _setup(arch)
    prompts = _prompts(cfg)
    base, _ = _serve(cfg, params, prompts)
    nf4, _ = _serve(cfg, params, prompts, quant="nf4")
    eng = Engine(cfg, params, EngineConfig(max_batch=len(prompts),
                                           max_seq=48, quant="nf4"))
    eng.decode_params = quantize_decode_params(params, "nf4_direct")
    reqs = [Request(rid=i, prompt=p, max_new=8)
            for i, p in enumerate(prompts)]
    assert eng.serve(reqs)["done"]
    assert nf4 == [r.out for r in reqs]
    assert [o[0] for o in nf4] == [o[0] for o in base]


def test_nf4p_pruned_decode_saves_bytes_within_agreement():
    """The pruned-residual engine: table bytes strictly saved, and served
    tokens stay above the agreement threshold vs unpruned nf4 (random-init
    reduced model — the bound is deliberately loose)."""
    cfg, params = _setup()
    prompts = _prompts(cfg)
    nf4, _ = _serve(cfg, params, prompts, quant="nf4")
    nf4p, eng = _serve(cfg, params, prompts, quant="nf4p")
    assert [o[0] for o in nf4] == [o[0] for o in nf4p]   # prefill exact
    agree = sum(a == b for o1, o2 in zip(nf4, nf4p)
                for a, b in zip(o1, o2))
    total = sum(len(o) for o in nf4)
    assert agree / total >= 0.4, (agree, total)
    # the pruned residual really is sparse, and sparse storage is smaller
    _, _, res = dc_decompose_codebook(jnp.asarray(NF4_CODEBOOK))
    kept_idx, kept_val = prune_residual(res, NF4P_PRUNE_THRESHOLD)
    assert 0 < int(kept_idx.shape[0]) < 16
    dense, pruned = residual_table_bytes(int(kept_idx.shape[0]))
    assert pruned < dense
    # scatter rebuilds the pruned table the engine actually decodes with
    leaf = jax.tree.leaves(
        eng.decode_params,
        is_leaf=lambda x: isinstance(x, QuantizedWeight))
    qws = [x for x in leaf if isinstance(x, QuantizedWeight)]
    assert qws and all(q.kernel == "nf4_dc" for q in qws)
    want = scatter_residual(kept_idx, kept_val)
    got = qws[0].residual
    np.testing.assert_allclose(
        np.asarray(got).reshape(-1, 16)[0], np.asarray(want),
        rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# dc_decompose_codebook optimality (property tests)
#
# With ``hypothesis`` installed (the ``dev`` extra) these are real
# property tests; without it (this image cannot pip install) the same
# properties run over a deterministic seeded sweep — the checks are
# identical, only the example generator differs.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                       # pragma: no cover
    HAVE_HYPOTHESIS = False


def _check_affine_exact(a: float, b: float) -> None:
    """EVERY affine codebook c[q] = a*q + b splits exactly into HI/LO
    sub-tables (zero residual) — the paper's D&C applies to the whole
    affine family, not just the uniform int4 grid."""
    cb = a * jnp.arange(16, dtype=jnp.float32) + b
    hi, lo, res = dc_decompose_codebook(cb)
    scale = max(1.0, abs(a) * 16 + abs(b))
    assert float(jnp.max(jnp.abs(res))) <= 1e-5 * scale
    rebuilt = (hi[:, None] + lo[None, :]).reshape(-1)
    np.testing.assert_allclose(np.asarray(rebuilt), np.asarray(cb),
                               rtol=1e-5, atol=1e-5 * scale)


def _check_ls_optimal(cb_vals, dh: int, dl: int, eps: float) -> None:
    """No perturbation of a single HI or LO entry reduces the residual
    norm — dc_decompose_codebook's split is the least-squares optimum over
    all additive (row value + column value) decompositions."""
    cb = jnp.asarray(cb_vals, jnp.float32)
    hi, lo, res = dc_decompose_codebook(cb)
    base = float(jnp.sum(res ** 2))
    hi_p = hi.at[dh].add(eps)
    lo_p = lo.at[dl].add(eps)
    for h, l in ((hi_p, lo), (hi, lo_p)):
        res_p = cb - (h[:, None] + l[None, :]).reshape(-1)
        assert float(jnp.sum(res_p ** 2)) >= base - 1e-5


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(a=st.floats(-4, 4, allow_nan=False, allow_infinity=False),
           b=st.floats(-4, 4, allow_nan=False, allow_infinity=False))
    def test_dc_decomposition_exact_on_any_affine_grid(a, b):
        _check_affine_exact(a, b)

    @settings(max_examples=25, deadline=None)
    @given(cb_vals=st.lists(st.floats(-2, 2, allow_nan=False,
                                      allow_infinity=False, width=32),
                            min_size=16, max_size=16),
           dh=st.integers(0, 3), dl=st.integers(0, 3),
           eps=st.floats(-0.3, 0.3, allow_nan=False))
    def test_dc_decomposition_is_least_squares_optimal(cb_vals, dh, dl,
                                                       eps):
        _check_ls_optimal(cb_vals, dh, dl, eps)
else:
    def test_dc_decomposition_exact_on_any_affine_grid():
        rng = np.random.default_rng(11)
        _check_affine_exact(0.0, 0.0)
        _check_affine_exact(0.37, -2.1)
        for _ in range(25):
            a, b = rng.uniform(-4, 4, size=2)
            _check_affine_exact(float(a), float(b))

    def test_dc_decomposition_is_least_squares_optimal():
        rng = np.random.default_rng(12)
        _check_ls_optimal(np.asarray(NF4_CODEBOOK, np.float32), 0, 0, 0.1)
        for _ in range(25):
            cb = rng.uniform(-2, 2, size=16).astype(np.float32)
            dh, dl = rng.integers(0, 4, size=2)
            eps = float(rng.uniform(-0.3, 0.3))
            _check_ls_optimal(cb, int(dh), int(dl), eps)


def test_fig13_ptq_within_documented_bound():
    """The documented accuracy bound: the bf16-trained fig13 harness MLP,
    frozen to 4-bit QuantizedWeight leaves, stays within PTQ_MAE_BOUND of
    its own MAE — and both evaluation kernels land the same number."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "fig13_nn_accuracy.py")
    spec = importlib.util.spec_from_file_location("fig13", path)
    fig13 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fig13)
    mae_ideal, trained = fig13.train_one("ideal")
    mae_lut = fig13.ptq_mae(trained, "lut_dc")
    mae_int = fig13.ptq_mae(trained, "dequant")
    assert mae_lut <= mae_ideal * fig13.PTQ_MAE_BOUND, (mae_lut, mae_ideal)
    assert mae_lut == mae_int


def test_quant_composes_with_paged_and_prefix_cache():
    """Warm == cold under quant: a lut4 engine with paged blocks + prefix
    cache emits the same tokens for a shared-head prompt admitted cold
    (populating the tree) and warm (seeded from COW blocks)."""
    cfg, params = _setup()
    rng = np.random.default_rng(7)
    head = rng.integers(1, cfg.vocab_size, 16).tolist()
    tail_a = rng.integers(1, cfg.vocab_size, 4).tolist()
    tail_b = rng.integers(1, cfg.vocab_size, 4).tolist()

    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq=64, quant="lut4", paged=True, block_size=8,
        prefix_cache=True))
    cold = Request(rid=0, prompt=head + tail_a, max_new=6)
    assert eng.serve([cold])["done"]
    warm = Request(rid=1, prompt=head + tail_b, max_new=6)
    stats = eng.serve([warm])
    assert stats["done"] and stats["prefix_hits"] == 1

    # reference: same requests on a quant engine WITHOUT the prefix cache
    ref, _ = _serve(cfg, params, [head + tail_a, head + tail_b],
                    max_new=6, quant="lut4", paged=True, block_size=8)
    assert [cold.out, warm.out] == ref


def test_quantized_decode_all_served_families():
    """Every servable family decodes under lut4, with the exclusion rules
    honored: MLA's direct-use w_uk/w_uv and the router stay float, while
    the MoE routed experts are frozen, each expert's matrix with its own
    per-output-column scale."""
    for arch in ("deepseek-v2-lite-16b", "mamba2-1.3b", "zamba2-1.2b"):
        cfg, params = _setup(arch)
        qp = quantize_decode_params(params, "lut4")
        prompts = _prompts(cfg, lens=(4, 6))
        base, _ = _serve(cfg, params, prompts, max_new=4)
        lut, _ = _serve(cfg, params, prompts, max_new=4, quant="lut4")
        assert all(len(o) == 4 for o in lut), (arch, lut)
        assert [o[0] for o in base] == [o[0] for o in lut], arch
        if cfg.family == "moe":
            moe = qp["blocks"]["moe"]
            assert isinstance(moe["w_up"], QuantizedWeight)
            layers, e, _, f = params["blocks"]["moe"]["w_up"].shape
            assert moe["w_up"].scale.shape == (layers, e, f)
            assert not isinstance(moe["router"], QuantizedWeight)
            assert isinstance(moe["shared"]["w_up"], QuantizedWeight)


def test_mla_direct_use_leaves_stay_float():
    """deepseek MLA consumes w_uk/w_uv via reshape+einsum — the tree
    quantizer must never touch them."""
    cfg, params = _setup("deepseek-v2-lite-16b")
    qp = quantize_decode_params(params, "lut4")
    attn = qp["blocks"]["attn"]
    assert not isinstance(attn["w_uk"], QuantizedWeight)
    assert not isinstance(attn["w_uv"], QuantizedWeight)
    assert isinstance(attn["w_dkv"], QuantizedWeight)


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_engine_config_quant_validation():
    with pytest.raises(ValueError, match="quant"):
        EngineConfig(quant="fp3")
    for mode in ("lut4", "int4", "nf4", "nf4p"):
        assert EngineConfig(quant=mode).quant == mode
    # "nf4_direct" is the test/fig13 oracle spelling, not an engine mode
    with pytest.raises(ValueError, match="quant"):
        EngineConfig(quant="nf4_direct")
    assert EngineConfig().quant is None


def test_engine_rejects_double_quantization():
    """Engine-level frozen 4-bit + model-level dynamic quant would
    quantize twice; the constructor refuses the combination."""
    from repro.core.layers import QuantConfig
    cfg, params = _setup(quant=QuantConfig(mode="luna_approx"))
    with pytest.raises(ValueError, match="twice"):
        Engine(cfg, params, EngineConfig(max_batch=1, max_seq=32,
                                         quant="lut4"))


def test_from_args_routes_shared_quant_flag():
    """The shared --quant flag: engine modes land on EngineConfig.quant,
    model-level spellings leave it None (the caller routes them into a
    QuantConfig)."""
    import argparse
    ap = argparse.ArgumentParser()
    EngineConfig.add_cli_args(ap)
    args = ap.parse_args(["--quant", "lut4"])
    assert EngineConfig.from_args(args).quant == "lut4"
    args = ap.parse_args(["--quant", "nf4"])
    assert EngineConfig.from_args(args).quant == "nf4"
    args = ap.parse_args(["--quant", "nf4p"])
    assert EngineConfig.from_args(args).quant == "nf4p"
    args = ap.parse_args(["--quant", "luna_approx"])
    assert EngineConfig.from_args(args).quant is None
    args = ap.parse_args([])
    assert EngineConfig.from_args(args).quant is None
