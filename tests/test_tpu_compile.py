"""The Pallas kernels of the serving models compile for a TPU v5e chip.

Each test lowers one kernel at zamba2-1.2b's or DeepSeek-V2-Lite's real
widths and compiles it for a *described* ``v5e:2x2`` topology: the TPU
compiler runs here, no chip
is attached and nothing executes.  This catches what interpret mode cannot
— tiling, memory-space and lowering refusals — at no chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.  Keep these tests in this
one file, so that one worker owns the library.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import QuantizedWeight
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.lut_gemm import ops as lut_ops
from repro.kernels.lut_gemm.lut_gemm import (lut_gemm_dc, lut_gemm_dc_res,
                                             lut_gemm_dc_res_experts)
from repro.kernels.paged_attention.paged_attention import (
    paged_decode_attention)
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.ssd_scan import ssd_scan

#: zamba2-1.2b widths: d_model 2048, Mamba2 in-projection 2048 -> 8384,
#: shared MLP 2048 -> 8192; SSD heads of P=64 with state N=64, chunk 256;
#: shared attention 32 heads of d=64
LUT_SHAPES = [(2048, 8384), (2048, 8192)]
DECODE_M = 8
#: zamba2-1.2b's decode projections (K, N): Mamba2 in and out, shared
#: attention, shared MLP up and down; served 12 rows at a time
NF4_DECODE_SHAPES = [(2048, 8384), (4096, 2048), (2048, 2048), (2048, 8192),
                     (8192, 2048)]
NF4_DECODE_ROWS = 12
#: DeepSeek-V2-Lite's frozen decode projections (K, N): MLA wq, w_dkv and
#: wo, the dense layer's 10944-wide FFN, the two shared experts' 2816;
#: its 8 held routed experts of 1408 (gate/up, down); served 32 rows
DSV2_DECODE_SHAPES = [(2048, 3072), (2048, 576), (2048, 2048),
                      (2048, 10944), (10944, 2048), (2048, 2816),
                      (2816, 2048)]
DSV2_EXPERT_SHAPES = [(2048, 1408), (1408, 2048)]
DSV2_HELD, DSV2_ROWS = 8, 32
#: zamba2-1.2b's paged pool in the benchmark: 12 rows of 1536 positions
#: in blocks of 16 (96 a row, 1153 with the garbage block), shared
#: attention 32 heads of 64 over 32 KV heads
PAGED_ROWS, PAGED_NBLK, PAGED_BLOCKS, PAGED_BS = 12, 96, 1153, 16
SSD_HEADS, SSD_P, SSD_N, SSD_CHUNK, SSD_SEQ = 64, 64, 64, 256, 512
ATTN_HEADS, ATTN_D, ATTN_SEQ = 32, 64, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kernel", ["lut_gemm_dc", "lut_gemm_dc_res"])
@pytest.mark.parametrize("k,n", LUT_SHAPES)
def test_lut_gemm_compiles_for_v5e(sds, kernel, k, n):
    """Decode-size LUT GEMMs with the block sizes the wrappers choose."""
    bm, bn, bk = lut_ops.gemm_blocks(DECODE_M, k, n)
    mp = DECODE_M + (-DECODE_M) % bm
    kp, np_ = k + (-k) % bk, n + (-n) % bn
    f32 = jnp.float32
    x = sds((mp, kp), f32)
    codes = sds((kp, np_), jnp.int8)
    tab4, chan = sds((4,), f32), sds((np_,), f32)
    if kernel == "lut_gemm_dc":
        _compile(lambda *a: lut_gemm_dc(*a, bm=bm, bn=bn, bk=bk),
                 x, codes, tab4, tab4, chan, chan)
    else:
        _compile(lambda *a: lut_gemm_dc_res(*a, bm=bm, bn=bn, bk=bk),
                 x, codes, tab4, tab4, sds((16,), f32), chan, chan)


def _nf4_weight(sds, lead, k, n):
    f32 = jnp.float32
    chan, tab4 = sds(lead + (n,), f32), sds(lead + (4,), f32)
    return QuantizedWeight(sds(lead + (k, n), jnp.int8), chan, chan, tab4,
                           tab4, sds(lead + (16,), f32), kernel="nf4_dc")


@pytest.mark.parametrize("rows,k,n", [
    *[(NF4_DECODE_ROWS, k, n) for k, n in NF4_DECODE_SHAPES],
    *[(DSV2_ROWS, k, n) for k, n in DSV2_DECODE_SHAPES]])
def test_nf4_decode_matmul_takes_fused_kernel_on_v5e(sds, rows, k, n):
    """Lowered for the TPU, an ``nf4_dc`` decode matmul runs the Pallas
    LUT kernel and holds no float copy of the weight: its temporaries
    stay below one f32 (K, N) weight (the ``jnp`` path's mux tree needs
    about twenty)."""
    x = sds((rows, 1, k), jnp.bfloat16)
    compiled = _compile(lut_ops.quantized_matmul, x,
                        _nf4_weight(sds, (), k, n))
    assert compiled.memory_analysis().temp_size_in_bytes < k * n * 4


@pytest.mark.parametrize("k,n", DSV2_EXPERT_SHAPES)
def test_nf4_expert_matmul_takes_named_kernel_on_v5e(sds, k, n):
    """A decode step's held routed experts are one Pallas call over the
    stack, named ``lut_gemm_dc_res_experts``, with no float copy of the
    stacked weights."""
    x = sds((DSV2_HELD, DSV2_ROWS, k), jnp.bfloat16)
    compiled = _compile(lut_ops.quantized_expert_matmul, x,
                        _nf4_weight(sds, (DSV2_HELD,), k, n))
    assert "lut_gemm_dc_res_experts" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < k * n * 4


def _stored(topo, shape, dtype):
    """A shape on one described chip in the layout an array is stored in
    (major to minor, tiled): left unset, a compile for a described chip
    chooses its own layouts for the program's inputs and outputs."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding
    fmt = Format(Layout(tuple(range(len(shape)))),
                 SingleDeviceSharding(topo.devices[0]))
    return jax.ShapeDtypeStruct(shape, dtype, sharding=fmt)


@pytest.mark.parametrize("heads,kv_heads,dh", [(32, 32, 64), (32, 8, 128)],
                         ids=["zamba2", "gqa-g4-dh128"])
def test_paged_decode_attention_reads_pool_in_place_on_v5e(topo, heads,
                                                          kv_heads, dh):
    """The kernel, named ``paged_decode_attention``, reads the pools as
    they are stored: no copy of a pool, no gather."""
    pool = _stored(topo, (PAGED_BLOCKS, PAGED_BS, kv_heads, dh),
                   jnp.bfloat16)
    compiled = _compile(paged_decode_attention,
                        _stored(topo, (PAGED_ROWS, heads, dh), jnp.bfloat16),
                        pool, pool,
                        _stored(topo, (PAGED_ROWS, PAGED_NBLK), jnp.int32),
                        _stored(topo, (PAGED_ROWS,), jnp.int32))
    hlo = compiled.as_text()
    assert "paged_decode_attention" in hlo and " gather(" not in hlo
    one_pool = PAGED_BLOCKS * PAGED_BS * kv_heads * dh * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_pool // 8


def test_hybrid_decode_step_attends_in_place_on_v5e(topo):
    """A hybrid decode step at zamba2-1.2b's widths and pool, two layers
    deep with the shared block after each, lowered for the TPU: each
    shared attention is the kernel, reading the pool its write has just
    produced; nothing gathers a row's table, nothing transposes a pool, and
    the only copies of a pool are the write's own copy of its input (the
    caches are not donated, as in the engine).  Its temporaries stay under
    one pool (the gather path's logical-order copies of K alone fill
    one)."""
    import re
    from dataclasses import replace

    from repro.models.common import CacheSpec
    from repro.models.registry import get_config, get_model
    base = get_config("zamba2-1.2b")
    cfg = replace(base, num_layers=2, hybrid=replace(base.hybrid, period=1))
    model = get_model(cfg)
    hkv, dh = cfg.hybrid.shared_num_kv_heads, cfg.resolved_head_dim

    def stored(tree):
        return jax.tree.map(lambda a: _stored(topo, a.shape, a.dtype), tree)

    def step(params, tokens, state, positions, tables):
        return model.decode_step(params, tokens, state, positions,
                                 tables=tables)

    args = stored((
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((PAGED_ROWS, 1), jnp.int32),
        jax.eval_shape(lambda: model.init_cache(
            PAGED_ROWS, PAGED_NBLK * PAGED_BS,
            spec=CacheSpec(PAGED_BS, PAGED_BLOCKS))),
        jax.ShapeDtypeStruct((PAGED_ROWS,), jnp.int32),
        jax.ShapeDtypeStruct((PAGED_ROWS, PAGED_NBLK), jnp.int32)))
    outs = stored(jax.eval_shape(step, *args))
    compiled = jax.jit(step, out_shardings=jax.tree.map(
        lambda a: a.format, outs)).lower(*args).compile()
    hlo = compiled.as_text()
    pool = f"bf16[{PAGED_BLOCKS},{PAGED_BS},{hkv},{dh}]"
    defs = {m[1]: (m[2], m[3], m[4]) for m in re.finditer(
        r"^\s*(?:ROOT )?%(\S+) = (\S+?)\{[^}]*\} ([\w-]+)\(([^)]*)\)",
        hlo, re.M)}
    calls = [k for k, v in defs.items() if k.startswith(
        "paged_decode_attention") and v[1] == "custom-call"]
    assert len(calls) == 2
    for name in calls:
        for arg in re.findall(r"%([\w.-]+)", defs[name][2]):
            if defs.get(arg, ("",))[0] == pool:   # the write's result
                assert defs[arg][1] in ("fusion", "scatter"), defs[arg]
    copies = [v for v in defs.values() if v[0] == pool and v[1] == "copy"]
    assert len(copies) <= 4 and all(
        defs[v[2].lstrip("%")][1] == "parameter" for v in copies)
    assert not [v for v in defs.values()
                if v[0] == pool and v[1] == "transpose"]
    row_table = PAGED_NBLK * PAGED_BS * hkv * dh
    for v in defs.values():
        if v[1] == "gather":
            dims = re.findall(r"\d+", v[0].split("[", 1)[1])
            assert int(np.prod([int(x) for x in dims])) < row_table, v
    one_pool = PAGED_BLOCKS * PAGED_BS * hkv * dh * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_pool


def test_ssd_scan_compiles_for_v5e(sds):
    """The resumable, masked SSD chunk scan at one sequence's heads."""
    bh, f32 = SSD_HEADS, jnp.float32
    _compile(lambda x, dt, a, b, c, s0, m: ssd_scan(
        x, dt, a, b, c, chunk=SSD_CHUNK, initial_state=s0, mask=m),
        sds((bh, SSD_SEQ, SSD_P), f32), sds((bh, SSD_SEQ), f32),
        sds((bh,), f32), sds((bh, SSD_SEQ, SSD_N), f32),
        sds((bh, SSD_SEQ, SSD_N), f32), sds((bh, SSD_N, SSD_P), f32),
        sds((bh, SSD_SEQ), jnp.bool_))


def test_flash_attention_compiles_for_v5e(sds):
    """Causal flash attention with the blocks ``ops.mha`` picks."""
    qkv = sds((ATTN_HEADS, ATTN_SEQ, ATTN_D), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(
        q, k, v, sm_scale=ATTN_D ** -0.5, causal=True,
        num_q_heads=ATTN_HEADS, num_kv_heads=ATTN_HEADS,
        bq=min(256, ATTN_SEQ), bkv=min(512, ATTN_SEQ)), qkv, qkv, qkv)


@pytest.mark.parametrize("fn", [
    lut_ops.nf4_matmul_kernel, lut_ops.lut4_matmul_kernel,
    lut_ops.nf4dc_matmul_kernel, lut_ops.nf4_dc_matmul,
    lut_ops.nf4_dc_expert_matmul, ssd_ops.ssd_chunked_kernel,
    lut_gemm_dc, lut_gemm_dc_res, lut_gemm_dc_res_experts, ssd_scan,
    flash_attention, paged_decode_attention,
], ids=lambda f: f.__name__)
def test_kernels_do_not_default_to_interpret(fn):
    """On a chip a kernel compiles unless its caller asks for the
    interpreter; CPU callers pass ``interpret=True`` explicitly."""
    default = inspect.signature(fn).parameters["interpret"].default
    assert default is not True
