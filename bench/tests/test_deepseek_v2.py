"""DeepSeek-V2-Lite's share of expert parallelism (family ``moe``): the
float32 reference against the engine at a tiny size on the CPU (batched
and chunked prefill, paged latent-cache decode, the frozen NF4 tree, a
share of 2 held experts of a router over 8), the decode step's work
count by hand at the published widths, and the expert kernel's readers
on a synthetic trace."""
from __future__ import annotations

import copy
import json
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import check
import weights
from cell import BENCH, load_module
from e2e import Record
from peaks import peaks_for
from reference.deepseek_v2 import Reference
from run import model_config, reference_dims
from trace_reduce import Reduced
from work import deepseek_v2

#: one dense layer, then two MoE layers holding experts 2 and 3 of a
#: router over 8 (top-2) beside 2 shared experts; MLA with YaRN whose
#: cos/sin factor is not 1, served paged with frozen NF4 decode weights
DSV2_TINY = {
    "name": "dsv2-tiny", "arch": "deepseek-v2-lite-16b",
    "reference": "deepseek_v2", "work": "deepseek_v2",
    "model": {"num_layers": 3, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 4, "d_ff": 32, "vocab_size": 256,
              "head_dim": 16, "rope_theta": 10000.0, "norm_eps": 1e-6,
              "dtype": "bfloat16",
              "rope_scaling": {"factor": 4.0,
                               "original_max_position_embeddings": 32,
                               "beta_fast": 32.0, "beta_slow": 1.0,
                               "mscale": 1.0, "mscale_all_dim": 0.707},
              "mla": {"kv_lora_rank": 32, "q_lora_rank": 0,
                      "qk_nope_dim": 16, "qk_rope_dim": 16, "v_dim": 16},
              "moe": {"num_experts": 2, "router_width": 8, "first_held": 2,
                      "num_shared": 2, "top_k": 2, "d_expert": 32,
                      "first_dense": 1, "dense_ff": 128}},
    "decode_nf4": ["wq", "w_dkv", "wo", "w_gate", "w_up", "w_down"],
    "serving": {"quant": "nf4", "max_batch": 4, "max_seq": 128,
                "paged": True, "block_size": 8, "prefill_bucket": 32,
                "prefill_chunk": 32},
}

#: the program against the f32 reference at these widths reads 0.00-0.02
#: (seeds 3, 5, 11, both trees), the float8 control 0.66-0.82
GAP = 0.08
# prompts under one chunk (batched bucket prefill) and over it (chunked)
LENS = (5, 17, 31, 45, 70)


def _serve(config, seed, prompt_lens, max_new=12):
    from repro.models.registry import get_model
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Engine, Request
    cfg = model_config(config)
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, seed)
    eng = Engine(cfg, params, EngineConfig(**config["serving"]))
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size,
                                               n).tolist(), max_new=max_new)
            for i, n in enumerate(prompt_lens)]
    eng.serve(reqs)
    recs = [Record(rid=r.rid, due=0.0, prompt=tuple(r.prompt),
                   max_new=max_new, tokens=list(r.out)) for r in reqs]
    return cfg, params, recs, eng


def _config(tree):
    config = copy.deepcopy(DSV2_TINY)
    if tree == "bf16":
        config["serving"]["quant"] = None
        config["decode_nf4"] = []
    return config


@pytest.mark.parametrize("tree", ["nf4", "bf16"])
def test_engine_agrees_with_reference(tree):
    config = _config(tree)
    cfg, params, recs, eng = _serve(config, 11, LENS)
    assert cfg.moe.routed == 8 and cfg.moe.num_experts == 2
    assert all(len(r.tokens) == 12 for r in recs)
    ref = Reference(reference_dims(config, cfg), params, seq_len=128,
                    decode_nf4=config["decode_nf4"])
    gaps = check.served_gaps(ref, recs)
    assert gaps.size == 12 * len(LENS)
    assert gaps.max() <= GAP, gaps.max()
    # the decode steps counted the held experts' tokens
    reg = eng.registry
    routed = reg.counter("engine_expert_pairs_routed_total").value()
    held = reg.counter("engine_expert_pairs_held_total").value()
    assert routed == 11 * len(LENS) * cfg.moe.top_k * 2
    assert 0 < held < routed


def test_nf4_decode_and_held_share_are_what_the_reference_checks():
    """The reference disagrees with the nf4 engine by far more without
    the NF4 decode weights, or with another share of the experts: the
    check sees the quantized decode and the held experts."""
    config = _config("nf4")
    cfg, params, recs, _ = _serve(config, 3, LENS, max_new=16)
    dims = reference_dims(config, cfg)
    good = check.served_gaps(Reference(
        dims, params, seq_len=128, decode_nf4=config["decode_nf4"]),
        recs).max()
    no_nf4 = check.served_gaps(Reference(dims, params, seq_len=128),
                               recs).max()
    other = dict(dims, moe=dict(dims["moe"], first_held=4))
    other_share = check.served_gaps(Reference(
        other, params, seq_len=128, decode_nf4=config["decode_nf4"]),
        recs).max()
    assert good <= GAP
    assert no_nf4 > 3 * good, (good, no_nf4)
    assert other_share > 3 * good, (good, other_share)


def test_dsv2_decode_step_by_hand():
    c = json.loads((BENCH / "configs" / "deepseek-v2-lite-ep8-nf4.json")
                   .read_text())
    m, frozen = c["model"], frozenset(c["decode_nf4"])

    def nf4(k, n):           # half a byte a weight, f32 scales, tables
        return k * n // 2 + n * 4 + 96

    # MLA: wq 2048 x 3072, w_dkv 2048 x 576, wo 2048 x 2048 frozen;
    # w_uk, w_uv 512 x 2048 bf16; kv_norm 512, ln1, ln2 2048 f32
    attn_w = (nf4(2048, 3072) + nf4(2048, 576) + nf4(2048, 2048)
              + 2 * 512 * 2048 * 2 + 512 * 4 + 2 * 2048 * 4)
    assert attn_w == 10_068_512
    rows, keys = 32, 32 * 500
    attn = deepseek_v2.mla_layer(m, rows, keys, frozen)
    assert attn.bytes == attn_w + (keys + rows) * 576 * 2
    proj = 2 * 2048 * (3072 + 576) + 2 * 2048 * 2048
    absorb = 2 * 16 * 128 * 512 * 2
    assert attn.flops == rows * (proj + absorb) + keys * 16 * 2 * (576 + 512)
    step = deepseek_v2.decode_step(m, "moe", rows, keys, frozen)
    routed = step.parts["routed_experts"]
    # 26 layers x 8 held experts x (gate, up 2048 x 1408, down 1408 x 2048)
    assert routed.bytes == 26 * 8 * (2 * nf4(2048, 1408) + nf4(1408, 2048))
    # 32 rows x top-6 x 8/64 held = 24 tokens a layer
    assert routed.flops == 26 * 24 * 2 * 3 * 2048 * 1408
    shared = step.parts["shared_experts"]
    assert shared.bytes == 26 * (2 * nf4(2048, 2816) + nf4(2816, 2048))
    dense = step.parts["dense_ffn"]
    assert dense.bytes == 2 * nf4(2048, 10944) + nf4(10944, 2048)
    assert step.parts["router"].bytes == 26 * 2048 * 64 * 4
    assert step.parts["head"].bytes == 2048 * 102400 * 2 + rows * 2048 * 2 \
        + 2048 * 4
    assert step.bytes == sum(p.bytes for p in step.parts.values())
    assert step.flops == sum(p.flops for p in step.parts.values())
    # the named module is this one
    mod = load_module(BENCH / "work" / f"{c['work']}.py")
    assert mod.decode_step(m, "moe", rows, keys, frozen).bytes == step.bytes


def _ctx(ops, runs, work):
    trace = Reduced(window_s=10.0, busy_s=9.0, chips=1,
                    programs={"jit__decode_impl": [5.0, runs],
                              "jit__prefill_impl": [1.0, 3]},
                    ops=ops)
    return SimpleNamespace(trace=trace, decode_step_work=work,
                           peaks=peaks_for("TPU v5 lite"))


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


def test_expert_readers_on_a_synthetic_trace():
    ops = {"jit__decode_impl/%lut_gemm_dc_res_experts.1": 0.3,
           "jit__decode_impl/%lut_gemm_dc_res_experts.2": 0.1,
           "jit__decode_impl/%lut_gemm_dc_res.3": 2.0,
           "jit__prefill_impl/%lut_gemm_dc_res_experts.1": 0.5}
    work = deepseek_v2.decode_step(
        {"d_model": 2048, "num_layers": 27, "num_heads": 16,
         "vocab_size": 102400,
         "mla": {"kv_lora_rank": 512, "qk_nope_dim": 128,
                 "qk_rope_dim": 64, "v_dim": 128},
         "moe": {"num_experts": 8, "router_width": 64, "top_k": 6,
                 "d_expert": 1408, "first_dense": 1, "dense_ff": 10944,
                 "num_shared": 2}},
        "moe", 32, 16000, frozenset({"w_gate", "w_up", "w_down"}))
    ctx = _ctx(ops, 100, work)
    # 0.4 s of the kernel inside 100 decode runs; the prefill's and the
    # dense kernel's time are not its
    assert _reader("expert_decode_ms").read(ctx) == pytest.approx(4.0)
    least = work.parts["routed_experts"].bytes / 819e9
    assert work.parts["routed_experts"].least_s(ctx.peaks)[1] == "memory"
    assert _reader("expert_gemm_roofline").read(ctx) == pytest.approx(
        100.0 * 100 * least / 0.4)


@pytest.mark.parametrize("case", ["no_kernel", "no_runs", "no_parts"])
def test_expert_readers_read_nothing_where_there_is_nothing(case):
    """Where the trace has no expert kernel (a program without it) or no
    decode run, or the work count has no routed-expert part (another
    family's), the readers leave their metric out."""
    ops = {"jit__decode_impl/%lut_gemm_dc_res_experts.1": 0.3}
    work = SimpleNamespace(parts={"routed_experts": deepseek_v2.Work(
        1e9, 1e9)})
    if case == "no_kernel":
        ctx = _ctx({"jit__decode_impl/%lut_gemm_dc_res.3": 2.0}, 10, work)
    elif case == "no_runs":
        ctx = _ctx(ops, 0, work)
    else:
        ctx = _ctx(ops, 10, deepseek_v2.Work(1e9, 1e9))
    assert _reader("expert_gemm_roofline").read(ctx) is None
    if case != "no_parts":
        assert _reader("expert_decode_ms").read(ctx) is None
