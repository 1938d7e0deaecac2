"""``bench/work/`` against hand counts for one layer of each
configuration, and for the whole decode step of the module a
configuration names, at the published widths."""
from __future__ import annotations

import json

import work
from cell import BENCH, named_objects
from work import mamba2_hybrid


def _model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


#: mamba2-1.3b's published widths (state-spaces/mamba2-1.3b), bf16
MAMBA2_1P3B = {"num_layers": 48, "d_model": 2048, "vocab_size": 50288,
               "norm_eps": 1e-05, "dtype": "bfloat16",
               "ssm": {"state_dim": 128, "expand": 2, "head_dim": 64,
                       "num_groups": 1, "conv_dim": 4, "chunk_size": 256}}


def test_mamba2_layer_by_hand():
    m = MAMBA2_1P3B
    # d 2048, d_inner 4096, 64 heads of 64, state 128, one group, conv 4
    # in_proj width 2*4096 + 2*128 + 64 = 8512; conv channels 4096 + 256
    weights = (2048 * 8512 * 2 + 4096 * 2048 * 2   # w_in, w_out bf16
               + 4 * 4352 * 2 + 4352 * 2           # conv w, b bf16
               + 3 * 64 * 4                        # A_log, D, dt_bias f32
               + 4096 * 2 + 2048 * 4)              # norm_w bf16, ln f32
    state = 2 * 64 * 64 * 128 * 4 + 2 * 3 * 4352 * 2
    flops = (2 * 2048 * 8512 + 2 * 4096 * 2048 + 5 * 64 * 64 * 128
             + 2 * 4 * 4352)
    w = mamba2_hybrid.mamba2_layer(m, rows=3)
    assert w.bytes == weights + 3 * state == 51_703_040 + 3 * 4_246_528
    assert w.flops == 3 * flops == 3 * 54_298_624


def test_zamba2_nf4_layer_by_hand():
    c = _model("zamba2-1.2b-nf4")
    m, frozen = c["model"], frozenset(c["decode_nf4"])
    # state 64: in_proj width 2*4096 + 2*64 + 64 = 8384; conv 4096 + 128
    # NF4: half a byte a weight + f32 column scales + 24 f32 of tables
    w_in = 2048 * 8384 // 2 + 8384 * 4 + 96
    w_out = 4096 * 2048 // 2 + 2048 * 4 + 96
    weights = (w_in + w_out + 4 * 4224 * 2 + 4224 * 2 + 3 * 64 * 4
               + 4096 * 2 + 2048 * 4)
    assert weights == 12_880_832
    w = mamba2_hybrid.mamba2_layer(m, rows=1, frozen=frozen)
    assert w.bytes == weights + 2 * 64 * 64 * 64 * 4 + 2 * 3 * 4224 * 2


def test_zamba2_shared_block_by_hand():
    c = _model("zamba2-1.2b-nf4")
    m, frozen = c["model"], frozenset(c["decode_nf4"])
    # 4 attention matrices 2048 x 2048, 3 MLP matrices 2048 x 8192, NF4
    attn = 4 * (2048 * 2048 // 2 + 2048 * 4 + 96)
    mlp = 2 * (2048 * 8192 // 2 + 8192 * 4 + 96) + (8192 * 2048 // 2
                                                    + 2048 * 4 + 96)
    keys = 100 + 200                  # two rows at positions 99 and 199
    kv = (keys + 2) * 32 * 64 * 2 * 2
    w = mamba2_hybrid.shared_block(m, rows=2, keys=keys, frozen=frozen)
    assert w.bytes == attn + mlp + 2 * 2048 * 4 + kv
    proj = 2 * (4 * 2048 * 2048 + 3 * 2048 * 8192)
    assert w.flops == 2 * proj + 4 * 32 * 64 * keys


def test_zamba2_nf4_decode_step_by_hand():
    """The step of the module the configuration names: 38 Mamba2 layers,
    the shared block's weights once and its keys at 7 applications, and
    the head, from the hand counts above."""
    c = _model("zamba2-1.2b-nf4")
    m, frozen = c["model"], frozenset(c["decode_nf4"])
    step = named_objects(c)["decode_step"]
    rows, keys = 2, 100 + 200
    state = 2 * 64 * 64 * 64 * 4 + 2 * 3 * 4224 * 2
    mamba_flops = (2 * 2048 * 8384 + 2 * 4096 * 2048 + 5 * 64 * 64 * 64
                   + 2 * 4 * 4224)
    attn = 4 * (2048 * 2048 // 2 + 2048 * 4 + 96)
    mlp = 2 * (2048 * 8192 // 2 + 8192 * 4 + 96) + (8192 * 2048 // 2
                                                    + 2048 * 4 + 96)
    kv = (keys + rows) * 32 * 64 * 2 * 2
    proj = 2 * (4 * 2048 * 2048 + 3 * 2048 * 8192)
    head_bytes = 2048 * 32000 * 2 + rows * 2048 * 2 + 2048 * 4
    w = step(m, "hybrid", rows, keys, frozen)
    assert w.bytes == (38 * (12_880_832 + rows * state)
                       + attn + mlp + 2 * 2048 * 4 + 7 * kv + head_bytes)
    assert w.flops == (38 * rows * mamba_flops
                       + 7 * (rows * proj + 4 * 32 * 64 * keys)
                       + rows * 2 * 2048 * 32000)


def test_least_time_names_its_bound():
    from peaks import peaks_for
    p = peaks_for("TPU v5 lite")
    t, bound = work.Work(flops=197e12, bytes=1.0).least_s(p)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.Work(flops=1.0, bytes=819e9).least_s(p)
    assert (t, bound) == (1.0, "memory")
