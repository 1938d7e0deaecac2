"""Config dataclasses for the model zoo and runtime."""
from __future__ import annotations

from dataclasses import dataclass, field, replace

from repro.core.layers import QuantConfig


@dataclass(frozen=True)
class MoEConfig:
    """Routed experts, of which this device holds ``num_experts``.

    The router scores ``router_width`` experts (0: as many as are held);
    the experts held here are ``first_held .. first_held + num_experts -
    1`` of them, so one device's share of an expert-parallel deployment
    is the same model with a narrower ``num_experts``."""
    num_experts: int            # routed experts held here
    num_shared: int = 0
    top_k: int = 2
    d_expert: int = 0           # expert FFN hidden size
    capacity_factor: float = 1.25
    first_dense: int = 1        # leading dense layers (deepseek-v2 style)
    dense_ff: int = 0           # FFN width of the dense layers
    aux_loss_coef: float = 0.001
    router_width: int = 0       # experts the router scores; 0 = num_experts
    first_held: int = 0         # router index of the first held expert

    @property
    def routed(self) -> int:
        """Experts the router scores."""
        return self.router_width or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    q_lora_rank: int = 0        # 0 = no q compression
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128


@dataclass(frozen=True)
class RopeScaling:
    """YaRN rope scaling (arXiv:2309.00071), keyed as DeepSeek-V2's
    ``config.json`` keys it: frequencies between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations over
    ``original_max_position_embeddings`` are ramped from unscaled to
    divided by ``factor``; the softmax scale gains
    ``yarn_get_mscale(factor, mscale_all_dim) ** 2``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


@dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128
    expand: int = 2
    head_dim: int = 64
    num_groups: int = 1
    conv_dim: int = 4
    chunk_size: int = 256


@dataclass(frozen=True)
class HybridConfig:
    """Zamba2-style: one weight-shared attention+MLP block applied every
    ``period`` SSM layers."""
    period: int = 6
    shared_num_heads: int = 32
    shared_num_kv_heads: int = 32
    shared_d_ff: int = 8192


@dataclass(frozen=True)
class EncDecConfig:
    enc_layers: int = 6
    enc_seq: int = 1500          # whisper: 30 s of audio @ 2x conv stride


@dataclass(frozen=True)
class VLMConfig:
    num_patches: int = 576       # llava-next base grid (anyres tiles stubbed)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | encdec | hybrid | ssm | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // num_heads
    mlp_type: str = "swiglu"     # swiglu | gelu
    rope_theta: float = 10000.0
    rope_scaling: RopeScaling | None = None
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    hybrid: HybridConfig | None = None
    encdec: EncDecConfig | None = None
    vlm: VLMConfig | None = None
    quant: QuantConfig = field(default_factory=QuantConfig)
    attn_impl: str = "chunked"   # full | chunked | flash
    attn_chunk: int = 512
    remat: bool = True
    scan_layers: bool = True
    # decode attention: "dense" = plain cache update + SDPA (baseline);
    # "sharded" = flash-decode shard_map over the model axis (hillclimbed —
    # kills the cache-reshard collectives; see EXPERIMENTS.md §Perf)
    decode_attn: str = "dense"
    # attention operand precision: True (baseline) casts K/V/P to f32 and
    # materializes f32 copies; False keeps bf16 operands and relies on the
    # MXU's f32 accumulation (preferred_element_type) — hillclimb knob for
    # the HBM-bytes roofline term.
    attn_f32: bool = True
    # remat policy: "nothing" (full recompute, min memory) | "dots" (save
    # matmul outputs — trades memory for fewer recomputed FLOPs/bytes)
    remat_policy: str = "nothing"
    # serving param sharding: "fsdp" (baseline, same as training — weights
    # sharded over data+model, all-gathered per use) | "tp" (replicate over
    # data, shard over model only — no per-token weight all-gathers; right
    # when params_bf16/model_axis fits HBM)
    serve_param_sharding: str = "fsdp"
    # sharded flash-decode operand handling: "f32" (baseline) repeats KV to
    # full H in f32; "bf16_grouped" keeps bf16 operands and GQA-grouped
    # einsums (no repeat — legal inside shard_map where tensors are local)
    decode_attn_precision: str = "f32"
    # attention byte-efficiency knobs (hillclimb; False = paper-baseline):
    # fused scale+mask where() instead of mul + broadcast-bias add
    attn_fused_mask: bool = False
    # causal chunks attend only to keys <= chunk end (the flash kernel's
    # block skipping; halves causal attention work). Applies to the
    # unrolled/accounting path — the TPU runtime gets this from the kernel.
    attn_causal_skip: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def reduced(self, **overrides) -> "ModelConfig":
        """A smoke-test-sized config of the same family (see assignment)."""
        small = dict(
            num_layers=min(self.num_layers, 2),
            d_model=128,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads else 0,
            d_ff=256,
            vocab_size=512,
            head_dim=32,
        )
        if self.moe:
            small["moe"] = replace(self.moe, num_experts=8, top_k=2,
                                   d_expert=64, dense_ff=256,
                                   router_width=0, first_held=0)
        if self.mla:
            small["mla"] = MLAConfig(kv_lora_rank=32, q_lora_rank=0,
                                     qk_nope_dim=16, qk_rope_dim=16, v_dim=16)
        if self.ssm:
            small["ssm"] = replace(self.ssm, state_dim=16, head_dim=16,
                                   chunk_size=32)
        if self.hybrid:
            small["hybrid"] = replace(self.hybrid, period=2,
                                      shared_num_heads=4,
                                      shared_num_kv_heads=2, shared_d_ff=256)
            small["num_layers"] = 4
        if self.encdec:
            small["encdec"] = replace(self.encdec, enc_layers=2, enc_seq=64)
        if self.vlm:
            small["vlm"] = VLMConfig(num_patches=16)
        small.update(overrides)
        return replace(self, **small)


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")
ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
