"""Tiny cells for the CPU tests: the published configurations' layer
kinds at a few dozen widths, served as the benchmark serves them."""
from __future__ import annotations

import copy

from cell import Cell, named_objects

MAMBA2 = {
    "name": "mamba2-tiny", "arch": "mamba2-1.3b",
    "reference": "mamba2_hybrid", "work": "mamba2_hybrid",
    "model": {"num_layers": 2, "d_model": 64, "vocab_size": 256,
              "norm_eps": 1e-5, "dtype": "bfloat16",
              "ssm": {"state_dim": 16, "expand": 2, "head_dim": 16,
                      "num_groups": 1, "conv_dim": 4, "chunk_size": 32}},
    "serving": {"max_batch": 4, "max_seq": 128, "prefill_bucket": 32,
                "prefill_chunk": 32},
    "check": {"sample_tokens": 64, "max_requests": 6, "min_tokens": 8,
              "max_logit_gap": 0.08},
}

ZAMBA2_NF4 = {
    "name": "zamba2-nf4-tiny", "arch": "zamba2-1.2b",
    "reference": "mamba2_hybrid", "work": "mamba2_hybrid",
    "model": {"num_layers": 4, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 4, "d_ff": 128, "vocab_size": 256,
              "head_dim": 16, "rope_theta": 10000.0, "norm_eps": 1e-5,
              "dtype": "bfloat16",
              "ssm": {"state_dim": 16, "expand": 2, "head_dim": 16,
                      "num_groups": 1, "conv_dim": 4, "chunk_size": 32},
              "hybrid": {"period": 2, "shared_num_heads": 4,
                         "shared_num_kv_heads": 4, "shared_d_ff": 128}},
    "decode_nf4": ["w_in", "w_out", "wq", "wk", "wv", "wo", "w_gate",
                   "w_up", "w_down"],
    "serving": {"quant": "nf4", "max_batch": 4, "max_seq": 128,
                "paged": True, "block_size": 8, "prefill_bucket": 32,
                "prefill_chunk": 32},
    "check": {"sample_tokens": 64, "max_requests": 6, "min_tokens": 8,
              "max_logit_gap": 0.08},
}

OPEN = {"loop": "open", "rate_rps": 12.0,
        "phases": [{"seconds": 0.8, "rate_mult": 0.5},
                   {"seconds": 0.2, "rate_mult": 3.0}],
        "prompt": {"median": 20, "sigma": 0.8, "min": 4, "max": 80},
        "output": {"median": 8, "sigma": 0.6, "min": 2, "max": 40},
        "drain_seconds": 60}

CLOSED = {"loop": "closed", "clients": 4, "rounds": 4,
          "prompt": {"median": 24, "sigma": 0.5, "min": 4, "max": 80},
          "output": {"median": 10, "sigma": 0.5, "min": 2, "max": 40}}


def cell(config: dict, traffic: dict, **check) -> Cell:
    config = copy.deepcopy(config)
    config["check"].update(check)
    e2e = [{"name": n, "unit": u} for n, u in (
        ("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"),
        ("tokens_per_s", "tokens/s"), ("setup_s", "s"))]
    if traffic["loop"] == "closed":
        e2e = e2e[1:]
    return Cell(name=config["name"], chips=1, config=config,
                traffic=copy.deepcopy(traffic), end_to_end=tuple(e2e),
                per_layer=(), **named_objects(config))
