"""Serve a small model with batched requests through the LUNA-quantized path.

The paper's CiM setting is inference: weights stationary in SRAM, inputs
streamed through the LUT multipliers.  The serving engine is the system
analogue — weights resident, requests streamed through batched prefill and
mixed-depth continuous-batching decode with every projection in the chosen
LUNA mode.  This example also shows the v2 request lifecycle: one request
is streamed token-by-token through its ``RequestHandle``.

``--quant`` is the shared flag registered by ``EngineConfig.add_cli_args``:
``lut4``/``int4`` freeze 4-bit affine decode weights on the engine (the
paper's D&C sub-table LUT gemm on the decode hot path), ``nf4``/``nf4p``
freeze non-affine NF4 weights (D&C + full or pruned residual correction);
any other spelling (``luna_*``, ``int8``, ``lut_nf4``, ``bf16``) is a
model-level ``QuantConfig`` mode applied dynamically to every projection.

``--spec ngram|self_lut`` (greedy-only) turns on speculative decoding:
drafts verified in one batched window, accepted prefixes emitted in
bulk, token-identical to plain greedy — see ``docs/speculative.md``.

Run:  PYTHONPATH=src python examples/serve_luna.py --quant luna_approx2 \
          --sampling top_k --top-k 20
      PYTHONPATH=src python examples/serve_luna.py --quant lut4
      PYTHONPATH=src python examples/serve_luna.py --quant nf4
      PYTHONPATH=src python examples/serve_luna.py --quant nf4p \
          --spec self_lut            # drafts alias the decode LUT tree
"""
import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.layers import QuantConfig  # noqa: E402
from repro.models.registry import get_config, get_model  # noqa: E402
from repro.serve.config import ENGINE_QUANT_MODES, EngineConfig  # noqa: E402
from repro.serve.engine import Engine, Request  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    EngineConfig.add_cli_args(ap)
    ap.set_defaults(max_batch=4, max_seq=96, quant="luna_approx")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    model_mode = (args.quant if args.quant not in ENGINE_QUANT_MODES
                  else "bf16")
    cfg = get_config("yi-9b").reduced(quant=QuantConfig(mode=model_mode))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(cfg, params, EngineConfig.from_args(args))

    rng = np.random.default_rng(0)
    # deliberately mixed prompt lengths: the engine buckets them for prefill
    # and decodes them at per-slot positions on one slab
    reqs = [Request(rid=i,
                    prompt=rng.integers(
                        1, cfg.vocab_size, int(rng.integers(3, 9))).tolist(),
                    max_new=args.max_new,
                    priority=1 if i == 0 else 0)
            for i in range(args.requests)]
    stats = engine.serve(reqs)
    print(f"served {len(reqs)} requests in {stats['ticks']} ticks "
          f"({stats['wall_s']:.1f}s wall, quant={args.quant}, "
          f"sampling={args.sampling})")
    print(f"  prefill {stats['prefill_tok_s']:.0f} tok/s over "
          f"{stats['prefill_calls']} bucket calls | decode "
          f"{stats['decode_tok_s']:.0f} tok/s | slot occupancy "
          f"{stats['occupancy']:.0%}")
    for r in reqs[:3]:
        print(f"  req {r.rid}: prompt {r.prompt} -> {r.out}")
    assert stats["done"]

    # v2 lifecycle: stream one more request incrementally off its handle
    handle = engine.submit(Request(
        rid=99, prompt=rng.integers(1, cfg.vocab_size, 5).tolist(),
        max_new=6, priority=1))
    streamed = list(handle.tokens())
    print(f"  streamed req 99: {streamed}")
    assert streamed == handle.out


if __name__ == "__main__":
    main()
