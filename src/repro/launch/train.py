"""Production training CLI.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --steps 100 \
      --reduced --quant luna_approx

``--reduced`` runs the smoke-scale config (CPU-friendly); without it the
full assigned config is used (real accelerators).  The mesh defaults to all
local devices; on a pod slice, start one process per host and the same code
path scales (jax.distributed initialization hook included).
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="bf16")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host platform devices (CPU testing)")
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (multi-host)")
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")
    import jax
    if args.distributed:
        jax.distributed.initialize()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from repro.core.layers import QuantConfig
    from repro.data.synthetic import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import get_config
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.quant != "bf16":
        from dataclasses import replace
        cfg = replace(cfg, quant=QuantConfig(mode=args.quant))

    mesh = make_host_mesh(model=args.model_parallel)
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         microbatch=args.microbatch,
                         grad_compression=args.grad_compression)
    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
    trainer = Trainer(cfg, tcfg, mesh)
    trainer.run(data)


if __name__ == "__main__":
    main()
