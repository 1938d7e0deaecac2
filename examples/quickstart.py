"""Quickstart: the LUNA-CIM technique end to end in 60 seconds.

1. the paper's multiplier variants on raw 4-bit codes (incl. the Fig 14
   transient-sim re-enactment: W=0110 x Y sequence);
2. hardware cost/energy/area model (Tables I/II, Figs 15/16/18);
3. a real matmul through the Pallas LUNA kernel;
4. a LunaDense-quantized transformer forward pass (model-level
   ``QuantConfig`` — dynamic quantization of every projection);
5. the serving engine with ``EngineConfig(quant="lut4")`` — 4-bit decode
   weights through the LUT gemm, their table derived by the paper's D&C
   split (the ``--quant lut4`` flag on both serving CLIs).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model as cm
from repro.core.layers import QuantConfig
from repro.core.luna import LunaMode, luna_product
from repro.kernels.luna_mm.ops import luna_matmul_f32_kernel

print("=" * 66)
print("1. LUNA multiplier variants (paper Figs 1-10)")
print("=" * 66)
w, y = 0b0110, 0b1011            # 6 x 11
for mode in LunaMode:
    z = int(luna_product(jnp.int32(w), jnp.int32(y), 4, mode))
    tag = "exact" if LunaMode(mode).is_exact else f"err={w*y-z:+d}"
    print(f"  {mode.value:>14}: {w} x {y} = {z:3d}  ({tag})")

print("\n  Fig 14 re-enactment: W=0110 fixed, Y applied sequentially")
for y_seq in (0b1010, 0b1011, 0b0011, 0b1100):
    z = int(luna_product(jnp.int32(w), jnp.int32(y_seq), 4, LunaMode.OPT_DC))
    print(f"    Y={y_seq:04b} -> OUT={z:08b} ({z})")

print()
print("=" * 66)
print("2. Hardware cost model (Tables I/II, Figs 15/16/18)")
print("=" * 66)
for bits in (4, 8, 16):
    conv = cm.conventional_cost(bits)
    opt = cm.opt_dc_cost(bits)
    print(f"  {bits:2d}b: conventional {conv.srams:>8} SRAMs -> "
          f"optimized D&C {opt.srams:>4} SRAMs "
          f"({conv.srams / opt.srams:.0f}x less storage)")
area = cm.area_report(4)
print(f"  area: optimized D&C is "
      f"{area['opt_dc']['area_vs_conventional']:.1f}x smaller (paper: ~3.7x)")
en = cm.energy_report()
print(f"  energy: multiplier = {en['mux_multiplier_J']*1e15:.2f} fJ "
      f"= {en['multiplier_share']*100:.4f}% of SRAM write (paper: 0.0276%)")
print(f"  array overhead: {cm.array_overhead(4)['overhead_fraction']*100:.0f}%"
      " (paper: 32%)")

print()
print("=" * 66)
print("3. Float matmul through the Pallas LUNA kernel (interpret mode)")
print("=" * 66)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
wm = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
ref = x @ wm
for mode in ("opt_dc", "approx_dc", "approx_dc2"):
    out = luna_matmul_f32_kernel(x, wm, mode=mode, interpret=True)
    rel = float(jnp.abs(out - ref).mean() / jnp.abs(ref).mean())
    print(f"  {mode:>10}: mean rel err vs f32 = {rel:.4f}")

print()
print("=" * 66)
print("4. A transformer under LUNA quantization (reduced yi-9b)")
print("=" * 66)
from repro.models.registry import get_config, get_model  # noqa: E402

for mode in ("bf16", "luna_dc", "luna_approx"):
    cfg = get_config("yi-9b").reduced(quant=QuantConfig(mode=mode))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)))
    loss, _ = model.loss(params, {"tokens": toks, "labels": toks})
    print(f"  quant={mode:>12}: loss {float(loss):.4f}")

print()
print("=" * 66)
print('5. Serving with EngineConfig(quant="lut4"): 4-bit decode weights')
print("=" * 66)
from repro.serve.config import EngineConfig  # noqa: E402
from repro.serve.engine import Engine, Request  # noqa: E402

cfg = get_config("yi-9b").reduced(dtype="float32")
model = get_model(cfg)
params = model.init(jax.random.PRNGKey(0))
for quant in (None, "lut4"):
    engine = Engine(cfg, params,
                    EngineConfig(max_batch=2, max_seq=48, quant=quant))
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 5).tolist(),
                    max_new=6)
            for i in range(2)]
    stats = engine.serve(reqs)
    print(f"  quant={str(quant):>5}: {stats['decode_tokens']} decode tok, "
          f"outputs {[r.out[:3] for r in reqs]}")
print("\nDone.")
