"""Benchmarks reproducing every LUNA-CIM table/figure (one function each).

Each function prints ``name,us_per_call,derived`` CSV rows (derived = the
paper-comparable quantity) and returns a dict for programmatic use.
Timings here are CPU control-flow checks, not speed (``bench/`` measures
speed on the TPU).

Run:  PYTHONPATH=src python benchmarks/paper_tables.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model as cm
from repro.core import luna
from repro.core.luna import LunaMode


def _time(fn, *args, iters=5):
    fn(*args)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out) if hasattr(out, "block_until_ready") else None
    return (time.perf_counter() - t0) / iters * 1e6


def table1() -> dict:
    """Paper Table I: conventional-LUT storage/mux growth 3b..8b."""
    rows = {}
    for bits in range(3, 9):
        c = cm.conventional_cost(bits)
        rows[bits] = (c.srams, c.muxes)
        print(f"table1_{bits}b,0,srams={c.srams};muxes={c.muxes}")
    expected = {3: (48, 42), 4: (128, 120), 5: (320, 310), 6: (768, 756),
                7: (1792, 1778), 8: (4096, 4080)}
    assert rows == expected, rows
    return rows


def table2() -> dict:
    """Paper Table II: traditional vs optimized D&C for 4/8/16 b."""
    rows = {}
    for bits in (4, 8, 16):
        t = cm.conventional_cost(bits)
        o = cm.opt_dc_cost(bits)
        rows[bits] = {"trad": (t.srams, t.muxes),
                      "opt": (o.srams, o.muxes, o.has, o.fas)}
        print(f"table2_{bits}b,0,trad_srams={t.srams};opt_srams={o.srams};"
              f"opt_muxes={o.muxes};opt_has={o.has};opt_fas={o.fas}")
    assert rows[16]["opt"] == (136, 432, 31, 105)
    return rows


def fig5() -> dict:
    """LSB-side product distribution; P(0) = 0.296."""
    vals, probs, _ = luna.lsb_product_distribution()
    us = _time(lambda: luna.lsb_product_distribution.__wrapped__())
    print(f"fig5,{us:.1f},p_zero={probs[0]:.4f}")
    return {"p_zero": float(probs[0]),
            "impossible": luna.impossible_lsb_products()}


def fig6() -> dict:
    """Hamming-distance-optimal Z_LSB approx: argmin 0, HD 0.275."""
    cands, hd = luna.hamming_distance_profile()
    us = _time(luna.hamming_distance_profile)
    print(f"fig6,{us:.1f},argmin={int(np.argmin(hd))};min_hd={hd.min():.4f}")
    return {"argmin": int(np.argmin(hd)), "min_hd": float(hd.min())}


def fig8() -> dict:
    """ApproxD&C error histogram: range [0, 45]."""
    err = luna.error_table(LunaMode.APPROX_DC)
    hist = np.bincount(err.ravel(), minlength=46)
    print(f"fig8,0,err_min={err.min()};err_max={err.max()};"
          f"mae={np.abs(err).mean():.3f}")
    return {"min": int(err.min()), "max": int(err.max()), "hist": hist}


def fig12() -> dict:
    """ApproxD&C2 error histogram: range [-15, 30], balanced."""
    err = luna.error_table(LunaMode.APPROX_DC2)
    print(f"fig12,0,err_min={err.min()};err_max={err.max()};"
          f"mean={err.mean():.3f};mae={np.abs(err).mean():.3f}")
    return {"min": int(err.min()), "max": int(err.max()),
            "mean": float(err.mean())}


def fig13() -> dict:
    """NN-level MAE per multiplier mode (paper's MATLAB experiment).

    Trains one small MLP regressor, then evaluates its forward pass with
    each multiplier mode; MAE is vs the IDEAL (f32) forward, averaged over
    100 random input batches — matching the paper's protocol.
    """
    from repro.core.quant import luna_matmul_f32
    rng = np.random.default_rng(0)
    d_in, d_h, d_out = 16, 32, 4
    w1 = jnp.asarray(rng.normal(size=(d_in, d_h)) * 0.5, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(d_h, d_out)) * 0.5, jnp.float32)

    def fwd(x, mode):
        if mode == "ideal":
            h = jax.nn.relu(x @ w1)
            return h @ w2
        h = jax.nn.relu(luna_matmul_f32(x, w1, mode, bits=4))
        return luna_matmul_f32(h, w2, mode, bits=4)

    maes = {}
    for mode in ("ideal", LunaMode.OPT_DC, LunaMode.APPROX_DC2,
                 LunaMode.APPROX_DC):
        tot = 0.0
        for it in range(100):          # paper: 100 iterations
            x = jnp.asarray(rng.normal(size=(8, d_in)), jnp.float32)
            ref = fwd(x, "ideal")
            out = fwd(x, mode)
            tot += float(jnp.abs(out - ref).mean())
        maes[str(mode)] = tot / 100
        print(f"fig13_{mode},0,mae={maes[str(mode)]:.4f}")
    assert maes["ideal"] == 0.0
    # paper ordering: exact D&C < ApproxD&C2 < ApproxD&C (balanced error wins)
    assert maes[str(LunaMode.OPT_DC)] <= maes[str(LunaMode.APPROX_DC)]
    return maes


def fig14() -> dict:
    """Transient-sim re-enactment: W=0110 fixed, Y in {1010,1011,0011,1100}."""
    w = 0b0110
    outs = {}
    for y in (0b1010, 0b1011, 0b0011, 0b1100):
        z = int(luna.luna_product(jnp.int32(w), jnp.int32(y), 4,
                                  LunaMode.OPT_DC))
        outs[f"{y:04b}"] = f"{z:08b}"
        assert z == w * y
    print(f"fig14,0,{';'.join(f'Y={k}->OUT={v}' for k, v in outs.items())}")
    return outs


def fig15() -> dict:
    """Energy: multiplier = 47.96 fJ = 0.0276 % of SRAM write energy."""
    rep = cm.energy_report()
    print(f"fig15,0,mult_share={rep['multiplier_share']*100:.4f}%")
    return rep


def fig16() -> dict:
    """Area comparison across variants (transistor model); opt D&C ~3.7x."""
    rep = cm.area_report(4)
    ratio = rep["opt_dc"]["area_vs_conventional"]
    print(f"fig16,0,opt_dc_vs_conventional={ratio:.2f}x;"
          f"approx_dc={rep['approx_dc']['area_vs_conventional']:.2f}x")
    return rep


def fig18() -> dict:
    """Array overhead: 4 LUNA units on 8x8 SRAM = 32 %."""
    rep = cm.array_overhead(4)
    print(f"fig18,0,overhead={rep['overhead_fraction']*100:.1f}%")
    return rep


ALL = [table1, table2, fig5, fig6, fig8, fig12, fig13, fig14, fig15, fig16,
       fig18]


def main() -> None:
    """Print every table and figure as CSV; exit 1 if any failed."""
    import sys
    import traceback
    print("name,us_per_call,derived")
    failures = 0
    for fn in ALL:
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{fn.__name__},FAIL,{traceback.format_exc(limit=1)!r}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
