"""Paper Fig 13 end-to-end: train MLPs whose forward pass uses each LUNA
multiplier mode (QAT via STE) and compare final task MAE — the paper's
"separate neural networks for each method" experiment.

Extended with the serving-side PTQ columns: the bf16-trained ("ideal") net
re-evaluated with its weights frozen to 4-bit ``QuantizedWeight`` leaves —
exactly what ``EngineConfig(quant=...)`` does to decode projections.

* affine (``lut4``): the uniform grid; documented bound
  ``MAE(ptq) <= PTQ_MAE_BOUND * MAE(ideal)``.
* non-affine pair (``nf4`` vs the direct full-table NF4 dequant oracle):
  the least-squares D&C split plus the per-code residual correction
  recovers the codebook exactly up to float rounding, so the documented
  bound is ``|MAE(nf4) - MAE(nf4_direct)| <= NF4_DC_VS_DIRECT_TOL``.
* pruned residual (``nf4p``): dropping small residual entries trades
  table bytes for a bounded MAE delta,
  ``MAE(nf4p) <= MAE(nf4) + NF4P_MAE_DELTA_BOUND``; the harness reports
  the residual-table bytes saved alongside.

Run:  PYTHONPATH=src python examples/fig13_nn_accuracy.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lut import prune_residual, residual_table_bytes
from repro.core.quant import (NF4P_PRUNE_THRESHOLD, quantize_weight,
                              ste_luna_matmul)
from repro.kernels.lut_gemm.ops import quantized_matmul

MODES = ["ideal", "opt_dc", "approx_dc2", "approx_dc"]

#: documented PTQ accuracy bound: frozen-4-bit MAE vs the bf16-trained MAE
PTQ_MAE_BOUND = 1.25

#: documented bound: residual-corrected D&C NF4 vs direct full-table NF4
#: dequant — the correction is exact up to float rounding, so the two MAEs
#: may differ only by accumulation noise.
NF4_DC_VS_DIRECT_TOL = 1e-4

#: documented bound on the MAE cost of pruning the NF4 residual sub-table
#: at ``NF4P_PRUNE_THRESHOLD`` (absolute MAE delta vs unpruned nf4).
NF4P_MAE_DELTA_BOUND = 0.05


def make_data(n=512, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d, 1)).astype(np.float32)
    y = np.tanh(x @ w_true) + 0.05 * rng.normal(size=(n, 1))
    return jnp.asarray(x), jnp.asarray(y.astype(np.float32))


def mlp_fwd(params, x, mode):
    mm = ((lambda a, b: a @ b) if mode == "ideal"
          else (lambda a, b: ste_luna_matmul(a, b, mode, 4)))
    h = jnp.tanh(mm(x, params["w1"]) + params["b1"])
    return mm(h, params["w2"]) + params["b2"]


def train_one(mode, steps=300, lr=3e-2):
    x, y = make_data()
    rng = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(rng)
    params = {"w1": jax.random.normal(k1, (8, 16)) * 0.3,
              "b1": jnp.zeros((16,)),
              "w2": jax.random.normal(k2, (16, 1)) * 0.3,
              "b2": jnp.zeros((1,))}

    @jax.jit
    def step(params):
        def loss_fn(p):
            return jnp.mean((mlp_fwd(p, x, mode) - y) ** 2)
        loss, g = jax.value_and_grad(loss_fn)(params)
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
        return params, loss

    for _ in range(steps):
        params, loss = step(params)
    mae = float(jnp.abs(mlp_fwd(params, x, mode) - y).mean())
    return mae, params


def ptq_mae(params, quant="lut4"):
    """MAE of the bf16-trained net with weights frozen to 4-bit codes —
    the serving engine's ``quant="lut4"|"nf4"|"nf4p"`` transform, or the
    ``nf4_direct`` oracle."""
    x, y = make_data()
    q1 = quantize_weight(params["w1"], quant)
    q2 = quantize_weight(params["w2"], quant)
    h = jnp.tanh(quantized_matmul(x, q1) + params["b1"])
    out = quantized_matmul(h, q2) + params["b2"]
    return float(jnp.abs(out - y).mean())


def nf4p_table_report(threshold=NF4P_PRUNE_THRESHOLD):
    """Residual sub-table cost: dense (16,) f32 vs pruned sparse storage."""
    from repro.core.lut import NF4_CODEBOOK, dc_decompose_codebook
    _, _, residual = dc_decompose_codebook(jnp.asarray(NF4_CODEBOOK))
    kept_idx, _ = prune_residual(residual, threshold)
    dense, pruned = residual_table_bytes(int(kept_idx.shape[0]))
    return {"kept": int(kept_idx.shape[0]), "dense_bytes": dense,
            "pruned_bytes": pruned, "bytes_saved": dense - pruned}


def main():
    print("mode,final_MAE  (paper Fig 13: exact < ApproxD&C2 < ApproxD&C)")
    results = {}
    trained = {}
    for mode in MODES:
        mae, params = train_one(mode)
        results[mode] = mae
        trained[mode] = params
        print(f"  {mode:>10}: MAE {mae:.4f}")
    for quant in ("lut4", "nf4", "nf4_direct", "nf4p"):
        label = f"ptq_{quant}"
        results[label] = ptq_mae(trained["ideal"], quant)
        print(f"  {label:>14}: MAE {results[label]:.4f}")
    tab = nf4p_table_report()
    print(f"  nf4p residual table: kept {tab['kept']}/16 entries, "
          f"{tab['pruned_bytes']}B vs {tab['dense_bytes']}B dense "
          f"({tab['bytes_saved']}B saved)")
    assert results["ideal"] <= results["approx_dc"] * 1.2
    assert results["ptq_lut4"] <= results["ideal"] * PTQ_MAE_BOUND, \
        (results["ptq_lut4"], results["ideal"])
    # non-affine: residual-corrected D&C matches direct dequant up to
    # float rounding; pruning costs a bounded MAE delta and saves bytes
    assert abs(results["ptq_nf4"] - results["ptq_nf4_direct"]) \
        <= NF4_DC_VS_DIRECT_TOL, \
        (results["ptq_nf4"], results["ptq_nf4_direct"])
    assert results["ptq_nf4p"] <= results["ptq_nf4"] + NF4P_MAE_DELTA_BOUND, \
        (results["ptq_nf4p"], results["ptq_nf4"])
    assert tab["bytes_saved"] > 0
    results["nf4p_table"] = tab
    return results


if __name__ == "__main__":
    main()
