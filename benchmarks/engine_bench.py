"""Serving-engine benchmarks: decode throughput vs slab width, batched
(bucketed) prefill vs per-row prefill, paged-block KV vs the dense slab,
and chunked-prefill interleave under a long-prompt admission — for the
attention AND recurrent (ssm/hybrid, state-continuing SSD scan) families.

Prints the orchestrator's ``name,us_per_call,derived`` CSV rows.  Timings on
CPU are correctness-level; the derived column carries the quantities that
transfer (tokens/s, per-token cost, speedup ratios).

  PYTHONPATH=src python benchmarks/engine_bench.py --quant luna_approx
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")
for _p in (_SRC, _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

DEF_BATCHES = (1, 8, 32)


def _build(quant: str, max_batch: int, max_seq: int, arch: str = "yi-9b",
           clock=None, **engine_kw):
    """``quant`` routes like the CLIs: ``lut4``/``int4`` become
    ``EngineConfig.quant`` (frozen 4-bit decode weights through the D&C LUT
    gemm); any other non-bf16 spelling is a model-level ``QuantConfig``
    mode (dynamic, every projection)."""
    import jax

    from repro.core.layers import QuantConfig
    from repro.models.registry import get_config, get_model
    from repro.serve.config import ENGINE_QUANT_MODES, EngineConfig
    from repro.serve.engine import Engine

    cfg = get_config(arch).reduced()
    if quant in ENGINE_QUANT_MODES:
        engine_kw["quant"] = quant
    elif quant != "bf16":
        from dataclasses import replace
        cfg = replace(cfg, quant=QuantConfig(mode=quant))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    econf = EngineConfig(max_batch=max_batch, max_seq=max_seq, **engine_kw)
    return cfg, Engine(cfg, params, econf, clock=clock)


def _steady_decode_tok_s(eng, cfg, mb: int, ticks: int, max_seq: int,
                         periodic: bool = False) -> float:
    """Fill every slot, burn warm-up (compile) ticks, time ``ticks``.
    ``periodic``: repeat a short token pattern instead of a uniform random
    prompt — gives the n-gram draft proposer material (the spec section
    runs its baseline with the same prompts for a fair ratio)."""
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(0)

    def prompt():
        if periodic:
            return rng.integers(1, cfg.vocab_size, 3).tolist() * 3
        return rng.integers(1, cfg.vocab_size, 6).tolist()

    reqs = [Request(rid=i, prompt=prompt(),
                    max_new=max_seq)           # never finishes mid-bench
            for i in range(mb)]
    for i, r in enumerate(reqs):
        assert eng.submit(r), i
    for _ in range(3):                          # warm-up (compile) ticks
        eng.step()
    eng.metrics.decode_s = 0.0
    eng.metrics.decode_tokens = 0
    t0 = time.perf_counter()
    for _ in range(ticks):
        eng.step()
    wall = time.perf_counter() - t0
    return eng.metrics.decode_tokens / max(wall, 1e-9)


def decode_throughput(quant: str = "bf16", batches=DEF_BATCHES,
                      ticks: int = 24, max_seq: int = 128) -> dict:
    """Steady-state decode tokens/s with every slot occupied, per slab
    width."""
    rows = {}
    for mb in batches:
        cfg, eng = _build(quant, mb, max_seq)
        tok_s = _steady_decode_tok_s(eng, cfg, mb, ticks, max_seq)
        us = mb / max(tok_s, 1e-9) * 1e6
        rows[mb] = tok_s
        print(f"engine_decode_b{mb},{us:.0f},"
              f"tok_s={tok_s:.1f};quant={quant};ticks={ticks}")
    if 1 in rows:
        for mb in batches:
            if mb != 1:
                print(f"engine_decode_scaling_b{mb},0,"
                      f"tok_s_ratio_vs_b1={rows[mb] / rows[1]:.2f}")
    return rows


def decode_paged_vs_dense(quant: str = "bf16", batch: int = 8,
                          ticks: int = 24, max_seq: int = 128) -> dict:
    """Steady-state decode: paged-block pool vs the dense slab, same
    workload (acceptance gate: the paged gather must not regress decode)."""
    rows = {}
    for mode, kw in (("dense", {}),
                     ("paged", {"paged": True, "block_size": 16})):
        cfg, eng = _build(quant, batch, max_seq, **kw)
        tok_s = _steady_decode_tok_s(eng, cfg, batch, ticks, max_seq)
        us = batch / max(tok_s, 1e-9) * 1e6
        rows[mode] = tok_s
        print(f"engine_decode_{mode}_b{batch},{us:.0f},"
              f"tok_s={tok_s:.1f};quant={quant}")
    ratio = rows["paged"] / max(rows["dense"], 1e-9)
    print(f"engine_decode_paged_vs_dense_b{batch},0,"
          f"tok_s_ratio={ratio:.2f}")
    return {"dense": rows["dense"], "paged": rows["paged"], "ratio": ratio}


def quant_decode_modes(batch: int = 4, ticks: int = 12, max_seq: int = 64,
                       modes=("bf16", "lut4", "int4", "nf4", "nf4p")) -> dict:
    """Steady-state decode tok/s per weight-quantization mode, same
    scenario (the ``quant`` section of ``BENCH_engine.json``).

    ``bf16`` is the dense baseline; ``lut4`` evaluates frozen 4-bit codes
    through the D&C sub-table LUT gemm; ``int4`` direct-dequants the same
    codes (identical tokens, conventional evaluation); ``nf4`` encodes
    against the non-affine NF4 codebook and adds the least-squares
    residual correction to the 6-select sum; ``nf4p`` prunes that residual
    sub-table (its row also reports the residual table bytes saved and the
    decode-weight MAE delta vs unpruned nf4).  Decode is memory-bound on
    real accelerators, so 4-bit weights approach a direct tok/s win there;
    CPU-interpreted numbers only track relative shape.
    """
    rows = {}
    for mode in modes:
        cfg, eng = _build(mode, batch, max_seq)
        tok_s = _steady_decode_tok_s(eng, cfg, batch, ticks, max_seq)
        rows[mode] = {"decode_tok_s": tok_s}
        print(f"engine_quant_{mode}_b{batch},{batch / max(tok_s, 1e-9) * 1e6:.0f},"
              f"tok_s={tok_s:.1f};ticks={ticks}")
    for mode in modes[1:]:
        ratio = rows[mode]["decode_tok_s"] / max(
            rows["bf16"]["decode_tok_s"], 1e-9)
        print(f"engine_quant_{mode}_vs_bf16,0,tok_s_ratio={ratio:.2f}")
    if "nf4p" in rows:
        rows["nf4p"].update(_nf4p_prune_stats())
        print(f"engine_quant_nf4p_residual_table,0,"
              f"bytes_saved={rows['nf4p']['table_bytes_saved']};"
              f"mae_delta={rows['nf4p']['mae_delta']:.4f}")
    return rows


def speculative_decode(batch: int = 4, ticks: int = 12, max_seq: int = 64,
                       spec_k: int = 4) -> dict:
    """Steady-state decode tok/s with speculative decoding vs the plain
    tick, same scenario (the ``spec`` section of ``BENCH_engine.json``).

    One row per proposer (``ngram`` prompt-lookup, ``self_lut``
    self-speculation over the pruned-LUT nf4p tree) plus the non-spec
    ``baseline``; each row reports emitted tok/s, the draft acceptance
    rate from the engine's own counters, and the ratio vs baseline.
    Prompts are periodic so prompt-lookup has material.  On a real
    accelerator the verify window amortizes weight reads over ``spec_k+1``
    positions and accepted drafts are nearly free; CPU-interpreted ratios
    only show the relative shape (``compare.check_spec_section`` gates
    presence, acceptance sanity, and a loose tok/s floor, not a CPU
    speedup)."""
    rows = {}
    cfg, eng = _build("bf16", batch, max_seq)
    base = _steady_decode_tok_s(eng, cfg, batch, ticks, max_seq,
                                periodic=True)
    rows["baseline"] = {"decode_tok_s": base}
    print(f"engine_spec_baseline_b{batch},"
          f"{batch / max(base, 1e-9) * 1e6:.0f},tok_s={base:.1f}")
    for mode in ("ngram", "self_lut"):
        cfg, eng = _build("bf16", batch, max_seq, spec=mode, spec_k=spec_k)
        tok_s = _steady_decode_tok_s(eng, cfg, batch, ticks, max_seq,
                                     periodic=True)
        m = eng.metrics
        drafted, accepted = int(m.spec_drafted), int(m.spec_accepted)
        acc = accepted / drafted if drafted else 0.0
        ratio = tok_s / max(base, 1e-9)
        rows[mode] = {"decode_tok_s": tok_s, "acceptance": acc,
                      "drafted": drafted, "accepted": accepted,
                      "tok_s_vs_baseline": ratio}
        print(f"engine_spec_{mode}_b{batch},"
              f"{batch / max(tok_s, 1e-9) * 1e6:.0f},tok_s={tok_s:.1f};"
              f"acceptance={acc:.2f};vs_baseline={ratio:.2f}")
    return rows


def _nf4p_prune_stats() -> dict:
    """Residual-table bytes saved by pruning, and the decode-weight MAE
    delta it costs vs the unpruned nf4 reconstruction (gated by
    ``compare.check_quant_section``)."""
    import jax
    import jax.numpy as jnp

    from repro.core.lut import (NF4_CODEBOOK, dc_decompose_codebook,
                                prune_residual, residual_table_bytes)
    from repro.core.quant import NF4P_PRUNE_THRESHOLD, quantize_weight
    from repro.kernels.lut_gemm.ops import quantized_matmul

    _, _, residual = dc_decompose_codebook(jnp.asarray(NF4_CODEBOOK))
    kept_idx, _ = prune_residual(residual, NF4P_PRUNE_THRESHOLD)
    dense, pruned = residual_table_bytes(int(kept_idx.shape[0]))
    w = jax.random.normal(jax.random.PRNGKey(7), (128, 64), jnp.float32)
    eye = jnp.eye(w.shape[0], dtype=jnp.float32)   # W_hat = I @ W_hat
    w_nf4 = quantized_matmul(eye, quantize_weight(w, "nf4_dc"))
    w_nf4p = quantized_matmul(
        eye, quantize_weight(w, "nf4_dc", NF4P_PRUNE_THRESHOLD))
    mae_delta = float(jnp.abs(w_nf4p - w_nf4).mean())
    return {"table_bytes_saved": dense - pruned,
            "residual_kept": int(kept_idx.shape[0]),
            "mae_delta": mae_delta}


def prefill_batched_vs_per_row(quant: str = "bf16", batch: int = 8,
                               prompt_len: int = 24, max_seq: int = 128,
                               iters: int = 3) -> dict:
    """One bucketed prefill call + slab scatter vs per-row prefill calls.

    Same prompts, same slab; per-row mode submits each request alone (the
    seed engine's strategy), batched mode admits them as one bucket.
    """
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 500, prompt_len).tolist()
               for _ in range(batch)]

    def _run(batched: bool) -> float:
        cfg, eng = _build(quant, batch, max_seq)
        vocab = cfg.vocab_size
        ps = [[t % vocab for t in p] for p in prompts]
        best = float("inf")
        for it in range(iters + 1):             # iter 0 = compile warm-up
            eng.slots = [None] * batch
            eng.active.clear()
            t0 = time.perf_counter()
            if batched:
                reqs = [Request(rid=it * batch + i, prompt=p, max_new=4)
                        for i, p in enumerate(ps)]
                eng._admit(reqs, list(range(batch)))
            else:
                for i, p in enumerate(ps):
                    assert eng.submit(
                        Request(rid=it * batch + i, prompt=p, max_new=4))
            wall = time.perf_counter() - t0
            if it > 0:
                best = min(best, wall)
        return best

    per_row = _run(batched=False)
    batched = _run(batched=True)
    speedup = per_row / max(batched, 1e-9)
    print(f"engine_prefill_per_row_b{batch},{per_row * 1e6:.0f},"
          f"len={prompt_len};quant={quant}")
    print(f"engine_prefill_batched_b{batch},{batched * 1e6:.0f},"
          f"speedup_vs_per_row={speedup:.2f}")
    return {"per_row_s": per_row, "batched_s": batched, "speedup": speedup}


def prefix_shared_system_prompt(quant: str = "bf16", n_requests: int = 6,
                                head_len: int = 64, tail_len: int = 8,
                                max_seq: int = 96) -> dict:
    """The million-user traffic shape: every request opens with the same
    system-prompt head.  Cold = every admission prefills from token 0;
    warm = the prefix cache seeds the head (transformer: copy-on-write
    paged blocks; mamba2: dense state snapshot) and prefills only the
    tail.  Reported tok/s counts the FULL prompt (reused + recomputed)
    over prefill wall-clock — the effective admission throughput.

    Acceptance gate (``benchmarks/compare.py``): warm strictly above cold.
    """
    import numpy as np

    from repro.serve.engine import Request

    out = {}
    for arch, kw in (("yi-9b", {"paged": True, "block_size": 16}),
                     ("mamba2-1.3b", {})):
        cfg, cold_eng = _build(quant, 4, max_seq, arch=arch, **kw)
        _, warm_eng = _build(quant, 4, max_seq, arch=arch,
                             prefix_cache=True, **kw)
        rng = np.random.default_rng(5)
        head = rng.integers(1, cfg.vocab_size, head_len).tolist()
        prompts = [head + rng.integers(1, cfg.vocab_size, tail_len).tolist()
                   for _ in range(n_requests)]
        # compile warm-up on a DIFFERENT head: both engines' prefill
        # programs (bucketed; staged seed + finish) get built off the clock
        wu_head = rng.integers(1, cfg.vocab_size, head_len).tolist()
        for eng in (cold_eng, warm_eng):
            for i in range(2):
                tail = rng.integers(1, cfg.vocab_size, tail_len).tolist()
                assert eng.serve([Request(rid=900 + i, prompt=wu_head + tail,
                                          max_new=1)])["done"]

        def run(eng, ps, rid0):
            tok = wall = 0.0
            hits = reused = 0
            for i, p in enumerate(ps):
                stats = eng.serve([Request(rid=rid0 + i, prompt=p,
                                           max_new=1)])
                assert stats["done"]
                wall += stats["prefill_s"]
                tok += stats["prefill_tokens"] + stats["prefix_tokens_reused"]
                hits += stats["prefix_hits"]
                reused += stats["prefix_tokens_reused"]
            return tok / max(wall, 1e-9), hits, reused

        cold_tok_s, _, _ = run(cold_eng, prompts, 0)
        # first warm-engine request populates the tree (not measured) ...
        assert warm_eng.serve([Request(rid=50, prompt=prompts[0],
                                       max_new=1)])["done"]
        # ... every following one must hit the shared head
        warm_tok_s, hits, reused = run(warm_eng, prompts[1:], 51)
        assert hits == n_requests - 1, (arch, hits)
        speedup = warm_tok_s / max(cold_tok_s, 1e-9)
        out[arch] = {"cold_prefill_tok_s": cold_tok_s,
                     "warm_prefill_tok_s": warm_tok_s,
                     "speedup": speedup,
                     "prefix_hits": hits,
                     "tokens_reused": reused}
        print(f"engine_prefix_{arch}_cold,0,prefill_tok_s={cold_tok_s:.1f};"
              f"head={head_len};quant={quant}")
        print(f"engine_prefix_{arch}_warm,0,prefill_tok_s={warm_tok_s:.1f};"
              f"speedup_vs_cold={speedup:.2f};reused={reused}")
    return out


def priority_mixed_load(quant: str = "bf16", n_each: int = 6,
                        max_seq: int = 64, max_new: int = 8,
                        max_batch: int = 2) -> dict:
    """Request-lifecycle latency under a mixed priority workload: 2*n_each
    requests (interleaved high/low priority at submission) contend for
    ``max_batch`` slots; the scheduler admits priority classes first, so
    high-priority requests should see strictly lower tail TTFT.

    Reports per-class TTFT and ITL p50/p95 (seconds) for the ``latency``
    section of ``BENCH_engine.json``.  Acceptance gate
    (``benchmarks/compare.py``): high-priority p95 TTFT < low-priority
    p95 TTFT.
    """
    import numpy as np

    from repro.serve.engine import Request

    cfg, eng = _build(quant, max_batch, max_seq)
    rng = np.random.default_rng(3)
    # compile warm-up off the clock: one bucketed prefill + decode program
    wu = [Request(rid=900 + i,
                  prompt=rng.integers(1, cfg.vocab_size, 6).tolist(),
                  max_new=2)
          for i in range(max_batch)]
    assert eng.serve(wu)["done"]

    reqs = []
    for i in range(2 * n_each):
        pri = 1 if i % 2 == 0 else 0          # interleaved arrival order
        reqs.append(Request(
            rid=i, prompt=rng.integers(1, cfg.vocab_size, 6).tolist(),
            max_new=max_new, priority=pri))
    stats = eng.serve(reqs)
    assert stats["done"]

    out = {}
    for name, pri in (("high", 1), ("low", 0)):
        sel = [r for r in reqs if r.priority == pri]
        ttft = np.asarray([r.token_ts[0] - r.submit_ts for r in sel])
        itl = np.concatenate([np.diff(np.asarray(r.token_ts))
                              for r in sel if len(r.token_ts) > 1])
        out[name] = {
            "n": len(sel),
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p95_s": float(np.percentile(ttft, 95)),
            "itl_p50_s": float(np.percentile(itl, 50)),
            "itl_p95_s": float(np.percentile(itl, 95)),
        }
        print(f"engine_latency_{name},0,"
              f"ttft_p50_ms={out[name]['ttft_p50_s'] * 1e3:.1f};"
              f"ttft_p95_ms={out[name]['ttft_p95_s'] * 1e3:.1f};"
              f"itl_p50_ms={out[name]['itl_p50_s'] * 1e3:.1f};"
              f"itl_p95_ms={out[name]['itl_p95_s'] * 1e3:.1f};quant={quant}")
    ratio = out["high"]["ttft_p95_s"] / max(out["low"]["ttft_p95_s"], 1e-9)
    print(f"engine_latency_priority_split,0,"
          f"high_vs_low_p95_ttft_ratio={ratio:.2f}")
    return out


def _admit_long_interleave(quant: str, max_seq: int, chunk: int, arch: str,
                           modes, tag: str = "") -> dict:
    """Shared harness: 3 short requests decode while one (max_seq-1)-token
    prompt is admitted; reports decode tokens emitted during the admission
    window per mode (whole-prompt admission stalls every decoder for the
    full prefill; chunked admission interleaves one chunk per tick)."""
    import numpy as np

    from repro.serve.engine import Request

    rows = {}
    for mode, kw in modes:
        cfg, eng = _build(quant, 4, max_seq, arch=arch, **kw)
        rng = np.random.default_rng(0)
        short = [Request(rid=i,
                         prompt=rng.integers(1, cfg.vocab_size, 6).tolist(),
                         max_new=max_seq)
                 for i in range(3)]
        for r in short:
            assert eng.submit(r)
        for _ in range(3):                      # warm-up/compile ticks
            eng.step()
        long = Request(rid=9,
                       prompt=rng.integers(1, cfg.vocab_size,
                                           max_seq - 1).tolist(),
                       max_new=4)
        emitted0 = sum(len(r.out) for r in short)
        t0 = time.perf_counter()
        assert eng.submit(long)                 # whole mode prefills HERE
        while not long.out:                     # chunked mode: tick it in
            eng.step()
        wall = time.perf_counter() - t0
        during = sum(len(r.out) for r in short) - emitted0
        rows[mode] = during
        print(f"engine_admit_long_{tag}{mode},{wall * 1e6:.0f},"
              f"decode_toks_during_admission={during};len={max_seq - 1};"
              f"chunk={0 if mode == 'whole' else chunk}")
    return rows


def long_prompt_interleave(quant: str = "bf16", max_seq: int = 128,
                           chunk: int = 16) -> dict:
    """Attention-family long-admission interleave (yi-9b): whole vs
    chunked prefill."""
    return _admit_long_interleave(
        quant, max_seq, chunk, "yi-9b",
        [("whole", {}), ("chunked", {"prefill_chunk": chunk})])


def recurrent_long_prompt_interleave(quant: str = "bf16", max_seq: int = 64,
                                     chunk: int = 16,
                                     archs=("mamba2-1.3b", "zamba2-1.2b")
                                     ) -> dict:
    """The recurrent-family spelling of :func:`long_prompt_interleave`:
    chunked admission resumes the state-continuing SSD scan one chunk per
    tick; the hybrid additionally runs its attention leaves in the paged
    block pool (split substrate)."""
    out = {}
    for arch in archs:
        modes = [("whole", {}), ("chunked", {"prefill_chunk": chunk})]
        if arch == "zamba2-1.2b":
            modes.append(("paged_chunked",
                          {"prefill_chunk": chunk, "paged": True,
                           "block_size": 16}))
        out[arch] = _admit_long_interleave(quant, max_seq, chunk, arch,
                                           modes, tag=f"{arch}_")
    return out


def observability_overhead(quant: str = "bf16", batch: int = 4,
                           ticks: int = 30, repeats: int = 5,
                           max_seq: int = 512,
                           trace_path: str | None = None,
                           metrics_path: str | None = None) -> dict:
    """Recording overhead + trace consistency: the ``observability``
    section of ``BENCH_engine.json``.

    Overhead: ONE engine, slots filled with never-finishing requests,
    decode tok/s measured with the tracer toggled off/on in interleaved
    repeats (same compiled programs, same thermal window — tok/s is
    computed from the MEDIAN per-tick wall time over all repeats, so a
    multi-ms scheduler hiccup inside one window can't bias a mode, and
    the off/on order flips every repeat so monotonic frequency drift
    can't either).  The registry
    observations are always on; the delta isolates trace-event
    recording.  Gate (``compare.check_observability_section``): on/off
    ratio >= 0.97.

    Consistency: a second engine on a virtual clock serves a small mix
    with tracing on; event counts must reconcile with token counts
    (first_token + token events == tokens emitted, one submit and one
    finish per request).  Optionally dumps that run's Perfetto trace and
    Prometheus text to ``trace_path`` / ``metrics_path`` (CI artifacts).
    """
    import numpy as np

    from repro.serve.engine import Request

    cfg, eng = _build(quant, batch, max_seq)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 6).tolist(),
                    max_new=max_seq)           # never finishes mid-bench
            for i in range(batch)]
    for i, r in enumerate(reqs):
        assert eng.submit(r), i
    for _ in range(3):                          # warm-up (compile) ticks
        eng.step()

    def measure() -> list[float]:
        out = []
        for _ in range(ticks):
            t0 = time.perf_counter()
            eng.step()
            out.append(time.perf_counter() - t0)
        return out

    # per-TICK samples, pooled across alternating off/on windows: the
    # median over repeats*ticks samples shrugs off multi-ms scheduler
    # hiccups that bias any whole-window estimator (best-of included)
    samples = {"off": [], "on": []}
    for rep in range(repeats):
        order = ("off", "on") if rep % 2 == 0 else ("on", "off")
        for mode in order:
            eng.tracer.enabled = mode == "on"
            samples[mode].extend(measure())
    eng.tracer.enabled = False

    def tok_s(mode: str) -> float:
        ts = sorted(samples[mode])
        return batch / max(ts[len(ts) // 2], 1e-9)   # median tick time

    best = {m: tok_s(m) for m in ("off", "on")}
    ratio = best["on"] / max(best["off"], 1e-9)
    print(f"engine_obs_overhead_b{batch},0,"
          f"decode_tok_s_off={best['off']:.1f};"
          f"decode_tok_s_on={best['on']:.1f};ratio={ratio:.2f}")

    from benchmarks.load_harness import VirtualClock

    cfg2, eng2 = _build(quant, batch, 64, clock=VirtualClock(), trace=True)
    rng = np.random.default_rng(4)
    reqs2 = [Request(rid=i,
                     prompt=rng.integers(
                         1, cfg2.vocab_size,
                         int(rng.integers(3, 12))).tolist(),
                     max_new=4)
             for i in range(2 * batch)]
    stats = eng2.serve(reqs2)
    assert stats["done"], stats
    emitted = sum(len(r.out) for r in reqs2)
    names: dict[str, int] = {}
    for e in eng2.tracer.events():
        if e.rid is not None:
            names[e.name] = names.get(e.name, 0) + 1
    if trace_path:
        from repro.obs import dump_trace
        dump_trace(eng2.tracer, trace_path)
    if metrics_path:
        from repro.obs import dump_metrics
        dump_metrics(eng2.registry, metrics_path)
    trace_sec = {
        "requests": len(reqs2),
        "emitted_tokens": emitted,
        "submit_events": names.get("submit", 0),
        "admit_events": names.get("admit", 0),
        "first_token_events": names.get("first_token", 0),
        "token_events": names.get("token", 0),
        "finish_events": names.get("finish", 0),
        "events_total": len(eng2.tracer.events()),
        "dropped": eng2.tracer.dropped,
    }
    print(f"engine_obs_trace,0,requests={trace_sec['requests']};"
          f"emitted={emitted};"
          f"token_events={trace_sec['first_token_events'] + trace_sec['token_events']};"
          f"finish={trace_sec['finish_events']}")
    return {"decode_tok_s_off": best["off"],
            "decode_tok_s_on": best["on"],
            "overhead_ratio": ratio,
            "ticks": ticks, "repeats": repeats,
            "trace": trace_sec}


def bench_json(path: str = "BENCH_engine.json", batches=DEF_BATCHES,
               ticks: int = 6, max_seq: int = 64,
               quant: str = "bf16") -> dict:
    """Machine-readable engine numbers for the perf trajectory: decode
    tok/s, prefill tok/s and occupancy per slab width, via a short serve()
    of 2*mb mixed-length requests after a steady-state decode measurement;
    plus a ``recurrent`` section — ssm/hybrid engines serving a
    long-prompt-interleave mix under chunked prefill (the hybrid with paged
    attention pools) — a ``prefix`` section — the shared-system-prompt
    scenario, whose warm-vs-cold prefill win ``benchmarks/compare.py``
    additionally gates in CI — a ``latency`` section — per-priority
    TTFT/ITL p50/p95 from the mixed-load scenario, gated on high-priority
    p95 TTFT beating low — and a ``quant`` section — decode tok/s for
    bf16 vs the frozen-4-bit lut4/int4 decode paths on one scenario,
    whose presence (all three rows) ``compare.py`` also gates — a
    ``spec`` section — speculative decoding (baseline vs ngram vs
    self_lut on periodic prompts: acceptance rate, drafted/accepted
    counts, effective tok/s vs baseline), gated by
    ``compare.check_spec_section`` — and an ``observability`` section — tracing-on vs tracing-off decode tok/s
    (gated at ratio >= 0.97) plus trace event counts reconciled against
    token counts; its consistency run's Perfetto trace and Prometheus
    dump land in ``TRACE_engine.json`` / ``METRICS_engine.prom``.
    """
    import numpy as np

    from repro.serve.engine import Request

    out = {"model_quant": quant, "max_seq": max_seq, "ticks": ticks,
           "per_batch": {}, "recurrent": {}, "prefix": {}, "latency": {},
           "quant": {}, "spec": {}}
    for mb in batches:
        cfg, eng = _build(quant, mb, max_seq)
        decode_tok_s = _steady_decode_tok_s(eng, cfg, mb, ticks, max_seq)
        cfg, eng = _build(quant, mb, max_seq)
        rng = np.random.default_rng(1)
        reqs = [Request(rid=i,
                        prompt=rng.integers(
                            1, cfg.vocab_size,
                            int(rng.integers(3, 12))).tolist(),
                        max_new=6)
                for i in range(2 * mb)]
        stats = eng.serve(reqs)
        out["per_batch"][str(mb)] = {
            "decode_tok_s": decode_tok_s,
            "prefill_tok_s": stats["prefill_tok_s"],
            "occupancy": stats["occupancy"],
        }
        print(f"engine_json_b{mb},0,decode_tok_s={decode_tok_s:.1f};"
              f"prefill_tok_s={stats['prefill_tok_s']:.1f};"
              f"occupancy={stats['occupancy']:.2f}")
    for arch, kw in (("mamba2-1.3b", {"prefill_chunk": 16}),
                     ("zamba2-1.2b", {"prefill_chunk": 16, "paged": True,
                                      "block_size": 16})):
        cfg, eng = _build(quant, 4, max_seq, arch=arch, **kw)
        rng = np.random.default_rng(2)
        reqs = [Request(rid=i,
                        prompt=rng.integers(
                            1, cfg.vocab_size,
                            int(rng.integers(3, max_seq - 2))).tolist(),
                        max_new=6)
                for i in range(8)]              # mixes whole + chunked
        stats = eng.serve(reqs)
        assert stats["done"] and stats["prefill_chunks"] > 0
        out["recurrent"][arch] = {
            "decode_tok_s": stats["decode_tok_s"],
            "prefill_tok_s": stats["prefill_tok_s"],
            "occupancy": stats["occupancy"],
        }
        print(f"engine_json_recurrent_{arch},0,"
              f"decode_tok_s={stats['decode_tok_s']:.1f};"
              f"prefill_tok_s={stats['prefill_tok_s']:.1f};"
              f"chunks={stats['prefill_chunks']}")
    out["prefix"] = prefix_shared_system_prompt(quant=quant)
    out["latency"] = priority_mixed_load(quant=quant)
    out["quant"] = quant_decode_modes(batch=4, ticks=ticks, max_seq=max_seq)
    out["spec"] = speculative_decode(batch=4, ticks=ticks, max_seq=max_seq)
    out["sustained"] = sustained_load()
    out["observability"] = observability_overhead(
        quant=quant, trace_path="TRACE_engine.json",
        metrics_path="METRICS_engine.prom")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"engine_json,0,wrote={path}")
    return out


def sustained_load(report_path: str = "LOAD_harness.json") -> dict:
    """Sustained-load section: deterministic virtual-time overload runs
    from the trace harness (Poisson arrivals, mixed priorities + deadline
    budgets, arrival rate far above service capacity) — goodput,
    deadline-miss rate, and per-priority TTFT/ITL percentiles are
    bit-stable, so `compare.py` gates them.  A short REAL background-loop
    run (threaded clients against `engine.start()`) rides along as the
    loop-integration smoke and lands in the detailed report written to
    ``report_path`` (the CI artifact)."""
    from benchmarks.load_harness import (build_engine, make_trace,
                                         run_threaded, sustained_report)

    out = sustained_report()
    # the same overload trace with speculative decoding on: priority
    # split and positive goodput must survive draft/verify/rollback
    out.update(sustained_report(arches=("yi-9b",), spec="ngram"))
    for arch, rep in out.items():
        print(f"engine_json_sustained_{arch},0,"
              f"goodput_tok_s={rep['goodput_tok_s']:.1f};"
              f"miss_rate={rep['deadline_miss_rate']:.2f};"
              f"ttft_p99_hi={rep['by_priority']['1']['ttft']['p99_s']:.3f};"
              f"ttft_p99_lo={rep['by_priority']['0']['ttft']['p99_s']:.3f}")
    eng, cfg = build_engine("yi-9b")
    trace = make_trace(16, 200.0, cfg.vocab_size, seed=1,
                       deadline_budgets={0: None, 1: None})
    smoke_rep = run_threaded(eng, trace, time_scale=0.01)
    assert smoke_rep["finished"] == smoke_rep["submitted"], smoke_rep
    assert smoke_rep["goodput_tok_s"] > 0, smoke_rep
    print(f"engine_json_sustained_loop_smoke,0,"
          f"finished={smoke_rep['finished']};"
          f"goodput_tok_s={smoke_rep['goodput_tok_s']:.1f}")
    with open(report_path, "w") as f:
        json.dump({"virtual": out, "threaded_smoke": smoke_rep}, f,
                  indent=2, sort_keys=True)
    # the gated section keeps only the deterministic virtual-time numbers
    # (wall-clock from the threaded smoke would flap the baseline)
    return out


def smoke() -> None:
    """Tiny CI-sized run: decode at b in (1, 4), prefill comparison, paged
    parity and the long-prompt interleaves (attention AND recurrent
    families) at reduced sizes."""
    decode_throughput(batches=(1, 4), ticks=6, max_seq=64)
    prefill_batched_vs_per_row(batch=4, prompt_len=12, max_seq=64, iters=1)
    decode_paged_vs_dense(batch=4, ticks=6, max_seq=64)
    long_prompt_interleave(max_seq=64, chunk=16)
    recurrent_long_prompt_interleave(max_seq=48, chunk=16,
                                     archs=("mamba2-1.3b",))


ALL = [decode_throughput, decode_paged_vs_dense, prefill_batched_vs_per_row,
       long_prompt_interleave, recurrent_long_prompt_interleave,
       prefix_shared_system_prompt, priority_mixed_load, quant_decode_modes,
       speculative_decode, observability_overhead]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quant", default="bf16",
                    help="bf16, lut4/int4 (engine-level frozen decode "
                         "weights) or a model-level mode (e.g. luna_approx)")
    ap.add_argument("--batches", type=int, nargs="+",
                    default=list(DEF_BATCHES))
    ap.add_argument("--ticks", type=int, default=24)
    ap.add_argument("--prefill-batch", type=int, default=8)
    ap.add_argument("--json", default=None,
                    help="also write BENCH_engine.json-style output here")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    if args.smoke:
        smoke()
        if args.json:
            bench_json(args.json)
        return
    ok = True
    decode_throughput(args.quant, tuple(args.batches), args.ticks)
    pd = decode_paged_vs_dense(args.quant, batch=8, ticks=args.ticks)
    if pd["ratio"] < 0.6:        # CPU timing is noisy; gate gross regressions
        print(f"engine_paged_regression,FAIL,"
              f"paged_much_slower_than_dense={pd['ratio']:.2f}")
        ok = False
    res = prefill_batched_vs_per_row(args.quant, args.prefill_batch)
    long_prompt_interleave(quant=args.quant)
    recurrent_long_prompt_interleave(quant=args.quant)
    if args.json:
        bench_json(args.json, quant=args.quant)
    if res["speedup"] <= 1.0:
        print(f"engine_prefill_regression,FAIL,"
              f"batched_slower_than_per_row={res['speedup']:.2f}")
        ok = False
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
