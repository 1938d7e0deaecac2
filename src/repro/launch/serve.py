"""Serving CLI: batched requests against any assigned arch (reduced or full).

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b \
      --quant luna_approx --requests 8 --sampling top_k --top-k 40

  # published widths (the default is the reduced smoke size); on a TPU:
  PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b \
      --no-reduced --paged --max-seq 256

  # LUT-quantized decode hot path (engine-level, D&C sub-table gemm):
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --quant lut4

  # non-affine NF4 decode (D&C + residual correction; nf4p = pruned):
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --quant nf4

  # speculative decoding (greedy-only; see docs/speculative.md):
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --spec ngram
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b \
      --spec self_lut --spec-k 4     # nf4p LUT drafts, full-prec verify

Engine knobs are single-sourced in ``repro.serve.config.EngineConfig`` —
``EngineConfig.add_cli_args`` registers the flags (including the shared
``--quant``), ``from_args`` builds the validated config.  ``--quant
lut4|int4|nf4|nf4p`` freezes 4-bit decode weights on the engine (affine
grid or NF4 codebook with full/pruned residual correction — see
docs/quantization.md); any other spelling (bf16, int8, luna_*, ...) is a
model-level mode applied to every projection dynamically.

The CLI serves from the BACKGROUND LOOP by default (``engine.start()``,
one ``submit()`` per request, streams consumed off the loop thread,
``engine.stop()`` drains) — the same path a network front-end would use.
``--sync`` keeps the old caller-pumped ``engine.serve(requests)`` path.
The whole path — config, parameters built on the device by a jitted
``model.init``, engine, serving — is :func:`serve`, which ``chip_smoke.py``
drives too.

Observability (see docs/observability.md): ``--metrics-port`` serves the
engine's metrics registry as a Prometheus scrape endpoint while the run
lasts, ``--metrics-dump PATH`` writes the text exposition on exit, and
``--trace-out PATH`` records request-lifecycle spans and writes Perfetto
JSON on exit (open at https://ui.perfetto.dev).
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass


@dataclass
class ServeRun:
    """What :func:`serve` built and what it measured."""
    engine: object
    streams: list        # per request, the tokens its handle streamed
    stats: dict          # engine summary + ``wall_s`` and ``done``
    setup_s: float       # parameter init (if built here) + engine build


def init_params(cfg, seed: int = 0):
    """Random parameters made on the device by one jitted init (no host
    copy of the full-width tree)."""
    import jax

    from repro.models.registry import get_model
    return jax.jit(get_model(cfg).init)(jax.random.PRNGKey(seed))


def serve(cfg, engine_config, requests, *, params=None, sync: bool = False,
          metrics_port: int | None = None) -> ServeRun:
    """Build params (unless given: :func:`init_params`) and an engine,
    then serve ``requests``.

    By default through the background loop — ``engine.start()``, one
    ``submit()`` per request, each handle's ``tokens()`` drained on its
    own client thread, ``engine.stop()`` — the path a network front end
    uses; ``sync=True`` pumps ``engine.serve(requests)`` instead (streams
    are then the final outputs).  ``metrics_port`` serves the engine's
    metrics registry while the requests run.
    """
    import jax

    from repro.serve.engine import Engine

    t0 = time.perf_counter()
    if params is None:
        params = init_params(cfg)
    engine = Engine(cfg, params, engine_config)
    jax.block_until_ready(engine.decode_params)
    setup_s = time.perf_counter() - t0

    metrics_server = None
    if metrics_port is not None:
        from repro.obs import start_metrics_server
        metrics_server = start_metrics_server(engine.registry, metrics_port)
        print(f"metrics: http://127.0.0.1:"
              f"{metrics_server.server_address[1]}/metrics")
    try:
        if sync:
            stats = engine.serve(requests)
            streams = [list(r.out) for r in requests]
        else:
            from concurrent.futures import ThreadPoolExecutor

            start = engine.metrics.snapshot()
            t1 = engine.clock()
            engine.start()
            try:
                handles = [engine.submit(r) for r in requests]
                with ThreadPoolExecutor(
                        max_workers=min(8, len(handles))) as pool:
                    streams = list(pool.map(lambda h: list(h.tokens()),
                                            handles))
            finally:
                engine.stop()
            stats = engine.metrics.since(start).summary(engine.max_batch)
            stats.update({"wall_s": engine.clock() - t1,
                          "done": all(r.done for r in requests)})
    finally:
        if metrics_server is not None:
            metrics_server.shutdown()
    return ServeRun(engine, streams, stats, setup_s)


def main():
    from repro.serve.config import EngineConfig

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-size config (default); --no-reduced serves "
                         "the published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--sync", action="store_true",
                    help="caller-pumped engine.serve() instead of the "
                         "background serve loop")
    EngineConfig.add_cli_args(ap)
    ap.set_defaults(max_batch=4, max_seq=128, quant="bf16")
    args = ap.parse_args()

    from dataclasses import replace

    import numpy as np

    from repro.core.layers import QuantConfig
    from repro.launch.cache import enable_compile_cache
    from repro.models.registry import get_config
    from repro.serve.config import ENGINE_QUANT_MODES
    from repro.serve.engine import Request

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.quant not in ("bf16", *ENGINE_QUANT_MODES):
        cfg = replace(cfg, quant=QuantConfig(mode=args.quant))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 6).tolist(),
                    max_new=args.max_new)
            for i in range(args.requests)]
    run = serve(cfg, EngineConfig.from_args(args), reqs, sync=args.sync,
                metrics_port=args.metrics_port)
    engine, stats = run.engine, run.stats
    for r, s in zip(reqs, run.streams):
        if s != r.out:
            raise RuntimeError(f"rid {r.rid}: stream diverged from out")
    tok_count = sum(len(r.out) for r in reqs)
    print(f"{tok_count} tokens over {len(reqs)} requests: "
          f"{stats['wall_s']:.2f}s wall, done={stats['done']} "
          f"(set-up {run.setup_s:.2f}s)")
    print(f"  prefill: {stats['prefill_tokens']} tok in "
          f"{stats['prefill_s']:.2f}s ({stats['prefill_tok_s']:.0f} tok/s, "
          f"{stats['prefill_calls']} bucket calls)")
    print(f"  decode:  {stats['decode_tokens']} tok in "
          f"{stats['decode_s']:.2f}s ({stats['decode_tok_s']:.0f} tok/s, "
          f"occupancy {stats['occupancy']:.0%})")
    if args.prefix_cache:
        print(f"  prefix:  {stats['prefix_hits']} hits, "
              f"{stats['prefix_tokens_reused']} tok reused, "
              f"{stats['cache_evictions']} evictions")
    # the rest of the summary: lifecycle + deadline accounting (zeros on
    # an ordinary run, but dropping them silently hid every non-zero one)
    print(f"  lifecycle: {stats['cancelled']} cancelled, "
          f"{stats['preemptions']} preempted")
    print(f"  deadlines: {stats['deadline_hits']} hit, "
          f"{stats['deadline_misses']} missed")
    if args.metrics_dump:
        from repro.obs import dump_metrics
        dump_metrics(engine.registry, args.metrics_dump)
        print(f"metrics dump: {args.metrics_dump}")
    if args.trace_out:
        from repro.obs import dump_trace
        dump_trace(engine.tracer, args.trace_out)
        print(f"trace: {args.trace_out} "
              f"({len(engine.tracer.events())} events, "
              f"{engine.tracer.dropped} dropped)")


if __name__ == "__main__":
    main()
