"""Serving smoke test on one TPU chip: the quickest proof that the engine
still starts on the chip.

    python chip_smoke.py

Serves zamba2-1.2b at its published widths (random weights from a seed)
through the engine's background loop — ``engine.start()``, ``submit()``,
each handle's ``tokens()``, ``engine.stop()`` — with
``EngineConfig(max_batch=4, max_seq=256, paged=True)``: four greedy
requests whose prompts share one prefill bucket, ``max_new=16``.  Then the
same requests again on a second engine with ``quant="nf4"`` (the frozen
4-bit LUT decode path) over the same parameters.

Checks, all of which must hold:

* every request finishes with exactly ``max_new`` tokens, all in
  ``[0, vocab)``;
* each handle's stream equals ``req.out``;
* each request's first token is the same under bf16 and nf4 (prefill runs
  the full-precision tree in both).

It exits 1 without a result when JAX finds no TPU, or when the ``repro``
package is not beside this file; a failed check raises.  The last line of
standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The seconds printed before it are set-up and smoke wall time, compilation
included — not performance results.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "zamba2-1.2b"
MAX_BATCH = 4
MAX_SEQ = 256
MAX_NEW = 16
PROMPT_LENS = (10, 12, 14, 16)      # all in the one 16-token prefill bucket


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def _import_repro():
    """Import ``repro`` from ``src/`` beside this file, and only there."""
    src = ROOT / "src"
    sys.path.insert(0, str(src))
    try:
        import repro
    except ImportError as e:
        raise SmokeFailure(f"no repro package under {src}: {e}") from e
    if Path(repro.__file__).resolve().parents[1] != src:
        raise SmokeFailure(f"repro was imported from {repro.__file__}, "
                           f"not from {src}")


def make_requests(cfg, seed: int = 0):
    """Fresh greedy requests, one per prompt length, from ``seed``."""
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
                    max_new=MAX_NEW)
            for i, n in enumerate(PROMPT_LENS)]


def check_run(label: str, reqs, streams, vocab: int, max_new: int = MAX_NEW):
    """Raise :class:`SmokeFailure` unless every request finished with
    exactly ``max_new`` in-vocabulary tokens and streamed its output."""
    if len(streams) != len(reqs):
        raise SmokeFailure(f"{label}: {len(streams)} streams for "
                           f"{len(reqs)} requests")
    for r, s in zip(reqs, streams):
        if not r.done or r.cancelled:
            raise SmokeFailure(f"{label} rid {r.rid}: not finished")
        if len(r.out) != max_new:
            raise SmokeFailure(f"{label} rid {r.rid}: {len(r.out)} tokens, "
                               f"expected {max_new}")
        bad = [t for t in r.out if not 0 <= t < vocab]
        if bad:
            raise SmokeFailure(f"{label} rid {r.rid}: tokens {bad} outside "
                               f"[0, {vocab})")
        if list(s) != list(r.out):
            raise SmokeFailure(f"{label} rid {r.rid}: stream {s} != "
                               f"out {r.out}")


def _peak_bytes():
    """The device's peak bytes in use so far (None where not reported)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def serve_and_check(cfg, *, seed: int = 0, log=print) -> dict:
    """Serve the smoke's requests under bf16 and nf4 on one parameter tree
    and run every check; returns what was measured."""
    import jax

    from repro.launch.serve import init_params, serve
    from repro.serve.config import EngineConfig

    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, seed))
    report = {"init_s": time.perf_counter() - t0,
              "param_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
              "phases": {}}
    log(f"parameters: {report['param_bytes']} bytes, initialised on the "
        f"device in {report['init_s']:.2f} s")
    first = {}
    for quant in (None, "nf4"):
        label = quant or "bf16"
        reqs = make_requests(cfg, seed)
        conf = EngineConfig(max_batch=MAX_BATCH, max_seq=MAX_SEQ, paged=True,
                            quant=quant)
        run = serve(cfg, conf, reqs, params=params)
        check_run(label, reqs, run.streams, cfg.vocab_size)
        first[label] = [r.out[0] for r in reqs]
        peak = _peak_bytes()
        report["phases"][label] = {"setup_s": run.setup_s,
                                   "wall_s": run.stats["wall_s"],
                                   "peak_bytes": peak,
                                   "tokens": [list(r.out) for r in reqs]}
        log(f"{label}: engine set-up {run.setup_s:.2f} s, smoke wall "
            f"{run.stats['wall_s']:.2f} s for {len(reqs)} x {MAX_NEW} "
            f"tokens, of which prefill calls {run.stats['prefill_s']:.2f} s "
            f"and decode calls {run.stats['decode_s']:.2f} s (compilation "
            f"included; not a performance result); peak device bytes so "
            f"far {peak}")
        del run
    if first["bf16"] != first["nf4"]:
        raise SmokeFailure(f"first tokens differ: bf16 {first['bf16']} vs "
                           f"nf4 {first['nf4']}")
    return report


def main() -> int:
    try:
        _import_repro()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1

    from repro.launch.cache import enable_compile_cache
    from repro.models.registry import get_config

    print(f"device_kind: {dev.device_kind} (x{len(devices)})")
    print(f"compile cache: {enable_compile_cache()}")
    serve_and_check(get_config(ARCH))
    print(f"peak_bytes_in_use: {_peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
