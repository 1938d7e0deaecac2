"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--control 1]

One process holds the chip: it makes the weights from the seed on the
device, builds the engine (``repro.serve.engine.Engine``), warms every
program shape the cell's traffic uses, then drives the engine's
background loop for ``--seconds`` with the cell's traffic from client
threads.  Nothing here knows a model family: the configuration file
names the module of its plain reference (``bench/reference/<name>.py``,
``Reference``) and of its decode step's work count
(``bench/work/<name>.py``, ``decode_step``), and ``bench/cell.py`` finds
both under the checkout and hands them over on the ``Cell``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``, each number compared beside its
limit (also the last lines of standard error).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the first
``trace_seconds`` of the window (written under ``.bench_traces/`` and
deleted once read).
``--control 1`` puts the control (the reference in float8) in the
program's place: ``correct`` is decided from the control's widest gap
over the same sample, and so comes out false.  The program's own widest
gap is printed beside it.  The benchmark's own runs do not pass it.

Exits 3 with no result when JAX finds no TPU or fewer chips than the
cell asks for, and 2 when the cell, its files, the modules its
configuration names or the ``repro`` package beside ``bench/`` cannot be
found.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is measured from here

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, replace  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

from cell import CellError, load_cell, load_module  # noqa: E402
from e2e import itl_values, p95, tokens_in_window, ttft_values  # noqa: E402

#: the persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: profiler traces, read and then deleted (one can take tens of MB)
TRACE_DIR = ROOT / ".bench_traces"
RID_WARM = 1 << 30


class NoDevice(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class Unavailable(RuntimeError):
    """The program under test is not beside the benchmark."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def import_repro():
    """Import ``repro`` from ``src/`` beside ``bench/``, and only there."""
    src = ROOT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    try:
        import repro
    except ImportError as e:
        raise Unavailable(f"no repro package under {src}: {e}") from e
    if Path(repro.__file__).resolve().parents[1] != src:
        raise Unavailable(f"repro imported from {repro.__file__}, not {src}")
    return repro


def set_up_jax():
    """Compilation cache at the checkout's fixed path; every program is
    kept, so a second run in the checkout compiles nothing."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs


def model_config(config: dict):
    """The registry's configuration of ``config["arch"]`` with every size
    in the configuration file's ``model`` section applied."""
    from repro.models.registry import get_config
    cfg = get_config(config["arch"])
    over = {}
    for k, v in config["model"].items():
        cur = getattr(cfg, k)
        over[k] = replace(cur, **v) if isinstance(v, dict) else v
    return replace(cfg, **over)


def reference_dims(config: dict, cfg) -> dict:
    return dict(config["model"], family=cfg.family)


def compile_counter():
    """Counts programs traced or compiled while ``counting[0]`` is set."""
    import jax
    counting, count = [False], [0]
    events = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def listener(name, _secs, **_kw):
        if counting[0] and name in events:
            count[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    return counting, count


def warm_up(engine, Request, cell, vocab: int):
    """Run every program shape the cell's traffic uses, and no other:
    the bucketed prefill of each admission count it can see (open loop:
    1 to max_batch; closed loop: 1), the chunked prefill of a prompt
    longer than one chunk, and the decode step."""
    s = cell.config["serving"]
    bucket, chunk = s["prefill_bucket"], s.get("prefill_chunk")
    counts = (range(1, s["max_batch"] + 1)
              if cell.traffic["loop"] == "open" else (1,))
    rid = RID_WARM
    rng = np.random.default_rng(0)
    for k in counts:
        reqs = [Request(rid=rid + i,
                        prompt=rng.integers(1, vocab, bucket).tolist(),
                        max_new=2) for i in range(k)]
        rid += k
        t0 = time.perf_counter()
        engine.serve(reqs)
        log(f"warm-up: prefill of {k} x {bucket} and decode, "
            f"{time.perf_counter() - t0:.2f} s")
    if chunk is not None and cell.traffic["prompt"]["max"] > chunk:
        engine.serve([Request(rid=rid,
                              prompt=rng.integers(1, vocab,
                                                  chunk + 1).tolist(),
                              max_new=2)])


@dataclass
class TracedContext:
    """What a per-layer reader reads (``bench/metrics/*.py``)."""
    trace: object             # trace_reduce.Reduced
    engine: dict              # engine counter deltas over the traced part
    max_batch: int
    span: tuple               # (start, end) of the traced part, host clock
    events: list              # the engine tracer's events of the requests
    records: list             # e2e.Record per request the clients sent
    decode_step_work: object  # work.Work of the mean decode step, or None
    decode_bound: str | None
    peaks: object


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no reader {path} for per-layer metric {name!r}")
    return load_module(path)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             control: bool = False, check_device: bool = True,
             t_start: float = T_START) -> tuple[dict, list[str]]:
    """Run one cell; returns the result line and the lines to print
    before it.  ``check_device=False`` skips the look for a chip (tests
    on the CPU)."""
    import jax

    from repro.models.registry import get_model
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Engine, Request

    import check
    import clients
    import load
    import weights
    from peaks import peaks_for

    devs = require_devices(cell.chips) if check_device else jax.devices()
    notes = []
    cfg = model_config(cell.config)
    vocab = cfg.vocab_size
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    params = jax.block_until_ready(weights.make_params(shapes, seed))
    serving = dict(cell.config["serving"])
    engine = Engine(cfg, params, EngineConfig(
        **serving, trace=trace, trace_buffer=1 << 20))
    jax.block_until_ready(engine.decode_params)
    warm_up(engine, Request, cell, vocab)
    counting, compiles = compile_counter()
    peaks = peaks_for(devs[0].device_kind) if check_device else None

    def make_request(rec):
        return Request(rid=rec.rid, prompt=list(rec.prompt),
                       max_new=rec.max_new)

    traffic = cell.traffic
    trace_s = min(float(traffic.get("trace_seconds", seconds)), seconds)
    state = {}

    def before_start():
        # before the engine's loop thread starts, so that the profiler's
        # Python tracer follows that thread too
        TRACE_DIR.mkdir(parents=True, exist_ok=True)
        state["trace_dir"] = str(TRACE_DIR / f"{cell.name}.{seed}")
        shutil.rmtree(state["trace_dir"], ignore_errors=True)
        jax.profiler.start_trace(state["trace_dir"])

    def on_open():
        counting[0] = True
        state["setup_s"] = time.perf_counter() - t_start
        if trace:
            state["window"] = jax.profiler.TraceAnnotation("bench_window")
            state["window"].__enter__()
            state["m0"] = engine.metrics.snapshot()
            state["t_trace0"] = time.perf_counter()

    def on_trace_end():
        state["t_trace1"] = time.perf_counter()
        state["m1"] = engine.metrics.snapshot()
        state["window"].__exit__(None, None, None)
        jax.profiler.stop_trace()

    hooks = dict(before_start=before_start if trace else None,
                 on_open=on_open,
                 on_trace_end=on_trace_end if trace else None,
                 trace_s=trace_s)
    if traffic["loop"] == "open":
        items = load.open_schedule(traffic, seconds, seed, vocab)
        win = clients.run_open(engine, make_request, items, seconds,
                               drain_s=float(traffic.get("drain_seconds",
                                                         60)), **hooks)
    else:
        win = clients.run_closed(engine, make_request, traffic, seed, vocab,
                                 seconds, **hooks)
    counting[0] = False
    t_stopped = time.perf_counter()
    recs = win.records
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    # what the deployment holds once its loop has stopped: weights, both
    # trees, cache pool and state, and no step's temporaries
    gc.collect()
    resident = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in devs[:cell.chips])

    # --- what the clients saw -------------------------------------------
    max_seq = serving["max_seq"]
    failed, mismatched = 0, 0
    for r in recs:
        expect = min(r.max_new, max_seq - len(r.prompt))
        short = r.done and not r.cancelled and len(r.tokens) < expect
        if r.error is not None or short or (
                traffic["loop"] == "open" and r.cancelled):
            failed += 1
        if r.engine_out is not None and r.engine_out != r.tokens:
            mismatched += 1
    in_window = [r for r in recs if r.due <= win.t_close]
    lat = np.asarray(win.lateness) * 1e3 if win.lateness else None
    notes.append(f"compilations inside the window: {compiles[0]}")
    notes.append(f"set-up: {state['setup_s']:.3f} s")
    if lat is not None:
        notes.append(f"load generator lateness: p50 "
                     f"{float(np.median(lat)):.3f} ms, max "
                     f"{float(lat.max()):.3f} ms")
    else:
        notes.append("load generator lateness: none (closed loop)")
    notes.append(f"requests sent {len(recs)}, succeeded "
                 f"{len(recs) - failed}, failed {failed}")
    notes.append(f"window close to loop stopped: "
                 f"{t_stopped - win.t_close:.1f} s")

    metrics = {}
    if not trace:
        values = {
            "ttft_p95_ms": lambda: 1e3 * p95(ttft_values(
                in_window, win.drain_deadline)),
            "itl_p95_ms": lambda: 1e3 * p95(itl_values(
                recs, win.t_open, win.t_close)),
            "tokens_per_s": lambda: tokens_in_window(
                recs, win.t_open, win.t_close) / seconds,
            "hbm_resident_gb": lambda: resident / 1e9,
            "setup_s": lambda: state["setup_s"],
        }
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise CellError(f"no arithmetic for end-to-end metric "
                                f"{m['name']!r}")
            metrics[m["name"]] = {"value": float(values[m["name"]]()),
                                  "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        metrics, breakdown, extra = traced_metrics(
            cell, state, engine, recs, cfg, peaks, serving)
        device.update(extra)

    # --- correct ---------------------------------------------------------
    ck = cell.config["check"]
    chosen = check.sample(recs, seed, ck["sample_tokens"],
                          ck["max_requests"])
    del engine
    gc.collect()
    dims = reference_dims(cell.config, cfg)
    t_ref = time.perf_counter()
    ref = cell.Reference(dims, params, seq_len=max_seq,
                         decode_nf4=cell.config.get("decode_nf4", ()))
    gaps = check.served_gaps(ref, chosen)
    # no served token to check reads 0 here and fails served_tokens_checked
    widest = float(gaps.max()) if gaps.size else 0.0
    notes.append(f"reference: {len(chosen)} requests, {gaps.size} served "
                 f"tokens, {time.perf_counter() - t_ref:.1f} s")
    if control:
        # the control in the program's place: its widest gap is compared
        low = cell.Reference(dims, params, seq_len=max_seq,
                             decode_nf4=cell.config.get("decode_nf4", ()),
                             low_precision=True)
        cg = check.control_gaps(ref, low, chosen)
        notes.append(f"program's widest gap (not compared in a control "
                     f"run): {widest:.6f}")
        widest = float(cg.max()) if cg.size else 0.0
        notes.append(f"control (float8 reference) widest gap: {widest:.6f}")
    checks = {
        "max_logit_gap": {"value": widest, "limit": ck["max_logit_gap"]},
        "served_tokens_checked": {"value": int(gaps.size),
                                  "limit": ck["min_tokens"]},
        "stream_mismatches": {"value": mismatched, "limit": 0},
    }
    correct = (widest <= ck["max_logit_gap"]
               and gaps.size >= ck["min_tokens"] and mismatched == 0)

    line = {"correct": bool(correct), "attempted": len(recs),
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line, notes


def traced_metrics(cell, state, engine, recs, cfg, peaks, serving):
    """Per-layer metrics from the trace of the window's first part."""
    from jax.profiler import ProfileData

    import trace_reduce

    path = glob.glob(os.path.join(state["trace_dir"], "**", "*.xplane.pb"),
                     recursive=True)
    red = trace_reduce.reduce_trace(ProfileData.from_file(path[0]))
    shutil.rmtree(state["trace_dir"], ignore_errors=True)
    m0, m1 = state["m0"], state["m1"]
    delta = {k: getattr(m1, k) - getattr(m0, k)
             for k in ("ticks", "occupancy_sum", "prefill_tokens",
                       "decode_tokens")}
    t0, t1 = state["t_trace0"], state["t_trace1"]
    events = [e for e in engine.tracer.events()
              if e.rid is not None and e.rid < RID_WARM]
    # decode work: rows per step and keys per row from what the clients
    # received inside the traced part (token j >= 1 of a request was
    # decoded at position len(prompt) + j - 1)
    rows = keys = 0
    for r in recs:
        for j, t in enumerate(r.times):
            if j >= 1 and t0 <= t <= t1:
                rows += 1
                keys += len(r.prompt) + j
    steps = red.program_runs("jit__decode_impl")
    step_work = bound = None
    if steps and rows:
        frozen = frozenset(cell.config.get("decode_nf4", ()))
        step_work = cell.decode_step(
            cell.config["model"], cfg.family, rows / steps, keys / steps,
            frozen)
        bound = step_work.least_s(peaks)[1]
    ctx = TracedContext(trace=red, engine=delta,
                        max_batch=serving["max_batch"], span=(t0, t1),
                        events=events, records=recs,
                        decode_step_work=step_work, decode_bound=bound,
                        peaks=peaks)
    metrics = {}
    for m in cell.per_layer:
        v = load_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    programs = sorted(red.programs.items(), key=lambda kv: -kv[1][0])
    ops = sorted(red.ops.items(), key=lambda kv: -kv[1])
    device_ops = [[k, v[0]] for k, v in programs][:4]
    device_ops += [[k, v] for k, v in ops][:10 - len(device_ops)]
    breakdown = {"device_ops": device_ops,
                 "idle_gaps": red.idle_by_label()[:10]}
    if bound is not None:
        log(f"decode step least time bound by {bound}")
    return metrics, breakdown, {"busy_s": red.busy_s,
                                "window_s": red.window_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        import_repro()
        set_up_jax()
        require_devices(cell.chips)
    except (CellError, Unavailable, OSError) as e:
        log(f"bench: {e}")
        return 2
    except NoDevice as e:
        log(f"bench: {e}")
        return 3
    line, notes = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           control=bool(args.control))
    for n in notes:
        print(n, flush=True)
    for k, v in line["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
