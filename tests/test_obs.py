"""Observability subsystem: registry/tracer units + engine integration.

The load-bearing pins:
  * ``EngineMetrics`` keeps its exact field/``since()``/``summary()``
    contracts with ``Engine.metrics`` now a live registry-backed view
    (reads, writes, ``+=``, and the bench's counter resets all work);
  * ``summary()`` reports 0.0 tok/s when no tokens moved (an empty run
    must not divide 0 by epsilon into garbage);
  * the tracer stamps exclusively from the injected clock: two identical
    virtual-clock load-harness runs produce BYTE-IDENTICAL Perfetto JSON
    and identical registry dumps;
  * every finished request's span set is complete (submit/queue/admit/
    first_token/finish, prefill_chunk events matching the metric delta,
    one token event per emitted token);
  * under virtual-clock overload every request drains with positive
    goodput and the high-priority class's p99 TTFT below the low class's;
  * the threaded (real background loop) drive emits a schema-valid trace
    — same completeness per request, no ordering assumptions across
    requests;
  * the Prometheus exporter serves the text exposition over HTTP;
  * one ``Engine._phase`` timing feeds histogram, ring and the profiler
    annotation hook; the ticking thread's spans tile each tick; tokens
    are the same with tracing on and off; ``engine_compiles_total``
    names the program whose jit cache grew.
"""
import contextlib
import json
import os
import sys
import urllib.request

import pytest

from repro.obs import (TTFT_BUCKETS, MetricsRegistry, Tracer, dump_metrics,
                       dump_trace, perfetto_json, start_metrics_server)
from repro.obs.trace import request_events
from repro.serve.engine import EngineMetrics, EngineMetricsView

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.load_harness import (VirtualClock, build_engine,  # noqa: E402
                                     make_trace, run_threaded, run_virtual)


# --- registry ------------------------------------------------------------
def test_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", ("priority",))
    c.add(priority="0")
    c.add(2, priority="1")
    assert c.value(priority="1") == 2 and c.value(priority="0") == 1
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.add(-2)
    assert g.value() == 5
    h = reg.histogram("lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 4
    text = reg.prometheus_text()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{priority="1"} 2' in text
    assert '# TYPE lat_seconds histogram' in text
    # cumulative le buckets: 1 <= 0.01, 2 <= 0.1, 3 <= 1.0, 4 <= +Inf
    assert 'lat_seconds_bucket{le="0.01"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert 'lat_seconds_count 4' in text


def test_registry_schema_enforced():
    reg = MetricsRegistry()
    c = reg.counter("a_total", "a", ("mode",))
    with pytest.raises(ValueError):
        c.add(wrong="x")
    with pytest.raises(ValueError):
        reg.gauge("a_total", "a", ("mode",))     # type mismatch
    with pytest.raises(ValueError):
        reg.counter("a_total", "a", ("other",))  # label-schema mismatch
    assert reg.counter("a_total", "a", ("mode",)) is c   # idempotent


def test_registry_dump_deterministic():
    def build():
        reg = MetricsRegistry()
        reg.counter("z_total", "z").add(3)
        h = reg.histogram("t_seconds", "t", ("k",), buckets=TTFT_BUCKETS)
        h.observe(0.004, k="a")
        h.observe(2.0, k="b")
        return reg

    a, b = build(), build()
    assert a.dump_json() == b.dump_json()
    assert a.prometheus_text() == b.prometheus_text()
    d = a.dump()
    assert d["t_seconds"]["kind"] == "histogram"
    assert d["t_seconds"]["series"]['k="a"']["count"] == 1


# --- EngineMetrics value type + view -------------------------------------
def test_summary_zero_tokens_is_zero():
    s = EngineMetrics().summary(max_batch=4)
    assert s["prefill_tok_s"] == 0.0
    assert s["decode_tok_s"] == 0.0
    assert s["occupancy"] == 0.0


def test_summary_nonzero_divides():
    m = EngineMetrics(prefill_s=2.0, prefill_tokens=10,
                      decode_s=0.5, decode_tokens=5, ticks=2,
                      occupancy_sum=4)
    s = m.summary(max_batch=2)
    assert s["prefill_tok_s"] == pytest.approx(5.0)
    assert s["decode_tok_s"] == pytest.approx(10.0)
    assert s["occupancy"] == pytest.approx(1.0)


def test_metrics_view_contract():
    view = EngineMetricsView(MetricsRegistry())
    assert view.ticks == 0
    view.ticks += 3                       # read-modify-write
    view.decode_tokens = 7
    view.prefill_s += 0.5
    assert view.ticks == 3 and view.decode_tokens == 7
    snap = view.snapshot()
    assert isinstance(snap, EngineMetrics) and snap.ticks == 3
    view.ticks += 1
    delta = view.since(snap)
    assert delta.ticks == 1 and delta.decode_tokens == 0
    view.decode_tokens = 0                # the bench's reset spelling
    view.decode_s = 0.0
    assert view.summary(4)["decode_tok_s"] == 0.0
    with pytest.raises(AttributeError):
        view.not_a_metric = 1
    with pytest.raises(AttributeError):
        _ = view.not_a_metric


# --- tracer --------------------------------------------------------------
def test_tracer_disabled_is_noop_and_ring_drops():
    clk = iter(float(i) for i in range(100)).__next__
    tr = Tracer(clock=clk, capacity=4, enabled=False)
    tr.event("submit", rid=1)
    assert tr.events() == [] and tr.dropped == 0
    tr.enabled = True
    for i in range(6):
        tr.event("token", rid=i)
    assert len(tr.events()) == 4 and tr.dropped == 2
    assert [e.rid for e in tr.events()] == [2, 3, 4, 5]   # oldest dropped
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


def test_tracer_span_and_perfetto_bytes():
    clk = iter([1.0, 1.5, 2.0, 3.0]).__next__
    tr = Tracer(clock=clk, enabled=True)
    with tr.span("decode", batch=2):
        pass
    tr.event("first_token", rid=7)
    evs = tr.events()
    assert evs[0].dur == pytest.approx(0.5) and evs[0].rid is None
    text = tr.perfetto()
    assert text == perfetto_json(evs)     # pure function of the events
    doc = json.loads(text)
    rows = doc["traceEvents"]
    meta = [r for r in rows if r["ph"] == "M"]
    assert {"engine", "requests"} <= {
        r["args"]["name"] for r in meta if r["name"] == "process_name"}
    span = next(r for r in rows if r.get("ph") == "X")
    assert span["dur"] == pytest.approx(0.5e6)            # microseconds
    inst = next(r for r in rows if r.get("ph") == "i")
    assert inst["tid"] == 7 and inst["pid"] == 1


class _Annotations:
    """A stand-in for ``jax.profiler.TraceAnnotation`` that records what
    it was entered with."""

    def __init__(self):
        self.entered, self.exited = [], 0

    def __call__(self, name):
        self.entered.append(name)

        @contextlib.contextmanager
        def cm():
            yield
            self.exited += 1
        return cm()


def test_annotate_entered_once_per_complete_span():
    ann = _Annotations()
    clk = iter(float(i) for i in range(10)).__next__
    tr = Tracer(clock=clk, enabled=True, annotate=ann)
    with tr.span("decode"):
        with tr.span("decode_wait"):
            assert tr.current().name == "decode_wait"
        assert tr.current().name == "decode"
    tr.event("token", rid=1)                  # instant: no annotation
    assert tr.current() is None
    assert ann.entered == ["engine.decode", "engine.decode_wait"]
    assert ann.exited == 2
    assert [e.name for e in tr.events()] == ["decode_wait", "decode",
                                             "token"]


def test_disabled_tracer_never_annotates_but_observes():
    ann = _Annotations()
    clk = iter([2.0, 2.5]).__next__
    seen = []
    tr = Tracer(clock=clk, enabled=False, annotate=ann)
    with tr.span("emit", observe=seen.append) as sp:
        assert tr.current() is None
    assert seen == [0.5] and sp.dur == 0.5
    with tr.span("emit") as sp:               # nothing to feed: no clock
        pass
    assert sp.dur is None
    assert ann.entered == [] and tr.events() == []


def test_phase_helper_feeds_histogram_ring_and_hook():
    eng, _ = build_engine("yi-9b", clock=VirtualClock(), trace=True)
    ann = _Annotations()
    eng.tracer.annotate = ann
    eng.clock.advance(1.0)
    with eng._phase("decode_wait") as ph:
        eng.clock.advance(0.125)
        ph.args["compiled"] = True
    assert ph.dur == 0.125
    (ev,) = [e for e in eng.tracer.events() if e.name == "decode_wait"]
    assert (ev.ts, ev.dur, ev.args) == (1.0, 0.125, {"compiled": True})
    series = eng.registry.dump()["engine_tick_phase_seconds"]["series"]
    assert series['phase="decode_wait"']["sum"] == 0.125
    assert series['phase="decode_wait"']["count"] == 1
    assert ann.entered == ["engine.decode_wait"] and ann.exited == 1
    # tracing off: histogram only
    eng.tracer.enabled = False
    with eng._phase("decode_wait"):
        eng.clock.advance(0.25)
    series = eng.registry.dump()["engine_tick_phase_seconds"]["series"]
    assert series['phase="decode_wait"']["sum"] == 0.375
    assert len([e for e in eng.tracer.events()
                if e.name == "decode_wait"]) == 1
    assert ann.entered == ["engine.decode_wait"]


# --- exporters -----------------------------------------------------------
def test_http_exporter_and_dumps(tmp_path):
    reg = MetricsRegistry()
    reg.counter("hits_total", "hits").add(5)
    server = start_metrics_server(reg, port=0)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "hits_total 5" in body
        js = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read()
        assert json.loads(js)["hits_total"]["series"][""] == 5
    finally:
        server.shutdown()
    mp = tmp_path / "m.prom"
    assert dump_metrics(reg, str(mp)) == mp.read_text()
    tr = Tracer(clock=iter([0.0]).__next__, enabled=True)
    tr.event("submit", rid=0)
    tp = tmp_path / "t.json"
    assert dump_trace(tr, str(tp)) == tp.read_text()
    json.loads(tp.read_text())


# --- engine integration --------------------------------------------------
def _virtual_run(trace_kw=None, **knobs):
    eng, cfg = build_engine("yi-9b", clock=VirtualClock(), trace=True,
                            **knobs)
    trace = make_trace(8, 100.0, cfg.vocab_size, seed=0,
                       deadline_budgets={0: 0.8, 1: 0.5},
                       **(trace_kw or {}))
    rep = run_virtual(eng, trace)
    assert rep["drained"], rep
    return eng


def test_virtual_runs_byte_identical():
    a = _virtual_run()
    b = _virtual_run()
    assert a.tracer.perfetto() == b.tracer.perfetto()
    assert a.registry.dump_json() == b.registry.dump_json()
    assert a.tracer.events()                       # not vacuous


def test_virtual_span_sets_complete():
    # prompts above the chunk size exercise the staged/chunked admission
    eng = _virtual_run(trace_kw={"prompt_lens": (4, 12, 20)},
                       prefill_chunk=8)
    evs = eng.tracer.events()
    per_req = request_events(evs)
    assert len(per_req) == 8
    for rid, res in per_req.items():
        names = [e.name for e in res]
        for need in ("submit", "queue", "admit", "first_token", "finish"):
            assert need in names, (rid, need, names)
        assert names.index("submit") < names.index("admit") \
            < names.index("first_token") < names.index("finish")
        assert names.count("submit") == names.count("finish") == 1
    # event/metric pairing: chunk events match the counter, token events
    # match tokens emitted (one first_token per request, rest tokens)
    m = eng.metrics
    assert sum(n == "prefill_chunk" for e in evs
               for n in [e.name]) == m.prefill_chunks > 0
    tok_ev = sum(e.name in ("first_token", "token") for e in evs)
    assert tok_ev == m.decode_tokens + len(per_req)
    # engine-phase lanes carry complete spans
    phases = {e.name for e in evs if e.rid is None}
    assert {"admit", "prefill", "decode", "emit"} <= phases
    # deterministic registry state reflects the run
    dump = eng.registry.dump()
    assert dump["engine_requests_submitted_total"]["series"]
    assert dump["engine_ttft_seconds"]["series"]
    assert dump["engine_info"]["series"]


@pytest.mark.parametrize("arch", ["yi-9b", "mamba2-1.3b"])
def test_virtual_overload_keeps_priority_split(arch):
    """Arrivals far above service on the virtual clock (deterministic):
    every request drains, goodput stays positive, and the high-priority
    class's p99 TTFT stays strictly below the low class's."""
    eng, cfg = build_engine(arch, clock=VirtualClock())
    trace = make_trace(48, 100.0, cfg.vocab_size, seed=0,
                       deadline_budgets={0: 0.8, 1: 0.5})
    rep = run_virtual(eng, trace)
    assert rep["drained"] and rep["finished"] == 48, rep
    assert rep["goodput_tok_s"] > 0
    byp = rep["by_priority"]
    assert byp["1"]["ttft"]["p99_s"] < byp["0"]["ttft"]["p99_s"], byp


def test_threaded_trace_schema_valid():
    eng, cfg = build_engine("yi-9b", trace=True)
    trace = make_trace(6, 200.0, cfg.vocab_size, seed=1,
                       deadline_budgets={0: None, 1: None})
    rep = run_threaded(eng, trace, time_scale=0.01)
    assert rep["finished"] == 6, rep
    doc = json.loads(eng.tracer.perfetto())        # parses
    assert doc["traceEvents"]
    per_req = request_events(eng.tracer.events())
    assert len(per_req) == 6
    for rid, res in per_req.items():
        names = [e.name for e in res]
        # unordered-tolerant across requests; per-request completeness
        # holds because every emission point runs under the engine lock
        assert names.count("submit") == names.count("finish") == 1, names
        assert "first_token" in names and "admit" in names
        ts = [e.ts for e in res]
        assert ts == sorted(ts), f"rid {rid}: events not time-ordered"


def test_tracing_off_records_nothing_but_metrics_live():
    eng, cfg = build_engine("yi-9b", clock=VirtualClock())
    trace = make_trace(4, 100.0, cfg.vocab_size, seed=3,
                       deadline_budgets={0: None, 1: None})
    run_virtual(eng, trace)
    assert eng.tracer.events() == [] and not eng.tracer.enabled
    assert eng.metrics.decode_tokens > 0
    assert eng.registry.dump()["engine_requests_finished_total"][
        "series"][""] == 4
    # gauges settle back to idle
    assert eng.registry.gauge("engine_queue_depth").value() == 0
    assert eng.registry.gauge("engine_active_slots").value() == 0


#: the spans that contain others on the loop thread, and what they contain
_PARENTS = {"admit": {"prefill"}, "decode": {"decode_wait"},
            "verify": {"verify_wait"}}


def _loop_spans(eng):
    return sorted(((e.ts, e.ts + e.dur, e.name) for e in eng.tracer.events()
                   if e.rid is None and e.name != "freeze"),
                  key=lambda s: (s[0], -s[1]))


def test_loop_thread_spans_tile_each_tick():
    eng, cfg = build_engine("yi-9b", trace=True, prefill_chunk=8)
    trace = make_trace(6, 200.0, cfg.vocab_size, seed=2,
                       deadline_budgets={0: None, 1: None},
                       prompt_lens=(4, 12, 20))
    rep = run_threaded(eng, trace, time_scale=0.01)
    assert rep["finished"] == 6, rep
    spans = _loop_spans(eng)
    names = [n for _, _, n in spans]
    for need in ("admit", "prefill", "decode_inputs", "decode",
                 "decode_wait", "emit", "loop_idle"):
        assert need in names, need
    # spans nest or are disjoint, and only the listed parents nest
    open_ = []
    for a, b, n in spans:
        while open_ and open_[-1][1] <= a:
            open_.pop()
        if open_:
            pa, pb, pn = open_[-1]
            assert b <= pb and n in _PARENTS.get(pn, ()), \
                (pn, pa, pb, n, a, b)
        open_.append((a, b, n))
    # every decode tick: admit, then decode_inputs, decode containing one
    # decode_wait, then emit
    for i, (a, b, n) in enumerate(spans):
        if n != "decode":
            continue
        prev = [s for s in spans[:i] if s[2] in ("admit", "decode_inputs",
                                                 "emit")]
        assert [s[2] for s in prev[-2:]] == ["admit", "decode_inputs"]
        inner = [s for s in spans if a <= s[0] and s[1] <= b and s[2]
                 == "decode_wait"]
        assert len(inner) == 1
        after = next(s for s in spans[i + 1:] if s[2] != "decode_wait")
        assert after[2] == "emit" and after[0] >= b


def test_tokens_identical_with_tracing_on_and_off():
    from repro.serve.engine import Request
    outs = []
    for trace in (False, True):
        eng, cfg = build_engine("yi-9b", trace=trace, prefill_chunk=8,
                                max_batch=3)
        reqs = [Request(rid=i, prompt=[(7 * i + j) % 50 + 1
                                       for j in range(n)], max_new=6)
                for i, n in enumerate((4, 12, 20, 9))]
        assert eng.serve(reqs)["done"]
        outs.append([r.out for r in reqs])
        assert bool(eng.tracer.events()) == trace
    assert outs[0] == outs[1] and all(len(o) == 6 for o in outs[0])


def test_compile_counter_names_the_program_that_compiled():
    from repro.serve.engine import Request
    eng, cfg = build_engine("yi-9b", trace=True)
    c = eng.registry.counter("engine_compiles_total", "", ("program",))

    def serve(k, rid0):
        reqs = [Request(rid=rid0 + i, prompt=[1 + i, 2, 3], max_new=2)
                for i in range(k)]
        eng.tracer.clear()
        assert eng.serve(reqs)["done"]
        return [e for e in eng.tracer.events() if e.name == "prefill"]

    first = serve(1, 0)
    assert c.value(program="prefill") == 1
    assert c.value(program="decode") == 1
    assert first[0].args.get("compiled") is True
    two = serve(2, 10)                  # a new admission count
    assert c.value(program="prefill") == 2
    assert two[0].args == {"batch": 2, "compiled": True}
    again = serve(2, 20)                # the same count again
    assert c.value(program="prefill") == 2
    assert c.value(program="decode") == 1
    assert "compiled" not in again[0].args
