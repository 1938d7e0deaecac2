"""Request-lifecycle tracer: ring-buffered span events, Perfetto export.

The engine emits one structured event per lifecycle transition —
``submit -> queue -> admit -> prefill_chunk* -> first_token -> token* ->
preempt/cancel -> finish`` — plus complete-span events for the engine
phases (:data:`PHASE_EVENTS`).  Every stamp comes from the clock
injected at construction (the engine's single time base), which is what
makes virtual-clock load-harness traces byte-identical across repeated
runs: no wall time ever leaks into an event.

An optional ``annotate`` hook (a callable from a name to a context
manager; the engine passes ``jax.profiler.TraceAnnotation``) wraps every
complete span recorded while the tracer is enabled in
``annotate("engine." + name)``, so the same span also lands on a
profiler's host timeline, on the thread that did the work.

Recording is OFF by default (``enabled=False`` → :meth:`Tracer.event` is
a cheap early-return) and ring-buffered when on: a bounded
``collections.deque`` drops the oldest events under overflow and counts
the drops (``dropped``), so a long-running serve loop can trace forever
in fixed memory and the export is honest about truncation.

Export is the Chrome/Perfetto ``trace_event`` JSON format (open the file
at https://ui.perfetto.dev or ``chrome://tracing``): one track per
request (pid 1, tid = rid) carrying instant lifecycle events, one track
per engine phase lane (pid 0) carrying complete ``X`` spans.  The JSON
is rendered with sorted keys and stable separators — byte-identical for
identical event sequences (pinned in ``tests/test_obs.py``).
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from dataclasses import dataclass, field

#: lifecycle event names a request track can carry, in canonical order
REQUEST_EVENTS = ("submit", "queue", "admit", "prefill_chunk",
                  "first_token", "token", "preempt", "cancel", "finish")

#: engine-track phase names (complete spans, one lane each)
PHASE_EVENTS = ("admit", "prefill", "decode", "draft", "verify", "emit",
                "decode_inputs", "decode_wait", "verify_wait", "loop_idle",
                "freeze")

#: what the ``annotate`` hook's names start with
ANNOTATION_PREFIX = "engine."

_ENGINE_PID = 0
_REQUEST_PID = 1


@dataclass(frozen=True)
class TraceEvent:
    """One trace record.  ``rid`` is None for engine-phase spans;
    ``dur`` is None for instant events.  ``ts``/``dur`` are clock
    seconds (the export converts to microseconds)."""
    name: str
    ts: float
    rid: int | None = None
    dur: float | None = None
    args: dict = field(default_factory=dict)


class Tracer:
    """Bounded, clock-stamped event recorder.

    >>> clk = iter([0.0, 0.5, 0.75]).__next__
    >>> tr = Tracer(clock=clk, capacity=8, enabled=True)
    >>> tr.event("submit", rid=3, priority=1)
    >>> with tr.span("decode"):
    ...     pass
    >>> [(e.name, e.ts, e.rid) for e in tr.events()]
    [('submit', 0.0, 3), ('decode', 0.5, None)]
    >>> tr.events()[1].dur
    0.25
    """

    def __init__(self, clock=None, capacity: int = 65536,
                 enabled: bool = False, annotate=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.clock = clock if clock is not None else time.perf_counter
        self.capacity = capacity
        self.enabled = enabled
        self.annotate = annotate
        self._buf: deque[TraceEvent] = deque(maxlen=capacity)
        self._seen = 0            # lifetime appends (dropped = seen - len)
        self._lock = threading.Lock()
        self._open = threading.local()     # per-thread stack of open spans

    @property
    def dropped(self) -> int:
        """Events lost to ring-buffer overflow."""
        with self._lock:
            return self._seen - len(self._buf)

    def event(self, name: str, *, rid: int | None = None, ts: float | None
              = None, dur: float | None = None, **args) -> None:
        """Record one event (no-op while disabled).  ``ts`` defaults to
        the injected clock's now; pass it explicitly to stamp a span you
        timed yourself (the engine reuses its metric timestamps so trace
        and registry never disagree)."""
        if not self.enabled:
            return
        e = TraceEvent(name, self.clock() if ts is None else ts,
                       rid=rid, dur=dur, args=args)
        with self._lock:
            self._buf.append(e)
            self._seen += 1

    def span(self, name: str, *, rid: int | None = None, observe=None,
             **args):
        """Context manager recording ``name`` as a complete span over the
        enclosed block (clock-stamped entry/exit), inside the ``annotate``
        hook while enabled.  ``observe``, if given, is called with the
        block's duration whether or not the tracer is enabled (the block
        is then timed either way, once); the span's ``dur`` holds it
        after the block, and entries added to its ``args`` inside the
        block are recorded with it."""
        return _Span(self, name, rid, args, observe)

    def current(self):
        """The innermost span this thread has open while enabled, else
        None."""
        stack = getattr(self._open, "stack", None)
        return stack[-1] if stack else None

    def events(self) -> list[TraceEvent]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._seen = 0

    def perfetto(self) -> str:
        """The ring buffer as Chrome ``trace_event`` JSON text."""
        return perfetto_json(self.events())


class _Span:
    __slots__ = ("_tr", "name", "rid", "args", "_observe", "_on", "_ann",
                 "t0", "dur")

    def __init__(self, tracer, name, rid, args, observe):
        self._tr, self.name, self.rid, self.args = tracer, name, rid, args
        self._observe = observe
        self._ann = None
        self.dur = None

    def __enter__(self):
        tr = self._tr
        self._on = tr.enabled
        if self._on:
            if tr.annotate is not None:
                self._ann = tr.annotate(ANNOTATION_PREFIX + self.name)
                self._ann.__enter__()
            stack = getattr(tr._open, "stack", None)
            if stack is None:
                stack = tr._open.stack = []
            stack.append(self)
        if self._on or self._observe is not None:
            self.t0 = tr.clock()
        return self

    def __exit__(self, *exc):
        tr = self._tr
        if not self._on and self._observe is None:
            return False
        self.dur = tr.clock() - self.t0
        if self._observe is not None:
            self._observe(self.dur)
        if self._on:
            tr._open.stack.pop()
            tr.event(self.name, rid=self.rid, ts=self.t0, dur=self.dur,
                     **self.args)
            if self._ann is not None:
                self._ann.__exit__(*exc)
        return False


def request_events(events: list[TraceEvent]) -> dict[int, list[TraceEvent]]:
    """Group the request-track events by rid, preserving order."""
    out: dict[int, list[TraceEvent]] = {}
    for e in events:
        if e.rid is not None:
            out.setdefault(e.rid, []).append(e)
    return out


def perfetto_json(events: list[TraceEvent]) -> str:
    """Render events as Chrome/Perfetto ``trace_event`` JSON.

    Deterministic: sorted JSON keys, compact separators, metadata rows
    emitted in sorted track order — identical event lists produce
    byte-identical text.
    """
    rows = []
    rids = sorted({e.rid for e in events if e.rid is not None})
    rows.append({"ph": "M", "pid": _ENGINE_PID, "tid": 0,
                 "name": "process_name", "args": {"name": "engine"}})
    phase_tids = {p: i for i, p in enumerate(PHASE_EVENTS)}
    for p, tid in phase_tids.items():
        rows.append({"ph": "M", "pid": _ENGINE_PID, "tid": tid,
                     "name": "thread_name", "args": {"name": f"phase:{p}"}})
    rows.append({"ph": "M", "pid": _REQUEST_PID, "tid": 0,
                 "name": "process_name", "args": {"name": "requests"}})
    for rid in rids:
        rows.append({"ph": "M", "pid": _REQUEST_PID, "tid": rid,
                     "name": "thread_name",
                     "args": {"name": f"request {rid}"}})
    for e in events:
        us = e.ts * 1e6
        if e.rid is None:
            row = {"name": e.name, "pid": _ENGINE_PID,
                   "tid": phase_tids.get(e.name, len(PHASE_EVENTS)),
                   "ts": us}
            if e.dur is not None:
                row.update(ph="X", dur=e.dur * 1e6)
            else:
                row.update(ph="i", s="p")
        else:
            row = {"name": e.name, "pid": _REQUEST_PID, "tid": e.rid,
                   "ts": us}
            if e.dur is not None:
                row.update(ph="X", dur=e.dur * 1e6)
            else:
                row.update(ph="i", s="t")
        if e.args:
            row["args"] = e.args
        rows.append(row)
    return json.dumps({"displayTimeUnit": "ms", "traceEvents": rows},
                      sort_keys=True, separators=(",", ":"))
