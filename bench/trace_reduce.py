"""From a profiler trace (``.xplane.pb``) to device numbers.

What is read:

* the traced window: the host event named :data:`WINDOW` (the harness
  wraps the traced part of the window in a ``TraceAnnotation`` of that
  name); everything below is clipped to it;
* device planes ``/device:TPU:<n>``: line ``XLA Modules`` holds one event
  per program execution, named ``<jit name>(<fingerprint>)``; line ``XLA
  Ops`` holds the operations inside them;
* the host plane's Python lines (lines named ``python``), on which the
  profiler's Python tracer records what the host was doing.

The device clock and the host clock of one trace differ by an offset
(about a millisecond on a v5e): each device plane is shifted by the
median gap between a program's execution and the nearest host launch
(``PjitFunction(<name>)``) of the same function, so device events and
the host window share one clock.

What comes out (:class:`Reduced`): busy seconds (the union of operation
intervals), per-program device seconds and execution counts, the top
operations, and the idle gaps between operations, each labelled by the
shortest host event that covers it on a thread that launches device
programs.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

WINDOW = "bench_window"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_LAUNCH = "PjitFunction("
#: control-flow operations contain the operations of their bodies
_CONTAINERS = ("%while", "%conditional", "%call")
#: gaps shorter than this share one label
SHORT_GAP_S = 1e-3


@dataclass
class Reduced:
    window_s: float
    busy_s: float                   # mean over the device planes read
    chips: int
    programs: dict = field(default_factory=dict)   # name -> [seconds, n]
    ops: dict = field(default_factory=dict)        # "prog/op" -> seconds
    gaps: list = field(default_factory=list)       # (seconds, label)

    def program_seconds(self, prefix: str) -> float:
        return sum(v[0] for k, v in self.programs.items()
                   if k.startswith(prefix))

    def program_runs(self, prefix: str) -> int:
        return sum(v[1] for k, v in self.programs.items()
                   if k.startswith(prefix))

    def idle_by_label(self) -> list:
        """[[label, seconds], ...], most idle time first."""
        tot = defaultdict(float)
        for s, label in self.gaps:
            tot[label] += s
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])


def _union(intervals):
    total, end = 0, None
    out = []
    for a, b in sorted(intervals):
        if end is None or a > end:
            out.append([a, b])
            end = b
        elif b > end:
            out[-1][1] = b
            end = b
    for a, b in out:
        total += b - a
    return total, out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def program_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip()


def reduce_trace(pd) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to device numbers."""
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    win = [(e.start_ns, e.start_ns + e.duration_ns)
           for line in host.lines for e in line.events if e.name == WINDOW]
    if not win:
        raise ValueError(f"no host event {WINDOW!r} in the trace")
    lo, hi = win[0]
    launchers = []
    for line in host.lines:
        if line.name != "python":
            continue
        evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for e in line.events]
        if any(n.startswith(_LAUNCH) for _, _, n in evs):
            launchers.append((np.array([s for s, _, _ in evs], np.int64),
                              np.array([e for _, e, _ in evs], np.int64),
                              [n for _, _, n in evs]))
    launches = defaultdict(list)
    for line in host.lines:
        for e in line.events:
            if e.name.startswith(_LAUNCH):
                launches[e.name[len(_LAUNCH):-1]].append(e.start_ns)
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    programs: dict = {}
    ops: dict = defaultdict(float)
    busy_total = 0.0
    gaps = []
    chips = 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        skew = _skew(lines["XLA Modules"].events, launches)
        mods = []
        for e in lines["XLA Modules"].events:
            a, b = _clip(e.start_ns - skew, e.start_ns - skew + e.duration_ns,
                         lo, hi)
            if b <= a:
                continue
            name = program_name(e.name)
            mods.append((a, b, name))
            rec = programs.setdefault(name, [0.0, 0])
            rec[0] += (b - a) / 1e9
            rec[1] += 1
        if not mods:
            continue
        chips += 1
        mods.sort()
        intervals = []
        for e in lines["XLA Ops"].events:
            a, b = _clip(e.start_ns - skew, e.start_ns - skew + e.duration_ns,
                         lo, hi)
            if b <= a:
                continue
            intervals.append((a, b))
            name = op_name(e.name)
            if name.startswith(_CONTAINERS):
                continue
            owner = next((n for ma, mb, n in mods if ma <= a < mb), "?")
            ops[f"{owner}/{name}"] += (b - a) / 1e9
        busy, merged = _union(intervals)
        busy_total += busy / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) / 1e9, _label(a, b, launchers)))
    if chips == 0:
        raise ValueError("no device operations inside the traced window")
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy_total / chips,
                   chips=chips, programs=programs, ops=dict(ops), gaps=gaps)


def _skew(module_events, launches) -> int:
    """Median of (device start - nearest host launch start) over program
    executions whose function has host launches; 0 when none has."""
    diffs = []
    for e in module_events:
        fn = program_name(e.name)
        fn = fn[len("jit_"):] if fn.startswith("jit_") else fn
        starts = launches.get(fn)
        if starts:
            diffs.append(min((e.start_ns - s for s in starts), key=abs))
    return int(np.median(diffs)) if diffs else 0


def _label(a, b, launchers) -> str:
    """What the host was doing in the idle gap ``[a, b]``: of the events
    on threads that launch device programs, the name whose events overlap
    the gap most (within a tenth), the shortest such events first — so a
    gap spent in many short waits is named by the wait, not by the loop
    around it."""
    if (b - a) / 1e9 < SHORT_GAP_S:
        return f"gaps under {SHORT_GAP_S * 1e3:g} ms"
    overlap = defaultdict(int)
    shortest = {}
    for starts, ends, names in launchers:
        idx = np.nonzero((starts <= b) & (ends >= a))[0]
        for i in idx:
            ov = min(ends[i], b) - max(starts[i], a)
            if ov <= 0:
                continue
            n = names[i]
            overlap[n] += int(ov)
            d = int(ends[i] - starts[i])
            shortest[n] = min(shortest.get(n, d), d)
    if not overlap:
        return "no host event"
    top = max(overlap.values())
    near = [n for n, v in overlap.items() if v >= 0.9 * top]
    return min(near, key=lambda n: shortest[n])
