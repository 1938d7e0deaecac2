"""``bench/trace_reduce.py`` on a small trace recorded on a TPU v5e by
``bench/testdata/record_trace.py``: three runs each of ``jit_mul_step``
and ``jit_add_step``, with host sleeps of 20 ms and 10 ms between them
inside ``host_wait`` annotations, all inside ``bench_window``."""
from __future__ import annotations

import pytest

import trace_reduce
from cell import BENCH

TRACE = BENCH / "testdata" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace_reduce.reduce_trace(ProfileData.from_file(str(TRACE)))


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(TRACE))
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {line.name: line for line in dev.lines}
    ops = [(e.start_ns, e.start_ns + e.duration_ns)
           for e in lines["XLA Ops"].events]
    mods = [(e.name, e.duration_ns) for e in lines["XLA Modules"].events]
    return ops, mods


def test_programs_and_counts(reduced, raw):
    _, mods = raw
    assert set(reduced.programs) == {"jit_mul_step", "jit_add_step"}
    for name in reduced.programs:
        secs, n = reduced.programs[name]
        assert n == 3
        want = sum(d for m, d in mods if m.startswith(name + "(")) / 1e9
        assert secs == pytest.approx(want)
    assert reduced.program_runs("jit_") == 6


def test_busy_is_the_union_of_operations(reduced, raw):
    ops, _ = raw
    # by hand: sweep the sorted intervals, adding only uncovered time
    total, end = 0, None
    for a, b in sorted(ops):
        if end is None or a >= end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    assert reduced.busy_s == pytest.approx(total / 1e9)
    assert 0 < reduced.busy_s < reduced.window_s
    assert reduced.window_s == pytest.approx(0.0989, abs=0.005)


def test_idle_gaps_are_the_host_waits(reduced):
    long = sorted((g for g in reduced.gaps if g[0] > 5e-3), reverse=True)
    # three sleeps of 20 ms and three of 10 ms, less the launch overlap
    assert len(long) == 6
    assert all(0.015 < s < 0.025 for s, _ in long[:3])
    assert all(0.008 < s < 0.015 for s, _ in long[3:])
    assert {label for _, label in long} <= {"host_wait", "$time sleep"}
    idle = sum(s for s, _ in reduced.gaps)
    assert idle == pytest.approx(reduced.window_s - reduced.busy_s)
    top = reduced.idle_by_label()[0]
    assert top[0] in ("host_wait", "$time sleep") and top[1] > 0.08
