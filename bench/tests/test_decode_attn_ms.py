"""``decode_attn_ms``: the paged decode attention kernel's device time per
decode run, read from a synthetic trace, and nothing where the program
has no such kernel (the gather path before it, or another platform)."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from cell import BENCH, load_module
from trace_reduce import Reduced


def _reader():
    return load_module(BENCH / "metrics" / "decode_attn_ms.py")


def _ctx(ops, runs):
    trace = Reduced(window_s=10.0, busy_s=9.0, chips=1,
                    programs={"jit__decode_impl": [8.0, runs],
                              "jit__chunk_step_impl": [1.0, 4]},
                    ops=ops)
    return SimpleNamespace(trace=trace)


def test_decode_attn_ms_on_a_synthetic_trace():
    # seven applications a step; the prefill's attention and the LUT
    # kernel are not the decode kernel's time
    ops = {f"jit__decode_impl/%paged_decode_attention.{i}": 0.02
           for i in range(1, 8)}
    ops |= {"jit__decode_impl/%lut_gemm_dc_res.3": 2.0,
            "jit__decode_impl/%fusion.12": 1.0,
            "jit__chunk_step_impl/%paged_decode_attention.1": 0.5}
    assert _reader().read(_ctx(ops, 70)) == pytest.approx(
        7 * 0.02 / 70 * 1e3)


@pytest.mark.parametrize("case", ["no_kernel", "no_runs"])
def test_decode_attn_ms_reads_nothing_where_there_is_nothing(case):
    """The gather path (the parent's program, or a cell whose attention
    takes no kernel) and a trace without decode runs leave the metric
    out."""
    if case == "no_kernel":
        ctx = _ctx({"jit__decode_impl/%convert.279": 0.1,
                    "jit__decode_impl/%gather.3": 0.2}, 70)
    else:
        ctx = _ctx({"jit__decode_impl/%paged_decode_attention.1": 0.1}, 0)
    assert _reader().read(ctx) is None
