"""Record the small profiler trace that ``bench/tests/test_trace_reduce.py``
reads, on the chip:

    python bench/testdata/record_trace.py

Runs two jitted programs, ``jit_mul_step`` and ``jit_add_step``, three
times each, with host sleeps between them inside a
``jax.profiler.TraceAnnotation("host_wait")``, so the trace holds known
device programs and known idle gaps, all inside a ``bench_window``
annotation.  Writes the trace's ``.xplane.pb`` over
``bench/testdata/small.xplane.pb`` and prints, per plane, its lines and
a few event names (what the reduction reads).  Exits 1 off a TPU.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
OUT = HERE


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1

    def mul_step(x):
        return (x @ x) * 0.5

    def add_step(x):
        return x + 1.0

    mul = jax.jit(mul_step)
    add = jax.jit(add_step)
    x = jnp.ones((2048, 2048), jnp.float32)
    jax.block_until_ready(add(mul(x)))              # compile outside
    with tempfile.TemporaryDirectory(dir=HERE) as tmp:
        jax.profiler.start_trace(tmp)
        window = jax.profiler.TraceAnnotation("bench_window")
        window.__enter__()
        for _ in range(3):
            y = jax.block_until_ready(mul(x))
            with jax.profiler.TraceAnnotation("host_wait"):
                time.sleep(0.02)
            jax.block_until_ready(add(y))
            with jax.profiler.TraceAnnotation("host_wait"):
                time.sleep(0.01)
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        shutil.copy(path, OUT / "small.xplane.pb")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(OUT / "small.xplane.pb"))
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})[:8]
            print("   line", repr(line.name), len(evs), names)
    return 0


if __name__ == "__main__":
    sys.exit(main())
