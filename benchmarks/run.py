"""Benchmark orchestrator: one function per paper table/figure + kernel,
engine and roofline benches.  Prints ``name,us_per_call,derived`` CSV.

``--smoke`` runs a minutes-not-hours subset (CI uploads its CSV as an
artifact): one kernel bench + the serving-engine smoke, and writes
``BENCH_engine.json`` (decode/prefill tok/s + occupancy per slab width,
recurrent chunked-prefill scenarios, and the prefix-cache
shared-system-prompt warm-vs-cold section) so the perf trajectory
accumulates across commits.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _run(fns, failures: int) -> int:
    for fn in fns:
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{fn.__name__},FAIL,{traceback.format_exc(limit=1)!r}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI subset: kernel modes + engine smoke")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0

    from benchmarks import engine_bench, kernel_bench

    if args.smoke:
        failures = _run([kernel_bench.luna_mm_modes, engine_bench.smoke,
                         engine_bench.bench_json],
                        failures)
        if failures:
            sys.exit(1)
        return

    from benchmarks import paper_tables
    failures = _run(paper_tables.ALL, failures)
    failures = _run(kernel_bench.ALL, failures)
    failures = _run(engine_bench.ALL, failures)

    # roofline summary from the dry-run artifacts (if the sweep has run)
    try:
        from benchmarks import roofline_report
        roofline_report.summary_csv()
    except Exception:  # noqa: BLE001
        print("roofline_report,SKIP,run `python -m repro.launch.dryrun --all`"
              " first")

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
