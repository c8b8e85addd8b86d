"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure; prints ``name,us_per_call,derived``
CSV rows. The roofline section reads the dry-run artifacts if present.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import (bench_completion, bench_distinct, bench_encoded,
                   bench_engine, bench_resources, bench_scale,
                   bench_skyline, bench_stream, bench_topn, bench_tpch,
                   roofline)
    from .common import write_results
    from repro import compile_cache
    compile_cache.enable()
    print("name,us_per_call,derived")
    ok = True
    for mod in (bench_distinct, bench_topn, bench_skyline, bench_engine,
                bench_stream, bench_tpch, bench_encoded, bench_scale,
                bench_completion, bench_resources, roofline):
        try:
            mod.run()
        except Exception:  # noqa: BLE001
            ok = False
            print(f"{mod.__name__},-1,ERROR")
            traceback.print_exc()
    print(f"wrote {write_results()}", file=sys.stderr)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
