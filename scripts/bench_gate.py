#!/usr/bin/env python
"""Bench regression gate: fresh --smoke numbers vs BENCH_results.json.

Runs ``benchmarks.bench_engine`` and ``benchmarks.bench_stream`` in
smoke mode (every stream shrunk, seconds of wall time) and gates each
``engine_*`` / ``stream_*`` row by its *name suffix* — the row name
declares its unit, so new rows are gated without name-guessing special
cases:

  ``*_us``    wall-clock microseconds. A smoke run is strictly smaller
              work than the committed full-size run of the same row, so
              fresh > THRESHOLD x committed can only mean a real
              regression (recompile storm, accidental O(m^2), a
              collective gone sequential), never small-m noise.
  ``*_p50_us`` / ``*_p99_us``  per-micro-batch latency percentiles,
              gated exactly like ``_us``: smoke micro-batches are
              strictly smaller, so smoke latency blowing past 3x the
              committed full-size latency means a blocking call or a
              recompile leaked onto the streaming hot path.
  ``*_x``     within-run speedup ratio, floored at FLOORS[name]
              (default 1.0): the batched/parallel path running slower
              than its baseline is breakage on any host at any m. Rows
              whose ratio legitimately dips below 1x at smoke shapes
              (mesh collective overhead floors) must be named
              ``*_ratio`` instead.
  ``*_qps``   throughput, higher is better. Smoke work is strictly
              smaller, so fresh qps below committed/THRESHOLD is a
              regression.
  ``*_eps``   entries/sec (streaming sustained throughput) — gated
              like ``_qps``.
  ``*_ratio`` informational ratio — reported, never gated.
  ``*_count`` resolved integer (lane counts etc.) — reported, never
              gated.

Any ``engine_*``/``stream_*`` row with none of these suffixes is an
error: the
conventions only work if every row declares its unit. Rows with no
committed baseline (newly added benches) are reported but never fail
the ``_us``/``_qps`` comparisons; ``_x`` floors always apply (they are
within-run, baseline-free).

Usage: python scripts/bench_gate.py  (from the repo root; sets its own
PYTHONPATH and the 8-device CPU platform, same as scripts/verify.sh)
"""
from __future__ import annotations

import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
THRESHOLD = 3.0

# per-row floors for *_x rows (default 1.0). The multiq floor is the
# CI acceptance: batched multi-query execution >= 5x a pre-jitted
# serial loop even at smoke shapes (full-size target is 10x, recorded
# in the row's derived string).
FLOORS = {
    "engine_topn_det_multiq_speedup_x": 5.0,
    # the streaming tentpole mechanism: a donated fold that stops
    # re-using its state buffers collapses to ~1x and must fail
    "stream_fold_donation_x": 1.2,
    # the tuning contract: the analytic incumbent is raced too, so the
    # winner can never be slower — < 1.0 means the race protocol broke
    # (incumbent skipped, or speedup computed from a re-measure instead
    # of the race's own timings)
    "tpch_tuned_vs_analytic_x": 1.0,
}

# percentile-latency suffixes before the plain "_us" they end with, so
# classify() names the specific unit; "_eps" gates like "_qps"
SUFFIXES = ("_p50_us", "_p99_us", "_us", "_x", "_qps", "_eps",
            "_ratio", "_count")
GATED_PREFIXES = ("engine_", "stream_", "tpch_", "encoded_",
                  "decode_skipped_")

# must precede any jax import (bench rows depend on the device count)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def classify(name: str) -> str:
    for s in SUFFIXES:
        if name.endswith(s):
            return s
    return ""


def main() -> int:
    committed_path = ROOT / "BENCH_results.json"
    committed = (json.loads(committed_path.read_text())
                 if committed_path.exists() else {})
    if not committed:
        print("bench_gate: no committed BENCH_results.json — gating "
              "only the within-run _x floors")

    from benchmarks import (bench_encoded, bench_engine, bench_stream,
                            bench_tpch, common)

    print("bench_gate: running bench_engine --smoke ...")
    bench_engine.run(smoke=True)
    print("bench_gate: running bench_stream --smoke ...")
    bench_stream.run(smoke=True)
    print("bench_gate: running bench_tpch --smoke ...")
    bench_tpch.run(smoke=True)
    print("bench_gate: running bench_encoded --smoke ...")
    bench_encoded.run(smoke=True)
    fresh = dict(common.RESULTS)

    failures: list[str] = []
    for name, val in sorted(fresh.items()):
        kind = classify(name)
        if not name.startswith(GATED_PREFIXES):
            continue  # kernel_/compact_ rows: tracked, not gated
        if not kind:
            failures.append(
                f"{name}: unknown unit suffix (expected one of "
                f"{', '.join(SUFFIXES)}) — name the row by its unit")
            print(f"bench_gate: {name}: no unit suffix FAIL")
            continue
        if kind == "_x":
            floor = FLOORS.get(name, 1.0)
            status = "FAIL" if val < floor else "ok"
            print(f"bench_gate: {name}: {val:.2f}x (floor {floor}x) "
                  f"{status}")
            if val < floor:
                failures.append(
                    f"{name}: {val:.2f}x below the {floor}x floor")
        elif kind in ("_us", "_p50_us", "_p99_us"):
            base = committed.get(name)
            if base is None:
                print(f"bench_gate: {name}: no committed baseline "
                      "(new row) — skipped")
                continue
            ratio = val / base if base > 0 else float("inf")
            status = "FAIL" if ratio > THRESHOLD else "ok"
            print(f"bench_gate: {name}: smoke {val:.1f}us vs committed "
                  f"{base:.1f}us ({ratio:.2f}x) {status}")
            if ratio > THRESHOLD:
                failures.append(
                    f"{name}: {val:.1f}us smoke > {THRESHOLD}x "
                    f"committed {base:.1f}us ({ratio:.2f}x)")
        elif kind in ("_qps", "_eps"):
            base = committed.get(name)
            if base is None:
                print(f"bench_gate: {name}: no committed baseline "
                      "(new row) — skipped")
                continue
            floor = base / THRESHOLD
            unit = "q/s" if kind == "_qps" else "entries/s"
            status = "FAIL" if val < floor else "ok"
            print(f"bench_gate: {name}: smoke {val:.1f} {unit} vs "
                  f"committed {base:.1f} (floor {floor:.1f}) {status}")
            if val < floor:
                failures.append(
                    f"{name}: {val:.1f} {unit} below committed/"
                    f"{THRESHOLD} = {floor:.1f}")
        else:  # _ratio / _count: informational
            print(f"bench_gate: {name}: {val:g} ({kind[1:]}) — "
                  "informational")

    if failures:
        print(f"\nbench_gate: {len(failures)} failure(s):")
        for f in failures:
            print(f"  {f}")
        return 1
    print("bench_gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
