"""Run the TPC-H-subset workload suite with self-tuned engine plans.

Each suite query (Q1 filter+groupby, Q3 join+topn, Q6 selective agg —
``repro.query.workloads``) runs through its pruned engine path and is
checked for exact equality against its plain-Python reference, then
timed. The ``--tune`` flag selects the plan source:

  off     the analytic planner's plan (no cache, no racing)
  cached  replay a previously raced plan; analytic on a miss
  race    race the mask-preserving candidate grid on a stream prefix,
          persist the winner in the plan cache (REPRO_PLAN_CACHE)

Results are bit-identical across all three settings — tuning changes
speed, never answers — which this script asserts on every run.

With ``--trace PATH`` the whole run executes at the ``"trace"``
telemetry level and finishes with an instrumented ``engine_prune`` over
the suite's lineitem TOP-N bed: its ``ExecReport`` is printed and
checked *exactly* against the mask (``entries_kept == mask.sum()``) and
against the plan's wire arithmetic (``state_bytes_shipped`` equals the
stacked pass-1 state the master gathers), then every recorded span is
written to PATH as Chrome trace-event JSON — open it in Perfetto or
``chrome://tracing``.

  PYTHONPATH=src python examples/tpch_suite.py [--smoke] [--tune=race]
  PYTHONPATH=src python examples/tpch_suite.py --smoke --trace /tmp/t.json
"""
import argparse
import json
import time

import numpy as np

from repro import compile_cache
from repro.query import workloads


def _trace_demo(tables, path):
    """One traced engine call over the suite's TOP-N bed, report
    verified exactly, Chrome trace written to `path`."""
    from repro import obs
    from repro.core import engine_prune

    streams, params = workloads.engine_streams("topn_det", tables)
    shards = 8
    r = engine_prune("topn_det", *streams, mode="two_pass",
                     shards=shards, obs="trace", **params)
    rep = r.report
    print(rep.summary())

    m = int(streams[0].shape[0])
    kept = int(np.asarray(r.keep).sum())
    assert rep.entries_scanned == m, (rep.entries_scanned, m)
    assert rep.entries_kept == kept, (rep.entries_kept, kept)
    assert rep.prune_ratio == 1.0 - kept / m
    # the wire bytes must equal the plan's own arithmetic: two_pass
    # gathers the stacked pass-1 states, i.e. exactly the state the
    # merge-free sharded mode hands back — S lanes x per-lane bytes
    stacked = engine_prune("topn_det", *streams, mode="sharded",
                           shards=shards, obs="off", **params).state
    import jax
    want = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(stacked))
    assert rep.state_bytes_shipped == want, \
        (rep.state_bytes_shipped, want)
    assert rep.merge_collective_count == 1

    obs.TRACER.write(path)
    doc = json.loads(open(path).read())
    assert doc["traceEvents"], "trace file has no events"
    assert all(e["ph"] == "X" and e["dur"] >= 0
               for e in doc["traceEvents"])
    print(f"report verified against mask + plan; "
          f"{len(doc['traceEvents'])} spans -> {path} "
          f"(open in Perfetto / chrome://tracing)")


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small tables, CI-sized (the verify.sh run)")
    ap.add_argument("--tune", default="off",
                    choices=("off", "cached", "race"))
    ap.add_argument("--scale", type=int, default=None,
                    help="lineitem rows (default 30000, smoke 2000)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run at obs='trace' and write Chrome "
                         "trace-event JSON to PATH")
    args = ap.parse_args(argv)
    scale = args.scale or (2_000 if args.smoke else 30_000)

    if args.trace:
        from repro import obs
        obs.set_default_level("trace")

    tables = workloads.tpch_tables(scale=scale, seed=0)
    print(f"TPC-H-subset suite: lineitem={scale} rows, "
          f"tune={args.tune}")
    for q in workloads.SUITE:
        ref = q.reference(tables)
        got = q.run(tables, tune=args.tune)  # warm (compile + any race)
        assert got == ref, (
            f"{q.name}: pruned result diverged from reference\n"
            f"  got: {str(got)[:200]}\n  ref: {str(ref)[:200]}")
        t0 = time.perf_counter()
        got = q.run(tables, tune=args.tune)
        us = (time.perf_counter() - t0) * 1e6
        assert got == ref
        print(f"  {q.name:<12} ({q.algo:>8}) {us/1e3:8.1f} ms   "
              f"== reference ✓")
    print("all suite results exactly equal their plain-Python "
          "references")
    if args.trace:
        _trace_demo(tables, args.trace)


if __name__ == "__main__":
    main()
