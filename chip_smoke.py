#!/usr/bin/env python3
"""Bring-up check of the Cheetah query path on a TPU.

    python chip_smoke.py              # one chip: phases A-E
    python chip_smoke.py --chips 4    # four chips: the mesh path only

One process drives the chip(s). Each phase prints one line: what it
checked, its cold time (first call, compilation included) and its warm
time (the same call again), in seconds on the host clock. These are
bring-up times, not a benchmark. Every answer is compared with an
independent reference; a mismatch or an exception exits non-zero and
the final JSON line is not printed. With no TPU, the script says so on
stderr and exits 2.

  A  device      the TPU, its kind and count, the compile cache in use
  B  tpch_sf1    Q1/Q3/Q6 at TPC-H SF1 (6,001,215 lineitem rows) through
                 ``SuiteQuery.run``, each == its plain-Python reference
  C  batched     16 TOP-N specs with mixed N over lineitem extprice via
                 ``run_queries`` == serial ``run_query`` == exact top-N,
                 on the meshless default (two-pass on sqrt(m) lanes)
  D  stream      2^22 extprice / orderkey entries through ``PruneStream``
                 (TOP-N, DISTINCT) in 2^14-entry micro-batches;
                 ``close().keep`` == one-shot two_pass on ``lane_view``
  E  kernels     every Pallas kernel ``kernels.ops`` dispatches, compiled,
                 at m = 2^22 against its ``use_ref=True`` mirror

``--chips 4`` runs Q1's GROUP BY, a lineitem TOP-N and an orderkey
DISTINCT at SF1 through ``run_query``/``run_queries`` on a 4-device
``jax.make_mesh`` mesh against plain-Python references, and prints the
lineitem stream bytes each device holds. It runs them once (cold): with
one lane per worker each query scans ~1.5M entries per device.

The last stdout line is ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import heapq
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SF1_ROWS = 6_001_215          # TPC-H SF1 lineitem
STREAM_ENTRIES = 1 << 22
MICRO_BATCH = 1 << 14
STREAM_LANES = 8
KERNEL_ENTRIES = 1 << 22
BATCH_NS = (1, 3, 10, 17, 32, 50, 64, 100, 128, 200, 256, 333, 500, 512,
            750, 1000)


class PhaseLog:
    """One stdout line per phase; ``cache_hits`` counts the persistent
    compilation-cache hits since the previous line."""

    def __init__(self, counter):
        self.counter = counter
        self._seen = 0

    def emit(self, phase: str, result: str, cold: float,
             warm: float | None) -> None:
        hits, self._seen = self.counter.hits - self._seen, self.counter.hits
        warm_s = "not_run" if warm is None else f"{warm:.3f}"
        print(f"phase {phase}: {result} cold_s={cold:.3f} "
              f"warm_s={warm_s} cache_hits={hits}", flush=True)


def cold_warm(fn):
    """(first result, second result, cold seconds, warm seconds)."""
    t0 = time.perf_counter()
    a = fn()
    t1 = time.perf_counter()
    b = fn()
    t2 = time.perf_counter()
    return a, b, t1 - t0, t2 - t1


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"mismatch: {what}")


def top_n_reference(values, n: int):
    """Exact (values, indices) of the n largest, largest first."""
    vals = values.tolist()
    idx = heapq.nlargest(n, range(len(vals)), key=vals.__getitem__)
    return [vals[i] for i in idx], idx


# ------------------------------------------------------------------ phases
def phase_device(chips: int):
    """Returns (devices, PhaseLog); exits 2 when JAX finds no TPU."""
    import jax

    from repro import compile_cache

    cache_dir = compile_cache.enable()
    log = PhaseLog(compile_cache.CacheCounter())
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, found {len(devices)}")
    entries = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
               else 0)
    x = jax.numpy.arange(1024, dtype=jax.numpy.float32)
    _, _, cold, warm = cold_warm(
        lambda: float(jax.jit(lambda v: (v * 2).sum())(x)))
    log.emit("A device", f"platform={devices[0].platform} "
             f"kind={devices[0].device_kind!r} count={len(devices)} "
             f"compile_cache={cache_dir} entries_at_start={entries}", cold,
             warm)
    return devices, log


def phase_tpch(log, tables) -> None:
    from repro.query import SUITE

    li = tables["lineitem"]
    col_bytes = sum(int(c.nbytes) for c in li.cols.values())
    parts, cold_t, warm_t = [], 0.0, 0.0
    for q in SUITE:
        want = q.reference(tables)
        a, b, cold, warm = cold_warm(lambda: q.run(tables))
        check(a == want and b == want, f"{q.name} != reference")
        parts.append(f"{q.name}=ok({cold:.3f}/{warm:.3f})")
        cold_t, warm_t = cold_t + cold, warm_t + warm
    log.emit("B tpch_sf1", f"rows={li.num_rows} "
             f"lineitem_bytes={col_bytes} " + " ".join(parts), cold_t,
             warm_t)


def phase_batched(log, tables, ns=BATCH_NS) -> None:
    import numpy as np

    from repro.query import QuerySpec, run_queries, run_query

    li = tables["lineitem"]
    specs = [QuerySpec("topn", ("extprice",), dict(mode="det", N=n, w=8))
             for n in ns]
    a, b, cold, warm = cold_warm(lambda: run_queries(specs, li))
    exact_v, exact_i = top_n_reference(np.asarray(li.cols["extprice"]),
                                       max(ns))
    for spec, ga, gb in zip(specs, a, b):
        n = spec.params["N"]
        serial = run_query(spec, li)
        for g in (ga, gb):
            check(all(np.array_equal(np.asarray(x), np.asarray(y))
                      for x, y in zip(g["output"], serial["output"])),
                  f"run_queries N={n} != serial run_query")
            check(np.asarray(g["output"][0]).tolist() == exact_v[:n]
                  and np.asarray(g["output"][1]).tolist() == exact_i[:n],
                  f"N={n} != exact top-N")
    log.emit("C batched", f"queries={len(specs)} group_dispatches=1 "
             f"rows={li.num_rows} == serial == exact", cold, warm)


def phase_stream(log, tables, m=STREAM_ENTRIES, batch=MICRO_BATCH,
                 lanes=STREAM_LANES) -> None:
    import numpy as np

    from repro import core
    from repro.core.streaming import PruneStream, lane_view

    li = tables["lineitem"].cols
    cases = (("topn_det", np.asarray(li["extprice"])[:m], dict(N=100, w=8)),
             ("distinct", np.asarray(li["orderkey"])[:m], dict(d=4096, w=4)))
    parts, cold_t, warm_t = [], 0.0, 0.0
    for algo, values, params in cases:
        def run():
            stream = PruneStream(algo, shards=lanes, **params)
            for lo in range(0, m, batch):
                stream.fold(values[lo:lo + batch])
            res = stream.close()
            return np.asarray(res.keep), res.stats["batches"]
        (ka, na), (kb, nb), cold, warm = cold_warm(run)
        lv, valid, arrival = lane_view(algo, (values,), [batch] * (m // batch),
                                       lanes, **params)
        one = core.engine_prune(algo, *lv, mode="two_pass", shards=lanes,
                                **params)
        want = np.asarray(one.keep)[valid]
        check(na == nb == m // batch, f"{algo} folded {na}/{nb} batches")
        check(np.array_equal(ka[arrival[valid]], want)
              and np.array_equal(kb[arrival[valid]], want),
              f"stream {algo} close() != one-shot two_pass")
        parts.append(f"{algo}=ok(kept {int(ka.sum())}/{m}, "
                     f"{cold:.3f}/{warm:.3f})")
        cold_t, warm_t = cold_t + cold, warm_t + warm
    log.emit("D stream", f"folds={m // batch} lanes={lanes} "
             + " ".join(parts), cold_t, warm_t)


def phase_kernels(log, tables, m=KERNEL_ENTRIES) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    li = tables["lineitem"].cols
    ext = li["extprice"][:m]
    okey = li["orderkey"][:m]
    pts = jnp.stack([ext, li["quantity"][:m].astype(jnp.float32)], axis=-1)
    wts = li["revenue"][:m].astype(jnp.float32)
    # an RLE column of m entries: run lengths from quantity (1..50)
    lengths = np.asarray(li["quantity"])
    R = int(np.searchsorted(np.cumsum(lengths), m))
    runs_l = jnp.asarray(lengths[:R + 1]).at[R].add(
        m - int(lengths[:R + 1].sum()))
    runs_v = li["extprice"][:R + 1]
    table = ops.cms_build(okey, wts, rows=3, width=1024, use_ref=True)
    bits = ops.bloom_build(okey, nbits=1 << 15, use_ref=True)
    cases = {
        "topn_prune": lambda r: ops.topn_prune(ext, d=1024, w=4,
                                               use_ref=r),
        "distinct_prune": lambda r: ops.distinct_prune(okey, d=1024, w=4,
                                                       use_ref=r),
        "skyline_prune": lambda r: ops.skyline_prune(pts, w=16,
                                                     use_ref=r),
        "topn_prune_parallel": lambda r: ops.topn_prune_parallel(
            ext, d=1024, w=4, shards=8, use_ref=r),
        "distinct_prune_parallel": lambda r: ops.distinct_prune_parallel(
            okey, d=1024, w=4, shards=8, use_ref=r),
        "skyline_prune_parallel": lambda r: ops.skyline_prune_parallel(
            pts, w=16, shards=8, use_ref=r),
        "rle_topn_prune": lambda r: ops.rle_topn_prune(
            runs_v, runs_l, N=100, w=4, use_ref=r),
        "bloom_build": lambda r: ops.bloom_build(okey, nbits=1 << 15,
                                                 use_ref=r),
        "bloom_query": lambda r: ops.bloom_query(bits, okey, use_ref=r),
        "cms_build": lambda r: ops.cms_build(okey, wts, rows=3, width=1024,
                                             use_ref=r),
        "cms_query": lambda r: ops.cms_query(table, okey, use_ref=r),
    }
    approx = {"cms_build", "cms_query"}   # f32 sums: as tests/test_kernels
    host = lambda out: [np.asarray(o) for o in
                        (out if isinstance(out, tuple) else (out,))]
    parts, cold_t, warm_t = [], 0.0, 0.0
    for name, fn in cases.items():
        a, b, cold, warm = cold_warm(lambda: host(fn(False)))
        want = host(fn(True))
        same = (np.allclose if name in approx else np.array_equal)
        check(all(same(x, y) and same(z, y)
                  for x, z, y in zip(a, b, want)),
              f"kernel {name} != use_ref mirror")
        parts.append(f"{name}=ok({cold:.3f}/{warm:.3f})")
        cold_t, warm_t = cold_t + cold, warm_t + warm
    log.emit("E kernels", f"m={m} interpret={ops._interpret()} "
             + " ".join(parts), cold_t, warm_t)


def phase_mesh(log, tables, chips: int) -> None:
    """The multi-chip path: run_query / run_queries on a make_mesh mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.query import QuerySpec, Table, run_queries, run_query
    from repro.query import workloads

    mesh = jax.make_mesh((chips,), ("data",))
    li = tables["lineitem"]
    keep = np.asarray(li.cols["shipdate"]) <= workloads.Q1_SHIP_CUT
    q1 = Table("lineitem_q1", {
        "flag": jnp.asarray(np.asarray(li.cols["flag"])[keep]),
        "revenue": jnp.asarray(np.asarray(li.cols["revenue"])[keep])})
    q1_want = workloads.get("q1_pricing").reference(tables)
    exact_v, exact_i = top_n_reference(np.asarray(li.cols["extprice"]), 100)
    distinct_want = sorted(set(np.asarray(li.cols["orderkey"]).tolist()))
    topn = [QuerySpec("topn", ("extprice",), dict(mode="det", N=n, w=8))
            for n in (10, 100)]
    distinct = QuerySpec("distinct", ("orderkey",), dict(d=4096, w=4))
    groupby = QuerySpec("groupby", ("flag", "revenue"), dict(d=8, w=4))

    t0 = time.perf_counter()
    g = run_query(groupby, q1, mesh=mesh)
    tn = run_queries(topn, li, mesh=mesh)
    d1 = run_query(distinct, li, mesh=mesh)
    cold = time.perf_counter() - t0
    check({int(k): float(v) for k, v in g["output"].items()} == q1_want,
          "mesh Q1 GROUP BY != reference")
    for spec, r in zip(topn, tn):
        n = spec.params["N"]
        check(np.asarray(r["output"][0]).tolist() == exact_v[:n]
              and np.asarray(r["output"][1]).tolist() == exact_i[:n],
              f"mesh TOP-N N={n} != exact top-N")
    check(np.asarray(d1["output"]).tolist() == distinct_want,
          "mesh DISTINCT != set(orderkey)")
    placement = []
    for name, r in (("groupby", g), ("topn", tn[0]), ("distinct", d1)):
        held = r["report"].meta["stream_bytes_per_device"]
        check(len(held) == chips and min(held) > 0,
              f"{name} stream not spread over {chips} devices: {held}")
        mask_devs = sorted(s.device.id for s in r["keep"].addressable_shards)
        placement.append(f"{name}:stream_bytes_per_device={held}"
                         f",keep_mask_devices={mask_devs}")
    log.emit(f"M mesh{chips}", f"rows={li.num_rows} q1=ok topn=ok "
             "distinct=ok " + " ".join(placement), cold, None)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip mesh path")
    args = ap.parse_args()
    devices, log = phase_device(args.chips)

    from repro.query import workloads

    t0 = time.perf_counter()
    tables = workloads.tpch_tables(scale=SF1_ROWS, seed=0)
    print(f"setup: tpch_tables(scale={SF1_ROWS}) in "
          f"{time.perf_counter() - t0:.3f}s", flush=True)
    if args.chips == 4:
        phase_mesh(log, tables, 4)
    else:
        phase_tpch(log, tables)
        phase_batched(log, tables)
        phase_stream(log, tables)
        phase_kernels(log, tables)
    print(f"compile cache: hits={log.counter.hits} "
          f"writes={log.counter.writes}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
