"""The program's host spans and the device's idle intervals, read from
a profiler trace (``.xplane.pb``), and the span metrics they give.

The program names its spans ``<entry>.<stage>`` (``suite.q3.master``,
``engine_prune.to_host``, ``prune_stream.prepare``, ...) on the
profiler's clock, one of the entries in ``PROGRAM``; the harness's own
spans start ``bench.``, ``query.`` or ``stream.``, and JAX's runtime
adds its own (``PjitFunction(...)``, ``DevicePut``, ...).
``reduce_trace.reduce_file`` returns the spans of the thread that ran
the window's queries or folds, clipped to the window, and each device's
idle intervals in it; ``read`` returns that reduction. The readers below
select these stages of the program's spans:

* ``to_host``: a device->host copy the master needs;
* ``master``: host completion;
* ``to_device``: a host->device upload on the query path.

Each reader returns None where the trace holds none of what it reads
(a program that opens no spans). The harness's ``metrics/*.py`` call
these readers on its own reduction of each traced run.

    python3 bench/spans.py read <trace.xplane.pb>
    python3 bench/spans.py record --workload <cell> --seed <n>
        [--scale <f>] [--set <traffic key>=<number> ...]
        [--out <trace.xplane.pb>]

``record`` runs the cell's traced window through the harness (on a
chip; ``--scale`` as the tests scale a configuration, ``--set`` to
shorten the mix for a small trace, e.g. ``trace_seconds=0.05``, or
``trace_lead_s=0.3 --set trace_cut_s=0.1`` for a sliced closed loop),
prints the harness's per-layer metrics (the span metrics among them, as
its ``metrics/*.py`` read them) and breakdown beside these readings,
and keeps the trace at ``--out``.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys

import numpy as np

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import reduce_trace  # noqa: E402

PROGRAM = ("suite.", "engine_prune.", "engine_prune_batch.",
           "prune_stream.", "run_query.", "planner.")
WINDOW = "bench.window"


def read(path: str, window: str = WINDOW) -> dict:
    """The trace's reduction: ``{"window_s", "spans": [(name, start_s,
    end_s)], "gaps": [[(start_s, end_s)] per device], "gap_in_program",
    ...}``, times from the window's start."""
    return reduce_trace.reduce_file(path, {}, window)


def program(name: str) -> bool:
    """A span the program opened (not the harness, not JAX)."""
    return name.startswith(PROGRAM)


def stage(name: str) -> str:
    return name.rsplit(".", 1)[-1]


def _merged(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap_s(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def host_bound_idle_share(t: dict) -> float | None:
    """Share of the window, in percent, in which no operation runs on
    the device, no device program is running either, and the host is
    inside a program span (mean over devices). The rest of the idle time
    is the device's own (the gaps between one program's operations) or
    the harness's: arrival waits, the loop between queries."""
    busy_host = _merged((s, e) for n, s, e in t["spans"] if program(n))
    if not busy_host:
        return None
    shares = [_overlap_s(busy_host, [g for g, inside in zip(gs, ins)
                                     if not inside]) / t["window_s"]
              for gs, ins in zip(t["gaps"], t["gap_in_program"])]
    return 100.0 * float(np.mean(shares))


def queries(t: dict) -> int:
    """Queries the harness ran in the window (its ``query.*`` spans)."""
    return sum(1 for n, _, _ in t["spans"] if n.startswith("query."))


def stage_ms_per_query(t: dict, name: str) -> float | None:
    """Host time inside the program's ``*.<name>`` spans (their union,
    so a span nested in another of the stage counts once), over the
    queries of the window."""
    sel = _merged((s, e) for n, s, e in t["spans"]
                  if program(n) and stage(n) == name)
    n = queries(t)
    if not sel or not n:
        return None
    return sum(e - s for s, e in sel) / n * 1e3


def readings(t: dict) -> dict:
    """Every reader, and the host seconds of each program span name."""
    by_name: dict = {}
    for n, s, e in t["spans"]:
        if program(n):
            c, d = by_name.get(n, (0, 0.0))
            by_name[n] = (c + 1, d + e - s)
    idle = float(np.mean([sum(b - a for a, b in g) for g in t["gaps"]]))
    return {
        "window_s": t["window_s"],
        "device_idle_share": 100.0 * idle / t["window_s"],
        "host_bound_idle_share": host_bound_idle_share(t),
        "queries": queries(t),
        "master_ms_per_query": stage_ms_per_query(t, "master"),
        "to_host_ms_per_query": stage_ms_per_query(t, "to_host"),
        "to_device_ms_per_query": stage_ms_per_query(t, "to_device"),
        "spans": {n: {"count": c, "s": d}
                  for n, (c, d) in sorted(by_name.items())},
    }


def _record(args) -> dict:
    """One traced run of a cell through the harness; the trace is read
    (and kept at ``args.out``) before the harness reduces it."""
    import time

    from bench import harness

    got: dict = {}
    reduce_dir = harness.reduce_trace.reduce_dir
    cell_parts = harness.cell_parts
    sets = {}
    for kv in args.set:
        key, value = kv.split("=", 1)
        sets[key] = int(value) if value.isdigit() else float(value)

    def keep_and_reduce(trace_dir, layers, window):
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        if args.out:
            shutil.copy(path, args.out)
        reduced = reduce_dir(trace_dir, layers, window)
        got.update(readings(reduced))
        return reduced

    def parts(spec, workload):
        cell, centry, cfg, traffic = cell_parts(spec, workload)
        return cell, centry, cfg, dict(traffic, **sets)

    harness.reduce_trace.reduce_dir, harness.cell_parts = \
        keep_and_reduce, parts
    try:
        with harness.quiet_stdout():
            out = harness.run_cell(args.workload, args.seed, 0.0, True,
                                   t_start=time.perf_counter(),
                                   scale=args.scale)
    finally:
        harness.reduce_trace.reduce_dir, harness.cell_parts = \
            reduce_dir, cell_parts
    return {"workload": args.workload, "seed": args.seed,
            "correct": out["correct"], "metrics": out["metrics"],
            "device": out["device"], "breakdown": out["breakdown"],
            "spans": got}


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("read")
    r.add_argument("path")
    rec = sub.add_parser("record")
    rec.add_argument("--workload", required=True)
    rec.add_argument("--seed", type=int, required=True)
    rec.add_argument("--scale", type=float)
    rec.add_argument("--set", action="append", default=[])
    rec.add_argument("--out")
    args = ap.parse_args()
    out = (readings(read(args.path)) if args.cmd == "read"
           else _record(args))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
