"""Find the highest rate an open-loop cell sustains.

    python3 bench/sweep.py --workload <cell> --rates 8e5,1e6,1.2e6 --seconds 5

One process, one set-up; each rate runs the cell's traffic for
``--seconds`` on the same standing streams (reset between rates) and
prints one JSON line: latency percentiles, the delivered rate, and the
95th percentile in the window's first and last quarter (a backlog that
grows shows as a last quarter far above the first). A cell's fixed rate
is then written into its traffic file by hand; the benchmark's runs
never sweep.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    import jax
    import numpy as np

    from bench import harness, loops, systems

    spec = harness.load_spec()
    cell, _, cfg, traffic = harness.cell_parts(spec, args.workload)
    harness.device_info(jax, cell["chips"], True)
    rates = [float(r) for r in args.rates.split(",")]
    b = traffic["micro_batch"]
    n = (int(max(rates) * args.seconds) // b + 1) * b
    schema = importlib.import_module("bench.schemas." + cfg["schema"])
    events = schema.make_stream(cfg, args.seed, traffic["table"], n,
                                [s["column"] for s in traffic["streams"]])
    system = systems.ProgramStreams(traffic["streams"])
    harness.warm_streams(system, {c: v[:b] for c, v in events.items()})
    for rate in rates:
        system.reset()
        w = loops.open_loop(system, dict(traffic, rate_per_s=rate), events,
                            seconds=args.seconds)
        lat = w["latency_s"] * 1e3
        q = max(len(lat) // 4, 1)
        print(json.dumps({
            "rate": rate, "offered": w["offered"],
            "events_per_s": w["delivered"] / w["seconds"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p95_first_quarter_ms": float(np.percentile(lat[:q], 95)),
            "p95_last_quarter_ms": float(np.percentile(lat[-q:], 95)),
            "late_p95_ms": float(np.percentile(w["lateness_s"], 95) * 1e3),
            "fold_us_p50": float(np.median(w["fold_s"]) * 1e6),
            "streams": system.describe(),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
