"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 [--program]

Runs the cell once per seed in one process, with the plain reference
computed in bfloat16 in the program's place (the control), or with
``--program`` the program itself, and prints each run's numbers
compared. The control has to come out not correct on every seed. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", action="store_true",
                    help="run the program instead of the control")
    args = ap.parse_args()

    from bench import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        with harness.quiet_stdout() as stdout:
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t_start=time.perf_counter(),
                                   control=not args.program)
        line = {"seed": seed, "who": "program" if args.program else "control",
                "correct": out["correct"], "attempted": out["attempted"],
                "compared": out["compared"]}
        print(json.dumps(line), file=stdout, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
