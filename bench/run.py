"""Run one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (tables or events from the seed, the device, every shape of the
cell's mix warmed) is timed from the start of this process. The window
then measures for ``--seconds`` (``--trace 0``: the cell's end-to-end
metrics, profiler off) or runs the mix's short traced window
(``--trace 1``: the per-layer metrics). Afterwards every answer the
window produced is compared with the plain reference. The last line of
stdout is the JSON result; the numbers compared, each beside its limit,
are the last lines of stderr.

Exits 3, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for. Must run from a checkout that holds the program
(``src/repro``): it is what the benchmark measures.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness

    with harness.quiet_stdout() as stdout:
        try:
            out = harness.run_cell(args.workload, args.seed, args.seconds,
                                   bool(args.trace), t_start=T_START)
        except harness.NoChip as e:
            print(f"bench: {e}; nothing was run", file=sys.stderr)
            return 3
    harness.print_result(out, stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
