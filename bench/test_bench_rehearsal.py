"""Each cell's traffic driven for about a second at a tiny scale on the
CPU, through the harness's functions (the look for a chip skipped):
the result line has its keys, the program's answers are correct, and
``correct`` turns false when the timed path is broken or when the
bfloat16 control stands in for the program."""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness, systems

SCALE = {"tpch_sf01.q1": 0.02, "tpch_sf1.q3_q6": 0.002}
CELLS = [c["name"] for c in harness.load_spec()["workloads"]]
SEED = 2**31 + 12345


def run(cell, wrap=None, control=False, seconds=0.5):
    return harness.run_cell(cell, SEED, seconds, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            use_cache=False, scale=SCALE.get(cell, 1.0),
                            wrap=wrap, control=control, log=lambda *_: None)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_result_line_and_correct(cell, capsys):
    out = run(cell)
    buf = io.StringIO()
    harness.print_result(out, buf)
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    want = {m["name"] for m in harness.cell_metrics(
        harness.load_spec(), cell, False)}
    assert set(line["metrics"]) == want
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-len(line["compared"]):] == [
        f"compared {k}={v['value']} limit={v['limit']}"
        for k, v in line["compared"].items()]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(systems.FAULTS))
def test_broken_timed_path_is_not_correct(cell, fault):
    out = run(cell, wrap=systems.FAULTS[fault])
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["compared"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(cell):
    out = run(cell, control=True)
    assert out["correct"] is False


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
