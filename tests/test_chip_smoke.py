"""chip_smoke.py's phases at a small size on the CPU.

The script itself refuses to run without a TPU; its phases are plain
functions, so the same checks (every answer against an independent
reference) run here on TPC-H-shaped tables of 30,011 rows, the kernels
in interpret mode and the mesh phase on 4 of the test platform's host
devices.
"""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.compile_cache import CacheCounter  # noqa: E402
from repro.query import workloads  # noqa: E402


@pytest.fixture(scope="module")
def tables():
    return workloads.tpch_tables(scale=30_011, seed=0)


@pytest.fixture(scope="module")
def log():
    return chip_smoke.PhaseLog(CacheCounter())


@pytest.mark.parametrize("phase,kwargs", [
    ("phase_tpch", {}),
    ("phase_batched", dict(ns=(1, 7, 50, 100))),
    ("phase_stream", dict(m=1 << 14, batch=1 << 10)),
    ("phase_kernels", dict(m=1 << 12)),
    ("phase_mesh", dict(chips=4)),
])
def test_phase_passes_small(log, tables, capsys, phase, kwargs):
    getattr(chip_smoke, phase)(log, tables, **kwargs)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith("phase ") and "cold_s=" in line, line


def test_no_tpu_exits_nonzero_without_result():
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 2
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout
