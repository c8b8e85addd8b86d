"""BENCHMARK.json against the benchmark's own files: every name it uses
is found, and the limits the contract sets hold."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench import harness

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_parts_found(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
    _, centry, cfg, traffic = harness.cell_parts(SPEC, cell["name"])
    assert cfg["name"] == centry["name"] and cfg["chips"] == cell["chips"]
    assert set(centry["reduced"]) == set(cfg["reduced"])
    assert traffic["loop"] in ("closed", "open")
    reported = {m["name"] for m in harness.cell_metrics(SPEC, cell["name"],
                                                         False)}
    assert "setup_s" in reported and len(reported) >= 2
    assert harness.cell_metrics(SPEC, cell["name"], True)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_and_fields(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert callable(harness.load_metric(metric["name"]).read)
    cells = {c["name"] for c in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        ends = {m["name"] for m in SPEC["end_to_end"]}
        assert metric["moves"] in ends


def test_layers_named_in_layer_map_exist():
    layers = harness.load_layers()
    assert "engine pass 1" in layers
    for patterns in layers.values():
        for p in patterns:
            re.compile(p)


def test_peaks_keyed_by_device_kind():
    peaks = harness.load_peaks()
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
