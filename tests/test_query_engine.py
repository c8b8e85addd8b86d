"""Distributed query engine: pruned results == direct query results.

Multi-worker correctness runs in a subprocess with 8 host devices so the
main test process keeps its single-device view (see dryrun.py note)."""
import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro import core
from repro.query import (QuerySpec, Table, make_products_ratings,
                         make_rankings, make_uservisits, run_query)


def test_running_example_products_ratings():
    """The paper's Table 1 example: DISTINCT seller; JOIN on name."""
    products, ratings = make_products_ratings()
    r = run_query(QuerySpec("distinct", ("seller",), dict(d=8, w=2)), products)
    assert set(np.asarray(r["output"]).tolist()) == {1, 2, 3}
    j = run_query(QuerySpec("join", ("name", "name"), dict(
        nbits=256, payload_a="price", payload_b="taste")),
        (products, ratings))
    # inner join: 4 of 5 rating names match (Cheetos doesn't)
    assert len(j["output"]) == 4
    assert j["forwarded"] < j["total"]  # Cheetos pruned


def test_engine_matches_oracles(rng):
    uv = make_uservisits(20_000, seed=3)
    r = run_query(QuerySpec("distinct", ("source_ip",), dict(d=256, w=4)), uv)
    truth = np.unique(np.asarray(uv.cols["source_ip"]))
    assert set(np.asarray(r["output"]).tolist()) == set(truth.tolist())

    r = run_query(QuerySpec("topn", ("ad_revenue",),
                            dict(d=512, w=6, N=100)), uv)
    true = np.sort(np.asarray(uv.cols["ad_revenue"]))[-100:]
    assert np.allclose(np.sort(r["output"][0]), true)

    r = run_query(QuerySpec("groupby", ("lang", "ad_revenue"),
                            dict(d=16, w=4, agg="sum")), uv)
    want = core.groupby_oracle(uv.cols["lang"], uv.cols["ad_revenue"], "sum")
    assert set(r["output"]) == set(want)


_MULTIWORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
import jax.numpy as jnp
from repro.query import QuerySpec, make_uservisits, make_rankings, run_query
from repro import core

mesh = jax.make_mesh((8,), ("data",))
uv = make_uservisits(16000, seed=9)
rk = make_rankings(8000, seed=10)
out = {}

r = run_query(QuerySpec("distinct", ("source_ip",), dict(d=128, w=4)), uv,
              mesh=mesh)
truth = set(np.unique(np.asarray(uv.cols["source_ip"])).tolist())
out["distinct_ok"] = set(np.asarray(r["output"]).tolist()) == truth
out["distinct_pruned"] = r["pruned_fraction"]

r = run_query(QuerySpec("topn", ("ad_revenue",), dict(d=256, w=8, N=50)), uv,
              mesh=mesh)
true = np.sort(np.asarray(uv.cols["ad_revenue"]))[-50:]
out["topn_ok"] = bool(np.allclose(np.sort(r["output"][0]), true))

r = run_query(QuerySpec("join", ("dest_url", "page_url"), dict(
    nbits=1 << 14, payload_a="duration", payload_b="avg_duration")),
    (uv, rk), mesh=mesh)
na, nb = 16000, 8000
oracle = core.join_oracle(uv.cols["dest_url"][:na], uv.cols["duration"][:na],
                          rk.cols["page_url"][:nb], rk.cols["avg_duration"][:nb])
out["join_ok"] = r["output"] == oracle

r = run_query(QuerySpec("having", ("lang", "ad_revenue"), dict(
    threshold=20000.0, rows=3, width=512)), uv, mesh=mesh)
want = core.having_oracle(uv.cols["lang"],
                          uv.cols["ad_revenue"].astype(jnp.int32), 20000)
got = sorted(r["output"])
out["having_ok"] = got == want

r = run_query(QuerySpec("groupby", ("lang", "ad_revenue"), dict(
    d=16, w=4, agg="sum")), uv, mesh=mesh)
want = core.groupby_oracle(uv.cols["lang"], uv.cols["ad_revenue"], "sum")
out["groupby_ok"] = set(r["output"]) == set(want) and all(
    abs(r["output"][k] - want[k]) < 1e-2 * max(1, abs(want[k])) for k in want)

print("RESULT:" + json.dumps(out))
"""


def test_multiworker_8_devices():
    proc = subprocess.run([sys.executable, "-c", _MULTIWORKER],
                          capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, PYTHONPATH="src"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    out = json.loads(line[0][len("RESULT:"):])
    for k, v in out.items():
        if k.endswith("_ok"):
            assert v, f"{k} failed: {out}"
    assert out["distinct_pruned"] > 0.5


# --------------------------------------------------- multi-query batching
def _results_equal(a, b):
    if a["forwarded"] != b["forwarded"] or a["total"] != b["total"]:
        return False
    x, y = a["output"], b["output"]
    if isinstance(x, tuple):
        return all(np.array_equal(np.asarray(p), np.asarray(q))
                   for p, q in zip(x, y))
    if isinstance(x, dict):
        return set(x) == set(y) and all(np.allclose(x[k], y[k]) for k in x)
    return np.array_equal(np.asarray(x), np.asarray(y))


def _multiq_specs():
    return [
        QuerySpec("topn", ("ad_revenue",), dict(mode="det", N=40, w=4)),
        QuerySpec("distinct", ("source_ip",), dict(d=128, w=4)),
        QuerySpec("topn", ("ad_revenue",), dict(mode="det", N=10, w=6)),
        QuerySpec("distinct", ("source_ip",), dict(d=64, w=2)),
        QuerySpec("topn", ("ad_revenue",), dict(mode="rand", d=256,
                                                w=8, N=25)),
        QuerySpec("groupby", ("lang", "ad_revenue"), dict(d=16, w=2)),
        QuerySpec("groupby", ("lang", "ad_revenue"), dict(d=8, w=4)),
        QuerySpec("having", ("lang", "ad_revenue"),
                  dict(threshold=20000.0, rows=2, width=256)),
        QuerySpec("having", ("lang", "ad_revenue"),
                  dict(threshold=5000.0, rows=3, width=512)),
    ]


def test_run_queries_matches_serial_loop():
    """Mixed specs grouped into batches come back in input order with
    results identical to a per-spec run_query loop (meshless default)."""
    from repro.query import run_queries

    uv = make_uservisits(8000, seed=11)
    specs = _multiq_specs()
    got = run_queries(specs, uv)
    assert len(got) == len(specs)
    for spec, g in zip(specs, got):
        assert _results_equal(g, run_query(spec, uv)), spec


def test_run_queries_budget_waves_match():
    from repro.query import run_queries

    uv = make_uservisits(4000, seed=12)
    specs = _multiq_specs()
    free = run_queries(specs, uv)
    tight = run_queries(specs, uv, device_budget_bytes=1 << 14)
    for spec, a, b in zip(specs, free, tight):
        assert _results_equal(a, b), spec


_MULTIQ_MESH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
from repro.query import QuerySpec, make_uservisits, run_query, run_queries

mesh = jax.make_mesh((8,), ("data",))
uv = make_uservisits(8000, seed=13)
specs = [
    QuerySpec("topn", ("ad_revenue",), dict(mode="det", N=40, w=4)),
    QuerySpec("topn", ("ad_revenue",), dict(mode="det", N=10, w=6)),
    QuerySpec("distinct", ("source_ip",), dict(d=128, w=4)),
    QuerySpec("distinct", ("source_ip",), dict(d=64, w=2)),
]
got = run_queries(specs, uv, mesh=mesh)
ok = True
for spec, g in zip(specs, got):
    r = run_query(spec, uv, mesh=mesh)
    ok &= g["forwarded"] == r["forwarded"] and g["total"] == r["total"]
    x, y = g["output"], r["output"]
    if isinstance(x, tuple):
        ok &= all(np.array_equal(np.asarray(p), np.asarray(q))
                  for p, q in zip(x, y))
    else:
        ok &= np.array_equal(np.asarray(x), np.asarray(y))
print("RESULT:" + json.dumps({"multiq_mesh_ok": bool(ok)}))
"""


def test_run_queries_mesh_8_devices():
    """Batched groups cross the mesh path (one shard_map + one fused
    collective per group) with results equal to the serial mesh loop."""
    proc = subprocess.run([sys.executable, "-c", _MULTIQ_MESH],
                          capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, PYTHONPATH="src"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    out = json.loads(line[0][len("RESULT:"):])
    assert out["multiq_mesh_ok"]


def test_readme_mesh_example_runs():
    """README's run_query-on-a-mesh snippet runs as written (it builds
    its mesh with jax.make_mesh, whose axes are Explicit) and returns
    the exact top-100."""
    import pathlib
    import re

    readme = (pathlib.Path(__file__).parents[1] / "README.md").read_text()
    block = next(b for b in re.findall(r"```python\n(.*?)```", readme, re.S)
                 if "jax.make_mesh" in b and "run_query(" in b)
    scope: dict = {}
    exec(block, scope)
    res, uv = scope["res"], scope["uv"]
    want = np.sort(np.asarray(uv.cols["ad_revenue"]))[-100:]
    assert np.array_equal(np.sort(np.asarray(res["output"][0])), want)
    assert 0 < res["pruned_fraction"] < 1


# ------------------------------------------------ the meshless default
_M = 5000  # ceil(sqrt(5000)) = 71 lanes, which do not divide 5000


def _algo_table():
    rs = np.random.default_rng(21)
    return Table("t", {
        "k": jnp.asarray(rs.integers(0, 300, _M).astype(np.uint32)),
        "n": jnp.asarray(rs.integers(1, 50, _M).astype(np.int32)),
        "v": jnp.asarray((rs.permutation(_M) + 1).astype(np.float32)),
        "a": jnp.asarray(rs.random(_M).astype(np.float32)),
        "b": jnp.asarray(rs.random(_M).astype(np.float32)),
    })


# two specs a kind, so run_queries batches them into one group
_ALGO_SPECS = {
    "distinct": [QuerySpec("distinct", ("k",), dict(d=64, w=4)),
                 QuerySpec("distinct", ("k",), dict(d=32, w=2))],
    "topn_det": [QuerySpec("topn", ("v",), dict(mode="det", N=50, w=4)),
                 QuerySpec("topn", ("v",), dict(mode="det", N=20, w=6))],
    "topn_rand": [QuerySpec("topn", ("v",), dict(d=256, w=8, N=20)),
                  QuerySpec("topn", ("v",), dict(d=128, w=8, N=10))],
    "skyline": [QuerySpec("skyline", ("a", "b"), dict(w=8)),
                QuerySpec("skyline", ("a", "b"), dict(w=4))],
    "groupby": [QuerySpec("groupby", ("k", "n"), dict(d=16, w=4)),
                QuerySpec("groupby", ("k", "n"), dict(d=8, w=2))],
    "having": [QuerySpec("having", ("k", "n"),
                         dict(threshold=500.0, rows=3, width=256)),
               QuerySpec("having", ("k", "n"),
                         dict(threshold=700.0, rows=2, width=128))],
}


def _oracle(spec, t):
    c, p = t.cols, spec.params
    if spec.kind == "distinct":
        return set(np.unique(np.asarray(c["k"])).tolist())
    if spec.kind == "topn":
        return np.sort(np.asarray(c["v"]))[-p["N"]:]
    if spec.kind == "skyline":
        return np.asarray(core.skyline_oracle(
            jnp.stack([c["a"], c["b"]], axis=-1)))
    if spec.kind == "groupby":
        return core.groupby_oracle(c["k"], c["n"], "sum")
    return core.having_oracle(c["k"], c["n"], p["threshold"])


def _answer(spec, out):
    if spec.kind == "distinct":
        return set(np.asarray(out).tolist())
    if spec.kind == "topn":
        return np.sort(np.asarray(out[0]))
    if spec.kind == "having":
        return sorted(out)
    return out if spec.kind == "groupby" else np.asarray(out)


@pytest.mark.parametrize("path", ["run_query", "run_queries"])
@pytest.mark.parametrize("algo", sorted(_ALGO_SPECS))
def test_meshless_default_matches_oracle(algo, path):
    """Every single-table algorithm, at an m its lane count does not
    divide, answers exactly through both entry points, on two-pass at
    the rule's lane count (HAVING: one lane, the scan)."""
    from repro.query import run_queries

    t = _algo_table()
    specs = _ALGO_SPECS[algo]
    lanes = core.meshless_lanes(algo, _M)
    assert _M % lanes or (algo == "having" and lanes == 1)
    outs = ([run_query(s, t) for s in specs] if path == "run_query"
            else run_queries(specs, t))
    for spec, out in zip(specs, outs):
        want, got = _oracle(spec, t), _answer(spec, out["output"])
        if isinstance(want, np.ndarray):
            np.testing.assert_array_equal(got, want)
        else:
            assert got == want, spec
        assert out["report"].meta["mode"] == "two_pass"
        assert out["report"].meta["shards"] == lanes


def test_meshless_default_reports_rule_lanes():
    """The engagement counters: a meshless run_query runs two-pass on
    exactly meshless_lanes(m) lanes (Q1's m: 770 lanes)."""
    t = _algo_table()
    r = run_query(_ALGO_SPECS["groupby"][0], t)
    assert r["report"].meta["mode"] == "two_pass"
    assert r["report"].meta["shards"] == core.meshless_lanes("groupby", _M)
    assert core.meshless_lanes("groupby", 591_788) == 770


def test_meshless_default_takes_no_timing(monkeypatch):
    """The lane rule reads no clock: with merge-cost calibration and the
    engine's timer made to raise, a meshless query still returns."""
    from repro.core import engine

    def boom(*a, **k):
        raise AssertionError("the meshless default timed something")

    monkeypatch.setattr(engine, "calibrate_merge_cost", boom)
    monkeypatch.setattr(engine, "_time_us", boom)
    t = _algo_table()
    for algo in ("groupby", "distinct"):
        spec = _ALGO_SPECS[algo][0]
        assert _answer(spec, run_query(spec, t)["output"]) \
            == _oracle(spec, t)


@pytest.mark.parametrize("algo", ["distinct", "skyline"])
def test_meshless_default_chunks_a_large_apply(algo, monkeypatch):
    """Where the unchunked [S, n, S·w(, D)] compare exceeds the element
    cap, the default applies in blocks within it; the mask equals the
    unchunked two-pass mask at the same lanes."""
    from repro.core import engine, planner

    cap = 1 << 17
    monkeypatch.setattr(planner, "APPLY_CHUNK_ELEMENTS", cap)
    calls = []
    real = engine._apply_chunked

    def spy(*a, **k):
        calls.append(a[-1])
        return real(*a, **k)

    monkeypatch.setattr(engine, "_apply_chunked", spy)
    t = _algo_table()
    spec = _ALGO_SPECS[algo][0]
    lanes = core.meshless_lanes(algo, _M)
    n = -(-_M // lanes)
    dims = 2 if algo == "skyline" else 1
    per_entry = lanes * lanes * spec.params["w"] * dims
    assert n * per_entry > cap  # unchunked would exceed the cap
    r = run_query(spec, t)
    assert len(calls) == 1 and calls[0] < n
    assert calls[0] * per_entry <= cap
    stream = (jnp.stack([t.cols["a"], t.cols["b"]], axis=-1)
              if algo == "skyline" else t.cols["k"])
    whole = core.engine_prune(algo, stream, mode="two_pass",
                               shards=lanes, obs="off", **spec.params)
    assert len(calls) == 1  # the reference ran unchunked
    np.testing.assert_array_equal(np.asarray(r["keep"]),
                                  np.asarray(whole.keep))
    want, got = _oracle(spec, t), _answer(spec, r["output"])
    if isinstance(want, np.ndarray):
        np.testing.assert_array_equal(got, want)
    else:
        assert got == want
