"""Core pruning algorithms: correctness vs oracles + paper bounds +
superset safety (the §7.2 reliability-protocol invariant) via hypothesis."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypstub import given, settings, st

from repro import core


def _dup_stream(rng, m=2000, D=200):
    base = rng.integers(1, 1 << 30, D).astype(np.uint32)
    return jnp.asarray(base[rng.integers(0, D, m)])


# ------------------------------------------------------------- DISTINCT
@pytest.mark.parametrize("policy", ["lru", "fifo"])
def test_distinct_no_false_positive(rng, policy):
    vals = _dup_stream(rng)
    r = core.distinct_prune(vals, d=64, w=4, policy=policy)
    opt = core.opt_keep_distinct(vals)
    # never prune a first occurrence
    assert bool(jnp.all(r.keep | ~opt))


def test_distinct_master_completion(rng):
    vals = _dup_stream(rng)
    r = core.distinct_prune(vals, d=32, w=2)
    got = core.master_complete_distinct(vals, r.keep)
    out = set(np.asarray(vals)[np.asarray(got)].tolist())
    assert out == set(np.asarray(vals).tolist())


def test_distinct_thm1_bound(rng):
    m, D, d, w = 60_000, 5_000, 1024, 4
    base = rng.integers(1, 1 << 30, D).astype(np.uint32)
    vals = jnp.asarray(base[rng.integers(0, D, m)])
    keep = core.distinct_prune(vals, d=d, w=w).keep
    opt = core.opt_keep_distinct(vals)
    dup_pruned = int(((~keep) & (~opt)).sum())
    frac = dup_pruned / int((~opt).sum())
    assert frac >= core.thm1_bound(D, d, w) * 0.9  # finite-sample slack


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(1, 50), min_size=4, max_size=120),
       st.integers(0, 1000))
def test_distinct_superset_safety(values, seed):
    """Q(S) == Q(D) for ANY S with A(D) ⊆ S ⊆ D (retransmission safety)."""
    vals = jnp.asarray(np.array(values, np.uint32))
    keep = np.asarray(core.distinct_prune(vals, d=8, w=2).keep)
    rs = np.random.default_rng(seed)
    extra = rs.random(len(values)) < 0.3
    superset = jnp.asarray(keep | extra)
    got = core.master_complete_distinct(vals, superset)
    out = set(np.asarray(vals)[np.asarray(got)].tolist())
    assert out == set(values)


# ---------------------------------------------------------------- TOP-N
def test_topn_rand_exact(rng):
    m, N = 20_000, 64
    v = jnp.asarray(rng.permutation(m).astype(np.float32) + 1)
    w = core.thm2_w(512, N, 1e-4)
    keep = core.topn_rand_prune(v, d=512, w=w).keep
    topv, _ = core.master_complete_topn(v, keep, N)
    assert np.allclose(np.sort(np.asarray(topv)),
                       np.sort(np.asarray(v))[-N:])


def test_topn_det_exact(rng):
    m, N = 20_000, 100
    v = jnp.asarray((rng.random(m) * 1e6 + 1).astype(np.float32))
    keep = core.topn_det_prune(v, N=N, w=6).keep
    topv, _ = core.master_complete_topn(v, keep, N)
    assert np.allclose(np.sort(np.asarray(topv)),
                       np.sort(np.asarray(v))[-N:])


def test_topn_thm3_bound(rng):
    m, N, d = 100_000, 100, 1024
    w = core.thm2_w(d, N, 1e-4)
    v = jnp.asarray(rng.permutation(m).astype(np.float32) + 1)
    keep = core.topn_rand_prune(v, d=d, w=w).keep
    assert int(keep.sum()) <= core.thm3_forwarded_bound(m, d, w)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 12), st.integers(13, 200))
def test_topn_det_superset_always(N, m):
    rs = np.random.default_rng(N * 1000 + m)
    v = jnp.asarray((rs.random(m) * 100 + 1).astype(np.float32))
    keep = core.topn_det_prune(v, N=N, w=5).keep
    topv, _ = core.master_complete_topn(v, keep, N)
    assert np.allclose(np.sort(np.asarray(topv)),
                       np.sort(np.asarray(v))[-N:])


# ----------------------------------------------------------------- JOIN
def test_join_exact(rng):
    ka = jnp.asarray(rng.integers(0, 300, 1500).astype(np.uint32))
    kb = jnp.asarray(rng.integers(150, 450, 1500).astype(np.uint32))
    va = jnp.arange(1500, dtype=jnp.int32)
    vb = jnp.arange(1500, dtype=jnp.int32)
    ra, rb = core.join_prune(ka, kb, nbits=4096)
    assert core.master_complete_join(ka, va, ra.keep, kb, vb, rb.keep) \
        == core.join_oracle(ka, va, kb, vb)


def test_join_asymmetric_small_table_first(rng):
    small = jnp.asarray(rng.integers(0, 50, 200).astype(np.uint32))
    large = jnp.asarray(rng.integers(0, 5000, 5000).astype(np.uint32))
    rs, rl = core.join_prune_asymmetric(small, large, nbits=2048)
    assert bool(jnp.all(rs.keep))  # small table unpruned
    out = core.master_complete_join(small, small, rs.keep, large, large,
                                    rl.keep)
    assert out == core.join_oracle(small, small, large, large)


# --------------------------------------------------------------- HAVING
def test_having_exact(rng):
    keys = jnp.asarray(rng.integers(0, 60, 4000).astype(np.uint32))
    vals = jnp.asarray(rng.integers(1, 9, 4000).astype(np.int32))
    thr = 250
    r = core.having_prune(keys, vals, thr, rows=3, width=256)
    assert core.master_complete_having(keys, vals, r.keep, thr) \
        == core.having_oracle(keys, vals, thr)


def test_having_count(rng):
    keys = jnp.asarray(rng.integers(0, 40, 3000).astype(np.uint32))
    r = core.having_prune(keys, None, 80, rows=3, width=256, agg="count")
    got = core.master_complete_having(keys, None, r.keep, 80, "count")
    assert got == core.having_oracle(keys, jnp.ones_like(keys, jnp.int32), 80,
                                     "count")


# -------------------------------------------------------------- SKYLINE
@pytest.mark.parametrize("score", ["aph", "sum"])
def test_skyline_never_prunes_skyline(rng, score):
    pts = jnp.asarray(rng.integers(1, 500, (1500, 3)).astype(np.float32))
    r = core.skyline_prune(pts, w=8, score=score)
    sky = core.skyline_oracle(pts)
    assert bool(jnp.all(r.keep | ~sky))
    got = core.master_complete_skyline(pts, r.keep)
    assert bool(jnp.all(got == sky))


def test_skyline_aph_score_monotone(rng):
    x = jnp.asarray(rng.integers(1, 1 << 16, (500, 4)).astype(np.float32))
    y = x + jnp.asarray(rng.integers(0, 100, (500, 4)).astype(np.float32))
    assert bool(jnp.all(core.score_aph(y) >= core.score_aph(x)))
    # piecewise-linear log2 error bound (~0.086 abs per dim)
    true = jnp.sum(jnp.log2(x), -1)
    assert float(jnp.max(jnp.abs(core.score_aph(x) - true))) < 0.09 * 4


# -------------------------------------------------------------- GROUPBY
@pytest.mark.parametrize("agg", ["sum", "count", "min", "max"])
def test_groupby_exact(rng, agg):
    keys = jnp.asarray(rng.integers(0, 50, 3000).astype(np.uint32))
    vals = jnp.asarray(rng.integers(1, 100, 3000).astype(np.int32))
    r = core.groupby_prune(keys, vals, d=16, w=4, agg=agg)
    got = core.master_complete_groupby(r, agg)
    want = core.groupby_oracle(keys, vals, agg)
    assert set(got) == set(want)
    for k in want:
        assert abs(got[k] - want[k]) < 1e-2 * max(1, abs(want[k]))


@pytest.mark.parametrize("path", ["run_query", "run_queries"])
def test_groupby_integer_sum_exact_past_2_24(path):
    """An int32 value column aggregates in an int32 state: 2^19 entries
    of 50 in one group sum to 26,214,400, past f32's exact 2^24, and
    the serial and the batched path both give Python's exact sum."""
    from repro.query import QuerySpec, Table, run_queries, run_query

    m = 1 << 19
    table = Table("t", {"k": jnp.zeros(m, jnp.uint32),
                        "v": jnp.full(m, 50, jnp.int32)})
    specs = [QuerySpec("groupby", ("k", "v"), dict(d=8, w=4)),
             QuerySpec("groupby", ("k", "v"), dict(d=16, w=2))]
    outs = ([run_query(specs[0], table)] if path == "run_query"
            else run_queries(specs, table))
    want = {0: sum([50] * m)}
    assert want[0] > 1 << 24
    for out in outs:
        assert out["output"] == want
        assert all(isinstance(v, int) for v in out["output"].values())


# --------------------------------------------------------------- FILTER
def test_filter_decomposition(rng):
    cols = {"taste": jnp.asarray(rng.integers(0, 11, 500)),
            "texture": jnp.asarray(rng.integers(0, 11, 500)),
            "name_like": jnp.asarray(rng.integers(0, 2, 500))}
    f = core.Or((core.Pred("taste", "gt", 5),
                 core.And((core.Pred("texture", "gt", 4),
                           core.Pred("name_like", "eq", 1,
                                     switch_supported=False)))))
    pr = core.filter_prune(f, cols)
    final = core.master_complete_filter(f, cols, pr.keep)
    assert bool(jnp.all(final == core.evaluate(f, cols)))
    # the relaxed formula is exactly the paper's: taste>5 OR texture>4
    relaxed = core.evaluate(core.relax(f), cols)
    assert bool(jnp.all(pr.keep == relaxed))


def test_filter_truthtable_matches_direct(rng):
    cols = {"a": jnp.asarray(rng.integers(0, 10, 300)),
            "b": jnp.asarray(rng.integers(0, 10, 300))}
    f = core.And((core.Pred("a", "ge", 3), core.Or((
        core.Pred("b", "lt", 7), core.Pred("a", "eq", 9)))))
    assert bool(jnp.all(core.evaluate_truthtable(f, cols)
                        == core.evaluate(f, cols)))


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10), st.integers(0, 10))
def test_filter_relax_is_implied(ta, tb):
    """relax(f) must be implied by f (monotone weakening)."""
    cols = {"a": jnp.arange(20), "b": jnp.arange(20)[::-1]}
    f = core.And((core.Pred("a", "gt", ta),
                  core.Pred("b", "gt", tb, switch_supported=False)))
    full = core.evaluate(f, cols)
    relaxed = core.evaluate(core.relax(f), cols)
    assert bool(jnp.all(relaxed | ~full))


# --------------------------------------------------------------- COMPACT
@pytest.mark.parametrize("shape", [(301,), (301, 4)])
def test_compact_cumsum_matches_argsort(rng, shape):
    """The O(m) scatter compact must byte-match the sort-based one."""
    v = jnp.asarray(rng.integers(0, 999, shape).astype(np.int32))
    keep = jnp.asarray(rng.random(shape[0]) < 0.35)
    a, ca = core.compact(v, keep, fill=-7)
    b, cb = core.compact_argsort(v, keep, fill=-7)
    assert int(ca) == int(cb) == int(keep.sum())
    assert bool(jnp.all(a == b))


def test_compact_preserves_stable_order(rng):
    v = jnp.arange(50, dtype=jnp.int32)
    keep = jnp.asarray(rng.random(50) < 0.5)
    out, count = core.compact(v, keep)
    kept = np.asarray(v)[np.asarray(keep)]
    np.testing.assert_array_equal(np.asarray(out)[: int(count)], kept)


def test_compact_all_and_none():
    v = jnp.arange(8, dtype=jnp.int32) + 1
    out, count = core.compact(v, jnp.ones(8, bool))
    assert int(count) == 8 and bool(jnp.all(out == v))
    out, count = core.compact(v, jnp.zeros(8, bool), fill=0)
    assert int(count) == 0 and bool(jnp.all(out == 0))
