"""Spans on the profiler's clock, and stable program names.

* Under ``jax.profiler.trace`` the program's stage spans
  (``suite.q3.master``, ``engine_prune.to_host``,
  ``prune_stream.prepare``, ...) appear in the trace on the calling
  thread, nested inside their entry, at ``obs="counters"`` and
  ``"trace"``, and not at all at ``"off"``.
* Every ``PruneStream`` program compiles under its own name
  (``jit_distinct_stream_fold``, ``jit_topn_det_stream_merge``, ...),
  which is what a profiler trace reports it by.
* Answers and masks are bit-identical at every obs level.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import PruneStream, obs
from repro.query import QuerySpec, make_uservisits, run_query, workloads

LEVELS = ("off", "counters", "trace")


@pytest.fixture
def level():
    """Sets the process-wide obs level; restores the default after."""
    was = obs.default_level()
    yield obs.set_default_level
    obs.set_default_level(was)


def _spans(trace_dir):
    """(name, start_ns, end_ns) of every host event in the trace."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
    return out


def _profiled(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = fn()
    return out, _spans(tmp_path)


def _inside(spans, child, *parents):
    """Every ``child`` span lies within some span of ``parents``."""
    kids = [(s, e) for n, s, e in spans if n == child]
    outer = [(s, e) for n, s, e in spans if n in parents]
    assert kids, f"no {child} span"
    return all(any(ps <= s and e <= pe for ps, pe in outer)
               for s, e in kids)


def _tables():
    return workloads.tpch_tables(3000, seed=2)


def _suite(tables):
    return {q.name: q.run(tables) for q in workloads.SUITE}


def _stream(obs_level):
    """Four folds and the close, each inside an annotation of the
    caller's own, as the benchmark's open loop opens them."""
    rs = np.random.default_rng(11)
    s = PruneStream("distinct", shards=2, merge_every=1, window=1,
                    obs=obs_level, d=16, w=4)
    for _ in range(4):
        with jax.profiler.TraceAnnotation("stream.fold"):
            idx = s.fold(jnp.asarray(rs.integers(1, 60, 256)
                                     .astype(np.uint32)))
            s.live_mask(idx)
    with jax.profiler.TraceAnnotation("stream.close"):
        s.close()
    return s


SUITE_STAGES = {
    "suite.q1": ("to_host", "master", "to_device"),
    "suite.q3": ("to_host", "master", "join", "to_device"),
    "suite.q6": ("switch", "to_host", "master"),
}
# and "window_block", when a fold finds its window full of masks not
# yet ready: whether it does depends on timing
STREAM_STAGES = ("prepare", "to_device", "fold_dispatch", "merge_dispatch",
                 "apply_dispatch")


@pytest.mark.parametrize("obs_level", ["counters", "trace"])
def test_suite_stage_spans_nest_under_their_query(tmp_path, level,
                                                  obs_level):
    level(obs_level)
    tables = _tables()
    _, spans = _profiled(tmp_path, lambda: _suite(tables))
    for entry, stages in SUITE_STAGES.items():
        for st in stages:
            assert _inside(spans, f"{entry}.{st}", entry)
    # the engine calls of Q1 (through run_query) and Q3 run inside them
    assert _inside(spans, "run_query.master", "suite.q1")
    assert _inside(spans, "engine_prune.to_host", "suite.q1", "suite.q3")


@pytest.mark.parametrize("obs_level", ["counters", "trace"])
def test_run_query_spans(tmp_path, obs_level):
    uv = make_uservisits(3000, seed=5)
    spec = QuerySpec("topn", ("ad_revenue",), dict(d=64, w=6, N=10))

    def call():
        with jax.profiler.TraceAnnotation("query.topn"):
            return run_query(spec, uv, obs=obs_level)

    _, spans = _profiled(tmp_path, call)
    # the meshless default runs two-pass: pass 1, merge and apply
    for name in ("engine_prune.pass1", "engine_prune.gather_merge",
                 "engine_prune.pass2_apply", "engine_prune.to_host",
                 "run_query.master"):
        assert _inside(spans, name, "query.topn")
    # completion starts after the engine's mask read-back has ended
    to_host = max(e for n, _, e in spans if n == "engine_prune.to_host")
    master = min(s for n, s, _ in spans if n == "run_query.master")
    assert to_host <= master


@pytest.mark.parametrize("obs_level", ["counters", "trace"])
def test_stream_stage_spans_nest_under_the_fold(tmp_path, obs_level):
    _, spans = _profiled(tmp_path, lambda: _stream(obs_level))
    for st in STREAM_STAGES:
        assert f"prune_stream.{st}" in {n for n, _, _ in spans}
    for name in {n for n, _, _ in spans if n.startswith("prune_stream.")}:
        assert _inside(spans, name, "stream.fold", "stream.close")
    assert sum(n == "prune_stream.fold_dispatch" for n, _, _ in spans) == 4


def test_no_program_spans_at_off(tmp_path, level):
    level("off")
    tables = _tables()
    uv = make_uservisits(3000, seed=5)
    spec = QuerySpec("topn", ("ad_revenue",), dict(d=64, w=6, N=10))

    def work():
        _suite(tables)
        run_query(spec, uv, obs="off")
        _stream("off")

    _, spans = _profiled(tmp_path, work)
    prefixes = ("suite.", "engine_prune.", "run_query.", "prune_stream.")
    assert not [n for n, _, _ in spans if n.startswith(prefixes)]
    # the harness-style annotation the test opened is still there
    assert any(n == "stream.fold" for n, _, _ in spans)


def test_module_span_levels(level):
    obs.TRACER.reset()
    level("off")
    with obs.span("suite.x.master"):
        pass
    level("counters")
    with obs.span("suite.x.master"):
        pass
    assert obs.TRACER.events() == []
    level("trace")
    with obs.span("suite.x.master"):
        pass
    assert [e["name"] for e in obs.TRACER.events()] == ["suite.x.master"]
    # an explicit level wins over the process-wide one
    with obs.span("suite.x.to_host", "counters"):
        pass
    assert len(obs.TRACER.events()) == 1


# ------------------------------------------------------- program names
def _module_name(lowered) -> str:
    text = lowered.as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


@pytest.mark.parametrize("algo,params", [("distinct", dict(d=16, w=4)),
                                         ("topn_det", dict(N=8, w=4))])
def test_stream_programs_are_named_per_algorithm(algo, params):
    s = PruneStream(algo, shards=2, merge_every=1, obs="off", **params)
    rs = np.random.default_rng(3)
    batch = rs.integers(1, 60, 256).astype(
        np.float32 if algo == "topn_det" else np.uint32)
    s.fold(batch)
    s.fold(batch)
    state, off = s._state, s._rep_scalar(0)
    chunks = (s._put(batch.reshape(2, -1)),)
    (fold,) = s._fold_fns.values()
    (apply,) = s._apply_fns.values()
    keep1 = fold(jax.tree_util.tree_map(jnp.copy, state), off, *chunks)[1]
    assert _module_name(fold.lower(state, off, *chunks)) \
        == f"jit_{algo}_stream_fold"
    assert _module_name(s._merge_fn.lower(state)) \
        == f"jit_{algo}_stream_merge"
    assert _module_name(apply.lower(s._merged, keep1, off, *chunks)) \
        == f"jit_{algo}_stream_apply"


# ------------------------------------------------- answers and masks
def test_suite_answers_identical_across_levels(level):
    tables = _tables()
    answers = []
    for lvl in LEVELS:
        level(lvl)
        answers.append(_suite(tables))
    assert answers[0] == answers[1] == answers[2]
    assert answers[0] == {q.name: q.reference(tables)
                          for q in workloads.SUITE}


def test_run_query_identical_across_levels():
    uv = make_uservisits(3000, seed=7)
    spec = QuerySpec("topn", ("ad_revenue",), dict(d=64, w=6, N=10))
    outs = [run_query(spec, uv, obs=lvl) for lvl in LEVELS]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(o["keep"]),
                                      np.asarray(outs[0]["keep"]))
        for a, b in zip(o["output"], outs[0]["output"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stream_live_masks_identical_across_levels():
    lives = []
    for lvl in LEVELS:
        s = _stream(lvl)
        lives.append([np.asarray(m) for m in s.live_masks()])
    for other in lives[1:]:
        for a, b in zip(lives[0], other):
            np.testing.assert_array_equal(a, b)
