"""Query planner: Table 2 resource model + §6 multi-query packing."""
import math

import pytest
from hypstub import given, settings, st

from repro.core import (ResourceFootprint, SwitchProfile, footprint,
                        optimal_shards, pack_queries, plan_multi_switch,
                        rule_count)


def test_table2_formulas():
    A = SwitchProfile().alus_per_stage
    fp = footprint("distinct_fifo", d=4096, w=2)
    assert fp == ResourceFootprint(math.ceil(2 / A), 2, 4096 * 2 * 8)
    fp = footprint("distinct_lru", d=4096, w=2)
    assert fp.stages == 2 and fp.sram_bytes == 4096 * 2 * 8
    fp = footprint("skyline_sum", D=2, w=10)
    assert fp.stages == 1 + 20 and fp.alus == 2 * 1 - 1 + 10 * 3
    fp = footprint("skyline_aph", D=2, w=10)
    assert fp.tcam == 128 and fp.sram_bytes == 10 * 3 * 8 + (1 << 16) * 4
    fp = footprint("topn_det", w=4)
    assert fp.stages == 5 and fp.sram_bytes == 5 * 8
    fp = footprint("join_bf", M=4 << 20, H=3)
    assert fp.stages == 2 and fp.sram_bytes == 4 << 20
    fp = footprint("having", d=3, w=1024)
    assert fp.alus == 3 and fp.sram_bytes == 3 * 1024 * 8


def test_rules_per_query_in_paper_range():
    for algo in ("distinct_lru", "topn_det", "join_bf", "having",
                 "skyline_aph", "groupby", "filter"):
        assert 10 <= rule_count(algo) <= 20


def test_packing_bigdata_workload():
    prof = SwitchProfile(stages=32, alus_per_stage=16,
                        sram_per_stage_bytes=6 << 20)
    plan = pack_queries({
        "filter": footprint("filter", num_predicates=2),
        "groupby": footprint("groupby", d=4096, w=8),
        "distinct": footprint("distinct_lru", d=4096, w=2),
        "join": footprint("join_bf", M=4 << 20, H=3),
    }, prof)
    assert plan.feasible and plan.stages_used <= prof.stages


def test_packing_infeasible_reported():
    prof = SwitchProfile(stages=4, alus_per_stage=2,
                         sram_per_stage_bytes=1 << 10)
    plan = pack_queries({"skyline": footprint("skyline_aph", D=2, w=10)},
                        prof)
    assert not plan.feasible and "skyline" in plan.reason


@settings(max_examples=25, deadline=None)
@given(st.integers(4, 64), st.integers(1, 16))
def test_packing_never_oversubscribes(stages, alus):
    prof = SwitchProfile(stages=stages, alus_per_stage=alus,
                         sram_per_stage_bytes=1 << 20)
    plan = pack_queries({
        "a": footprint("topn_det", w=4),
        "b": footprint("distinct_lru", d=512, w=2),
        "c": footprint("filter", num_predicates=2),
    }, prof)
    if plan.feasible:
        # re-play placements and check per-stage budgets
        alu_used = [0] * prof.stages
        for name, (s0, fp) in plan.placements.items():
            per = math.ceil(fp.alus / max(fp.stages, 1))
            for s in range(s0, s0 + fp.stages):
                alu_used[s] += per
        assert all(u <= prof.alus_per_stage for u in alu_used)


# ------------------------------------------------- multi-switch placement
def test_multi_switch_speedup_and_merge_cost():
    q = {"topn": footprint("topn_rand", d=1024, w=8),
         "distinct": footprint("distinct_fifo", d=1024, w=4)}
    m = 1 << 20
    p1 = plan_multi_switch(q, m, shards=1)
    p8 = plan_multi_switch(q, m, shards=8)
    assert p1.feasible and p8.feasible
    assert p8.entries_per_switch == m // 8
    state = sum(fp.sram_bytes for fp in q.values())
    assert p8.merge_bytes == 8 * state
    assert p8.est_speedup > p1.est_speedup > 0.9


def test_multi_switch_diminishing_returns():
    """Past the optimum, the master's merge fold eats the speedup."""
    q = {"gb": footprint("groupby", d=4096, w=8)}
    m = 1 << 16
    best = optimal_shards(m, sum(fp.sram_bytes for fp in q.values()))
    lo = plan_multi_switch(q, m, shards=max(best // 4, 1))
    opt = plan_multi_switch(q, m, shards=best)
    hi = plan_multi_switch(q, m, shards=best * 16)
    assert opt.est_speedup >= lo.est_speedup
    assert opt.est_speedup >= hi.est_speedup


def test_multi_switch_infeasible_propagates():
    prof = SwitchProfile(stages=4, alus_per_stage=2,
                         sram_per_stage_bytes=1 << 10)
    plan = plan_multi_switch({"sky": footprint("skyline_aph", D=2, w=10)},
                             1 << 20, shards=4, profile=prof)
    assert not plan.feasible and "sky" in plan.reason


def test_optimal_shards_scaling():
    # bigger streams or smaller states → more useful switches
    assert optimal_shards(1 << 24, 1 << 16) > optimal_shards(1 << 18, 1 << 16)
    assert optimal_shards(1 << 20, 1 << 10) > optimal_shards(1 << 20, 1 << 20)
    assert optimal_shards(1 << 20, 0) == 4096  # stateless: no merge cost


# ------------------------------------------------- multi-query admission
def test_plan_query_batch_no_budget_single_wave():
    from repro.core import plan_query_batch

    plan = plan_query_batch([100, 200, 300])
    assert plan.waves == ((0, 1, 2),)
    assert plan.num_waves == 1
    assert plan.per_query_bytes == (100, 200, 300)
    assert plan.device_budget_bytes is None and plan.oversized == ()
    assert plan_query_batch([]).waves == ()


def test_plan_query_batch_order_preserving_next_fit():
    """Waves are contiguous index runs in arrival order, each within
    the budget — concatenating wave results preserves query order."""
    from repro.core import plan_query_batch

    plan = plan_query_batch([40, 40, 40, 40, 40], device_budget_bytes=100)
    assert plan.waves == ((0, 1), (2, 3), (4,))
    for wave in plan.waves:
        assert sum(plan.per_query_bytes[i] for i in wave) <= 100
    flat = [i for w in plan.waves for i in w]
    assert flat == sorted(flat) == list(range(5))


def test_plan_query_batch_oversized_admitted_alone():
    from repro.core import plan_query_batch

    plan = plan_query_batch([50, 500, 50], device_budget_bytes=100)
    assert plan.waves == ((0,), (1,), (2,))
    assert plan.oversized == (1,)


def test_plan_query_batch_bad_budget_raises():
    from repro.core import plan_query_batch

    with pytest.raises(ValueError, match="positive"):
        plan_query_batch([10], device_budget_bytes=0)
    with pytest.raises(ValueError, match="positive"):
        plan_query_batch([10], device_budget_bytes=-5)


def test_plan_query_batch_hashable_static_metadata():
    """The plan rides on the batched result pytree as a static field,
    so it must hash and compare by value."""
    from repro.core import plan_query_batch

    a = plan_query_batch([10, 20], device_budget_bytes=25)
    b = plan_query_batch([10, 20], device_budget_bytes=25)
    assert a == b and hash(a) == hash(b)


@pytest.mark.parametrize("m,groupby,distinct", [
    (1, 1, 1), (2, 2, 2), (4, 2, 2), (5, 3, 3), (591_788, 770, 256),
    (6_001_215, 2450, 256)])
def test_meshless_lanes_rule(m, groupby, distinct):
    """ceil(sqrt(m)) lanes clamped to [1, m]; the chunkable algorithms
    stop at CHUNKABLE_MAX_LANES; HAVING, whose pass 1 is one vectorised
    sketch build, keeps one lane."""
    from repro.core import meshless_lanes

    assert meshless_lanes("groupby", m) == groupby
    assert meshless_lanes("topn_det", m) == groupby
    assert meshless_lanes("topn_rand", m) == groupby
    assert meshless_lanes("having", m) == 1
    assert meshless_lanes("distinct", m) == distinct
    assert meshless_lanes("skyline", m) == distinct


@pytest.mark.parametrize("algo,shape,w", [
    ("distinct", (6_001_215,), 4), ("skyline", (6_001_215, 2), 64),
    ("groupby", (6_001_215,), 4)])
def test_meshless_plan_bounds_the_apply_chunk_at_sf1(algo, shape, w):
    """At SF1 lineitem's length one block of the chunkable applies stays
    within APPLY_CHUNK_ELEMENTS; a non-chunkable plan has no block."""
    import jax
    import jax.numpy as jnp
    from repro.core import meshless_plan, planner

    stream = jax.ShapeDtypeStruct(shape, jnp.float32)
    plan = meshless_plan(algo, (stream,), [dict(w=w), dict(w=1)])
    assert plan.mode == "two_pass"
    assert plan.shards == planner.meshless_lanes(algo, shape[0])
    if algo == "groupby":
        assert plan.apply_block is None
        return
    per_entry = plan.shards * plan.shards * w * math.prod(shape[1:])
    assert plan.apply_block * per_entry <= planner.APPLY_CHUNK_ELEMENTS
    assert plan.apply_block < -(-shape[0] // plan.shards)
