"""Compile the chip's programs for a described TPU v5e, without the chip.

Every Pallas kernel that ``kernels.ops`` dispatches is lowered with
``interpret=False`` and compiled by the TPU compiler at real widths, as
is the jnp ``engine_prune`` two_pass program at TPC-H SF1 shape. That
catches what interpret mode cannot: Mosaic's tiling and layout rules,
primitives it has no lowering for, casts it refuses. Nothing runs.

The topology is described inside a module fixture, never at import:
only the worker that runs this file loads the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import core
from repro.kernels import bloom_filter, cms_sketch, parallel, rle_scan

M = 1 << 22            # entries per kernel call (chip_smoke phase E)
SF1_ROWS = 6_001_215   # TPC-H SF1 lineitem
D, W, SHARDS, BLOCK = 1024, 4, 8, 256
f32, u32, i32 = jnp.float32, jnp.uint32, jnp.int32

# name -> (kernel call with static arguments, argument (shape, dtype)s)
KERNELS = {
    "topn_sequential": (
        lambda v: parallel.topn_shard_states_kernel(
            v, d=D, w=W, shards=1, block=BLOCK, seed=0, interpret=False),
        [((M,), f32)]),
    "distinct_sequential": (
        lambda v: parallel.distinct_shard_states_kernel(
            v, d=D, w=W, shards=1, block=BLOCK, seed=0, interpret=False),
        [((M,), u32)]),
    "skyline_sequential": (
        lambda p: parallel.skyline_shard_states_kernel(
            p, w=16, shards=1, block=BLOCK, score="aph", interpret=False),
        [((M, 2), f32)]),
    "topn_pass1": (
        lambda v: parallel.topn_shard_states_kernel(
            v, d=D, w=W, shards=SHARDS, block=BLOCK, seed=0,
            interpret=False),
        [((M,), f32)]),
    "topn_pass2": (
        lambda v, s: parallel.topn_apply_kernel(
            v, s, d=D, shards=SHARDS, block=BLOCK, seed=0, interpret=False),
        [((M,), f32), ((D, W), f32)]),
    "distinct_pass1": (
        lambda v: parallel.distinct_shard_states_kernel(
            v, d=D, w=W, shards=SHARDS, block=BLOCK, seed=0,
            interpret=False),
        [((M,), u32)]),
    "distinct_pass2": (
        lambda v, k, s, o: parallel.distinct_apply_kernel(
            v, k, s, o, d=D, shards=SHARDS, block=BLOCK, seed=0,
            interpret=False),
        [((M,), u32), ((M,), i32), ((D, SHARDS * W), u32),
         ((D, SHARDS * W), i32)]),
    "skyline_pass1": (
        lambda p: parallel.skyline_shard_states_kernel(
            p, w=16, shards=SHARDS, block=BLOCK, score="sum",
            interpret=False),
        [((M, 2), f32)]),
    "skyline_pass2": (
        lambda p, q, s: parallel.skyline_apply_kernel(
            p, q, s, block=BLOCK, interpret=False),
        [((M, 2), f32), ((SHARDS * 16, 2), f32), ((SHARDS * 16,), f32)]),
    "rle_topn": (
        lambda v, n: rle_scan.rle_topn_det_kernel(
            v, n, N=100, w=W, block=BLOCK, interpret=False),
        [((M,), f32), ((M,), i32)]),
    "bloom_build": (
        lambda k: bloom_filter.bloom_build_kernel(
            k, nbits=1 << 15, num_hashes=3, block=BLOCK, seed=0,
            interpret=False),
        [((M,), u32)]),
    "bloom_query": (
        lambda b, k: bloom_filter.bloom_query_kernel(
            b, k, num_hashes=3, block=BLOCK, seed=0, interpret=False),
        [((1 << 15,), f32), ((M,), u32)]),
    "cms_build": (
        lambda k, w: cms_sketch.cms_build_kernel(
            k, w, rows=3, width=1024, block=BLOCK, seed=0,
            interpret=False),
        [((M,), u32), ((M,), f32)]),
    "cms_query": (
        lambda t, k: cms_sketch.cms_query_kernel(
            t, k, block=BLOCK, seed=0, interpret=False),
        [((3, 1024), f32), ((M,), u32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # these compiles can be written to a persistent cache but never read
    # back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, args, sharding):
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
              for s, dt in args]
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = KERNELS[name]
    hlo = _compile(fn, args, one_chip).as_text()
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in HLO"


def test_engine_two_pass_compiles_at_sf1(one_chip):
    fn = lambda x: core.engine_prune("topn_det", x, mode="two_pass",
                                     shards=64, obs="off", N=100, w=8).keep
    compiled = _compile(fn, [((SF1_ROWS,), f32)], one_chip)
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("algo,args,params", [
    ("groupby", [((591_788,), u32), ((591_788,), i32)], dict(d=8, w=4)),
    ("distinct", [((SF1_ROWS,), u32)], dict(d=4096, w=4))],
    ids=["groupby_q1_sf01", "distinct_sf1"])
def test_meshless_default_compiles(one_chip, algo, args, params):
    """The meshless run_query default at its lane rule: Q1's GROUP BY
    (770 lanes) and an SF1 DISTINCT (256 lanes, chunked apply)."""
    shapes = [jax.ShapeDtypeStruct(s, dt) for s, dt in args]
    plan = core.meshless_plan(algo, shapes, [params])
    fn = lambda *xs: jax.tree_util.tree_leaves(core.execute_plan(
        algo, *xs, plan=plan, obs="off", **params))
    compiled = _compile(fn, args, one_chip)
    assert compiled.memory_analysis() is not None
