#!/usr/bin/env python
"""2-process jax.distributed CPU smoke for the mesh engine (CI).

The tier-1 suite exercises the mesh backend on a single-process
8-device platform, where shard_map "collectives" never leave the
process. This smoke is the per-commit stand-in for the ROADMAP's "true
multi-host mesh run": two OS processes (4 forced CPU devices each, 8
global) joined via ``jax.distributed`` + gloo CPU collectives, running
a minimal ``mode="mesh"`` TOP-N query both with the master-side apply
and with the mesh-resident pass 2 — so the pass-1 state all-gather and
the resident broadcast genuinely cross process boundaries — plus one
*batched* multi-query TOP-N run (mixed per-query N/w in a single
program) whose fused Q-state collective crosses the same boundary.

Checks: both placements produce the same mask, the mask is a superset
of the true top-N (completion recovers the exact answer), the
resident mask's addressable shards per process cover only that
process's devices, and the batched masks are bit-identical to a
serial per-query loop.

Usage:
  python scripts/ci_distributed_smoke.py            # parent: spawns 2 workers
  python scripts/ci_distributed_smoke.py --worker I # internal
"""
from __future__ import annotations

import os
import pathlib
import socket
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
NUM_PROCESSES = 2
DEVICES_PER_PROCESS = 4
M, N, SHARDS = 4096, 32, 8


def worker(process_id: int, port: int) -> None:
    # both knobs must be set before the backend initializes
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={DEVICES_PER_PROCESS}"
    ).strip()
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=NUM_PROCESSES, process_id=process_id)

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import engine_prune, unshard_mask

    ndev = len(jax.devices())
    assert ndev == NUM_PROCESSES * DEVICES_PER_PROCESS, ndev
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("shards",))

    # every process holds the same host copy; the device data is built
    # shard-by-shard so no process ever owns the other's slice
    host = (np.random.default_rng(0).random(M) * 1e6 + 1).astype(
        np.float32)
    v = jax.make_array_from_callback(
        (M,), NamedSharding(mesh, P("shards")), lambda idx: host[idx])

    masks = {}
    for p2 in ("master", "mesh"):
        fn = jax.jit(lambda x, p2=p2: engine_prune(
            "topn_det", x, mode="mesh", shards=SHARDS, mesh=mesh,
            pass2=p2, N=N, w=8).keep)
        keep = fn(v)
        if p2 == "mesh":
            # resident: this process only materializes its own lanes
            local = sum(s.data.size for s in keep.addressable_shards)
            assert local == M // NUM_PROCESSES, local
            keep = unshard_mask(keep, M)
        # replicate the flat mask (O(m) bools — the only gather) so the
        # host-side oracle check below can read it
        keep = jax.jit(jnp.asarray,
                       out_shardings=NamedSharding(mesh, P()))(keep)
        masks[p2] = np.asarray(keep)

    assert (masks["master"] == masks["mesh"]).all(), \
        "pass-2 placement changed the mask across processes"
    survivors = host[masks["mesh"]]
    want = np.sort(host)[-N:]
    assert np.isin(want, survivors).all(), "pruned a true top-N entry"
    print(f"worker {process_id}: OK (mask equal across placements, "
          f"top-{N} superset holds, kept {int(masks['mesh'].sum())}/{M})")

    # encoded TOP-N: the same query pruned in code space — uint32 codes
    # sharded across both processes, the dictionary gather fused into
    # pass 1 — must reproduce the decoded mask bit-for-bit across the
    # gloo boundary (the mesh merge moves *code-derived* state)
    from repro.core.encoding import dict_encode

    codes_host, enc = dict_encode(host)
    codes = jax.make_array_from_callback(
        (M,), NamedSharding(mesh, P("shards")),
        lambda idx: np.asarray(codes_host)[idx])
    efn = jax.jit(lambda x: engine_prune(
        "topn_det", x, mode="mesh", shards=SHARDS, mesh=mesh,
        pass2="master", encoding=enc, N=N, w=8).keep)
    ekeep = np.asarray(jax.jit(
        jnp.asarray, out_shardings=NamedSharding(mesh, P()))(efn(codes)))
    assert (ekeep == masks["master"]).all(), \
        "encoded mask != decoded mask across processes"
    print(f"worker {process_id}: encoded OK (dict codes, "
          f"lut size {enc.size}, mask == decoded)")

    # batched multi-query: Q mixed-param TOP-N queries in ONE program —
    # a single shard_map dispatch whose fused [Q, lanes, ...] state
    # all-gather crosses the 2-process boundary — must reproduce the
    # serial per-query loop bit-for-bit
    from repro.core import engine_prune_batch, unshard_mask_batch

    queries = [dict(N=8, w=4), dict(N=N, w=8), dict(N=16, w=6),
               dict(N=4, w=5)]
    replicate = jax.jit(jnp.asarray,
                        out_shardings=NamedSharding(mesh, P()))
    bfn = jax.jit(lambda x: engine_prune_batch(
        "topn_det", queries, x, mode="mesh", shards=SHARDS, mesh=mesh,
        pass2="mesh").keep)
    kb = bfn(v)
    # resident layout: each process materializes only its own lanes,
    # Q times over
    local = sum(s.data.size for s in kb.addressable_shards)
    assert local == len(queries) * M // NUM_PROCESSES, local
    kb = np.asarray(replicate(unshard_mask_batch(kb, M)))
    for i, q in enumerate(queries):
        sfn = jax.jit(lambda x, q=q: engine_prune(
            "topn_det", x, mode="mesh", shards=SHARDS, mesh=mesh,
            pass2="mesh", **q).keep)
        ks = np.asarray(replicate(unshard_mask(sfn(v), M)))
        assert (kb[i] == ks).all(), \
            f"batched mask != serial loop for query {i}: {q}"
    print(f"worker {process_id}: multiq OK (Q={len(queries)} batched "
          f"masks == serial loop across {NUM_PROCESSES} processes)")

    # streamed TOP-N: micro-batches folded into donated mesh-resident
    # lane state on the same 8-device global mesh; the periodic
    # cross-lane merge (one fused all_gather inside shard_map) and the
    # close() replication cross the gloo process boundary. merge_every
    # is an explicit int — "auto" runs a timing calibration that the
    # two processes could resolve differently.
    from repro.core.streaming import PruneStream, lane_view

    sizes = [1024, 1024, 1024, 1024]
    stream = PruneStream("topn_det", shards=SHARDS, mesh=mesh,
                         merge_every=2, window=2, obs="counters",
                         N=N, w=8)
    lo = 0
    for b in sizes:
        stream.fold(host[lo:lo + b])
        lo += b
    res = stream.close()
    assert stream.stats["merges"] >= 2, stream.stats
    lv, valid, arrival = lane_view("topn_det", (host,), sizes, SHARDS,
                                   N=N, w=8)
    one = engine_prune("topn_det", *lv, mode="two_pass", shards=SHARDS,
                       N=N, w=8)
    got = np.asarray(res.keep)[arrival[valid]]
    want = np.asarray(one.keep)[valid]
    assert (got == want).all(), \
        "streamed close() mask != one-shot across processes"
    # live masks (judged against 2-batch-stale snapshots) stay safe
    live = np.asarray(res.live_keep)
    assert np.isin(np.sort(host)[-N:], host[live]).all(), \
        "streamed live mask pruned a true top-N entry"
    print(f"worker {process_id}: stream OK ({len(sizes)} folds, "
          f"{stream.stats['merges']} cross-process merges, close() == "
          f"one-shot, kept {int(got.sum())}/{M})")

    # telemetry across the process boundary: the stream's ExecReport
    # must count every cross-process merge collective exactly (4 folds
    # at merge_every=2 -> merges at t=1 and t=3, plus close()'s final
    # refresh merge = 3) and ship a nonzero state-bytes figure — the
    # gloo wire traffic the paper's Fig. 9 accounting is built on
    rep = res.report
    assert rep is not None, "obs='counters' must attach a stream report"
    assert rep.counters["merge_collective_count"] \
        == stream.stats["merges"] == 3, \
        (rep.counters.get("merge_collective_count"), stream.stats)
    assert rep.counters["state_bytes_shipped"] > 0, rep.counters
    assert rep.entries_scanned == sum(sizes), rep.counters
    assert rep.entries_kept == int(np.asarray(res.keep).sum()), \
        rep.counters
    print(f"worker {process_id}: obs OK (report counted "
          f"{rep.merge_collective_count} merge collectives, "
          f"{rep.state_bytes_shipped} state bytes shipped)")


def main() -> int:
    if "--worker" in sys.argv:
        worker(int(sys.argv[sys.argv.index("--worker") + 1]),
               int(os.environ["SMOKE_PORT"]))
        return 0

    with socket.socket() as s:  # free port for the coordinator
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    # one process per chip: both workers stay on the CPU platform
    env = dict(os.environ, SMOKE_PORT=str(port), JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (str(ROOT / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    procs = [subprocess.Popen(
        [sys.executable, __file__, "--worker", str(i)], env=env, cwd=ROOT)
        for i in range(NUM_PROCESSES)]
    try:
        codes = [p.wait(timeout=600) for p in procs]
    except subprocess.TimeoutExpired:
        # a hung worker (e.g. the coordinator port got sniped between
        # probe and bind) must not orphan its sibling into the job
        # timeout — kill the whole set and fail cleanly
        for p in procs:
            p.kill()
        print("distributed smoke: FAILED (worker timeout; all killed)")
        return 1
    if any(codes):
        print(f"distributed smoke: FAILED (exit codes {codes})")
        return 1
    print("distributed smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
