"""Distributed query execution: workers → switch pruning → master.

Deployment mapping (DESIGN.md §2):
 * mesh axis "data" plays the worker rack; each shard's pruning runs at
   the point where its traffic would cross the wire (inside shard_map,
   immediately before the gather to the master).
 * Every single-table pruner (DISTINCT / TOP-N / SKYLINE / GROUP BY /
   HAVING) executes through ``core.engine_prune`` — ``mode="mesh"``
   with ``pass2="mesh"`` when a mesh is given (one switch lane per
   worker; shard-local states all-gathered *across the workers*, the
   merged state broadcast back, and the pass-2 filter applied to each
   worker's resident entries — the master never re-touches the entry
   stream). Without a mesh, ``tune="off"`` (the default) runs
   ``mode="two_pass"`` on ``core.meshless_lanes`` lanes, about sqrt(m)
   of them, one (the scan) for HAVING's vectorised sketch build
   (``core.meshless_plan``); ``tune="cached"``/``"race"`` run
   a tuned plan. On a
   mesh the engine hands back a device-sharded stacked keep mask; this
   module flattens only the mask (O(m) bools via
   ``core.unshard_mask``) for master completion over the
   worker-resident columns it already holds. The engine is
   the single entry point for scan / sharded / two_pass / mesh
   execution; this module only adds table plumbing and master
   completion.
 * JOIN keeps its bespoke two-table Bloom exchange (filters are
   mergeable: OR across workers reproduces the shared switch state
   exactly); FILTER is stateless.
 * The master completes the query on the pruned survivors only.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat, core
from repro.constants import NEG
from repro.core.options import ExecOptions
from repro.obs import span as obs_span
from .tables import DictColumn, Table


# replication checking stays off: workers return per-shard masks, not
# replicated values
_shard_map = compat.shard_map


@dataclasses.dataclass
class QuerySpec:
    kind: str          # distinct|topn|join|having|skyline|groupby|filter
    columns: tuple     # relevant column names (join: (key_a, key_b))
    params: dict       # algorithm params (d, w, N, nbits, threshold, ...)


def _num_workers(mesh, axis="data") -> int:
    if mesh is None:
        return 1
    return mesh.shape[axis]


def _check_tune(tune: str, mesh) -> None:
    if tune not in core.TUNE_MODES:
        raise ValueError(
            f"tune must be one of {core.TUNE_MODES}, got {tune!r}")
    if tune != "off" and mesh is not None:
        raise ValueError(
            "tune= picks its own lane count and device spread; it can't "
            "be combined with an explicit worker mesh (the mesh IS the "
            "deployment) — pass mesh=None or tune='off'")


def _engine_call(algo: str, streams: tuple, mesh, axis: str,
                 params: dict, tune: str = "off",
                 plan_cache=None, encoding=None,
                 obs: str | None = None) -> core.PruneResult:
    """One engine invocation per query: mesh-backed when a mesh exists
    (S = one lane per worker on the data axis, pass 2 resident on the
    workers), two-pass on ``core.meshless_plan``'s S ~ sqrt(m) lanes
    otherwise. The result's keep mask is normalized to the flat
    bool[m] layout — only the mask is gathered (``unshard_mask``); the
    entry stream stays sharded on the workers and master completion
    reads the columns this layer already holds.

    tune != "off" (meshless only) replaces that default with a
    cached/raced two-pass-family plan (see ``core.planner.tune``); the
    mask stays flat and bit-identical to the analytic plan's.

    encoding: per-stream ``DictEncoding | None`` tuple — streams carry
    codes and pass 1 prunes in code space (see ``core.engine``).
    """
    if mesh is None:
        plan = (core.meshless_plan(algo, streams, [params]) if tune == "off"
                else core.resolve_plan(algo, streams, params, tune_mode=tune,
                                       cache=plan_cache, obs=obs).plan)
        return core.execute_plan(algo, *streams, plan=plan,
                                 encoding=encoding, obs=obs, **params)
    r = core.engine_prune(algo, *streams, mode="mesh",
                          shards=mesh.shape[axis], mesh=mesh,
                          mesh_axis=axis, pass2="mesh",
                          encoding=encoding, obs=obs, **params)
    m = streams[0].shape[0]
    out = core.PruneResult(keep=core.unshard_mask(r.keep, m),
                           state=r.state, emitted=r.emitted)
    out.report = r.report  # telemetry rides along the normalized result
    return out


def _code_stream(col, decode: str):
    """(engine stream, encoding) for one column under the decode policy."""
    if decode == "eager":
        return col.decoded(), None
    return col.code_stream()


def _prepare(spec: QuerySpec, table: Table, decode: str = "auto"):
    """Per-kind stream building / engine params / master completion.

    Shared by `run_query` (one engine call) and `run_queries` (one
    batched call per compatible group): returns ``(algo, streams,
    encodings, engine_params, complete)`` where ``complete`` maps a
    flat-mask ``PruneResult`` to the user-facing result dict.
    join/filter have bespoke bodies and are not prepared here.

    Encoded columns (``DictColumn``/``RLEColumn``) prune in code space
    and the completions below materialize decoded values for pass-2
    *survivors only* (``Column.take`` — the late-materialization
    contract). The code-space completion rules:

    * DISTINCT dedups codes (the sorted dictionary is a bijection, so
      code equality == value equality) and decodes the survivors.
    * TOP-N runs ``top_k`` on codes (sorted dictionary => code order ==
      value order, and equal values share one code, so the index
      tie-break matches) and decodes the N winners.
    * HAVING groups compacted survivor *codes*, aggregates decoded
      survivor values, and decodes only the qualifying keys (code sort
      order == value sort order).
    * SKYLINE compares codes when every column shares one dictionary
      (per-dimension order isomorphism preserves dominance); otherwise
      it falls back to the decoded stack.
    * GROUP BY needs no completion change: the engine's fused decode
      runs in-scan, so the switch state already holds decoded keys.
    """
    k = spec.kind
    p = dict(spec.params)
    if k == "distinct":
        (cname,) = spec.columns
        col = table.col(cname)
        stream, enc = _code_stream(col, decode)
        params = dict(d=p["d"], w=p["w"], policy=p.get("policy", "lru"))
        if "seed" in p:
            params["seed"] = p["seed"]

        def complete(r):
            out_mask = core.master_complete_distinct(stream, r.keep)
            idx = np.nonzero(np.asarray(out_mask))[0]
            uniq = np.unique(np.asarray(col.take(idx)))
            return _result(uniq, r.keep)

        return "distinct", (stream,), (enc,), params, complete
    if k == "topn":
        (cname,) = spec.columns
        col = table.col(cname)
        stream, enc = _code_stream(col, decode)
        if p.get("mode", "rand") == "rand":
            algo, params = "topn_rand", dict(d=p["d"], w=p["w"])
            if "seed" in p:
                params["seed"] = p["seed"]
        else:
            algo, params = "topn_det", dict(N=p["N"], w=p.get("w", 4))

        def complete(r):
            topv, topi = core.master_complete_topn(stream, r.keep,
                                                   p["N"])
            topv, topi = np.asarray(topv), np.asarray(topi)
            if enc is not None:
                # decode the N winners via their original rows; slots
                # filled with NEG (< N survivors) stay NEG
                real = topv != np.float32(NEG)
                dec = np.asarray(col.take(topi)).astype(np.float32)
                topv = np.where(real, dec, np.float32(NEG))
            return _result((topv, topi), r.keep)

        return algo, (stream,), (enc,), params, complete
    if k == "having":
        kname, vname = spec.columns
        kcol, vcol = table.col(kname), table.col(vname)
        kstream, kenc = _code_stream(kcol, decode)
        vstream, venc = _code_stream(vcol, decode)
        params = dict(threshold=p["threshold"], rows=p.get("rows", 3),
                      width=p.get("width", 1024), agg=p.get("agg", "sum"))
        if "seed" in p:
            params["seed"] = p["seed"]

        def complete(r):
            # compact first: only survivor values are ever decoded
            kidx = np.nonzero(np.asarray(r.keep))[0]
            keys = np.asarray(kstream)[kidx]
            vals = np.asarray(vcol.take(kidx))
            ones = np.ones(kidx.shape[0], np.bool_)
            out = core.master_complete_having(keys, vals, ones,
                                              p["threshold"],
                                              p.get("agg", "sum"))
            if kenc is not None:
                lut = np.asarray(kenc.lut)
                out = [lut[c].item() for c in out]  # sorted is preserved
            return _result(out, r.keep)

        return "having", (kstream, vstream), (kenc, venc), params, complete
    if k == "skyline":
        cols = [table.col(c) for c in spec.columns]
        encs = [c.encoding if isinstance(c, DictColumn) else None
                for c in cols]
        # code-space dominance needs ONE dictionary across all D
        # dimensions (per-dimension order isomorphism); otherwise decode
        shared = (decode != "eager" and len(encs) > 0
                  and all(e is not None for e in encs)
                  and all(e is encs[0] for e in encs))
        if shared:
            pts, enc = jnp.stack([c.codes for c in cols], axis=-1), encs[0]
        else:
            pts, enc = jnp.stack([c.decoded() for c in cols], axis=-1), None
        params = dict(w=p["w"], score=p.get("score", "aph"))

        def complete(r):
            # dominance is per-dimension >=/>; the shared sorted
            # dictionary preserves both, so the mask needs no decode
            out = core.master_complete_skyline(pts, r.keep)
            return _result(np.asarray(out), r.keep)

        return "skyline", (pts,), (enc,), params, complete
    if k == "groupby":
        kname, vname = spec.columns
        kstream, kenc = _code_stream(table.col(kname), decode)
        vstream, venc = _code_stream(table.col(vname), decode)
        agg = p.get("agg", "sum")
        params = dict(d=p["d"], w=p["w"], agg=agg)
        if "seed" in p:
            params["seed"] = p["seed"]

        def complete(r):
            # the fused in-scan decode means r.state/r.emitted already
            # hold decoded keys and values — identical to the plain run
            out = core.master_complete_groupby(r, agg)
            # switch→master traffic = valid evictions + state entries
            ev_ok = np.asarray(r.emitted[2]).ravel()
            st_ok = np.asarray(r.state.valid).ravel()
            traffic = jnp.asarray(np.concatenate([ev_ok, st_ok]))
            return _result(out, ~traffic)  # emitted partials = traffic

        return "groupby", (kstream, vstream), (kenc, venc), params, complete
    raise KeyError(k)


def run_query(spec: QuerySpec, tables, mesh=None, axis: str = "data",
              tune: str | None = None, plan_cache=None,
              options: ExecOptions | None = None,
              decode: str | None = None,
              obs: str | None = None) -> dict:
    """Execute a query with switch pruning; returns output + statistics.

    tune: "off" | "cached" | "race" — self-tuned engine plans for the
    single-table pruners (join/filter have bespoke execution paths and
    ignore it). Incompatible with an explicit mesh; results are
    bit-identical across all three settings.

    options / decode: ``ExecOptions`` bundle (tune/plan_cache/decode
    apply here; mode/shards/pass2/apply_block are the mesh's job at
    this layer and are rejected). Encoded table columns prune in code
    space and decode survivors only; ``decode="eager"`` decodes up
    front instead.

    obs: telemetry level (see ``core.engine_prune``). The result dict's
    ``"report"`` key carries the engine's ``ExecReport`` (None for the
    bespoke join/filter paths or when obs="off").
    """
    opts = ExecOptions.resolve(options, tune=tune, plan_cache=plan_cache,
                               decode=decode, obs=obs)
    opts.require_unset("run_query", "mode", "shards", "pass2",
                       "apply_block")
    tune = opts.tune if opts.tune is not None else "off"
    plan_cache = opts.plan_cache
    decode = opts.decode if opts.decode is not None else "auto"
    _check_tune(tune, mesh)
    k = spec.kind
    p = dict(spec.params)
    if k == "join":
        return _run_join(spec, tables, mesh, axis, p)
    if k == "filter":
        table: Table = tables
        formula = p["formula"]
        cols = {c: table.col(c).decoded() for c in spec.columns}
        pr = core.filter_prune(formula, cols, p.get("truthtable", True))
        final = core.master_complete_filter(formula, cols, pr.keep)
        return _result(np.nonzero(np.asarray(final))[0], pr.keep)
    algo, streams, encs, params, complete = _prepare(spec, tables, decode)
    r = _engine_call(algo, streams, mesh, axis, params, tune,
                     plan_cache, encoding=encs, obs=opts.obs)
    with obs_span("run_query.master", opts.obs):
        out = complete(r)
    out["report"] = r.report
    return out


def _group_key(spec: QuerySpec):
    """Batching key: specs batch together only when their streams and
    family statics agree — same columns, same policy/score/agg, and the
    same side of `hash_mod`'s 2^16 multiply-shift/modulo branch (a
    static in the traced program; see `core.batched`). Returns None for
    kinds with bespoke execution paths (join, filter)."""
    k, p = spec.kind, spec.params
    if k == "distinct":
        return (k, spec.columns, p.get("policy", "lru"),
                int(p["d"]) < (1 << 16))
    if k == "topn":
        if p.get("mode", "rand") == "rand":
            return (k, spec.columns, "rand", int(p["d"]) < (1 << 16))
        return (k, spec.columns, "det")
    if k == "skyline":
        return (k, spec.columns, p.get("score", "aph"))
    if k == "groupby":
        return (k, spec.columns, p.get("agg", "sum"),
                int(p["d"]) < (1 << 16))
    if k == "having":
        return (k, spec.columns, p.get("agg", "sum"))
    return None


def run_queries(specs, tables, mesh=None, axis: str = "data",
                device_budget_bytes: int | None = None,
                tune: str | None = None, plan_cache=None,
                options: ExecOptions | None = None,
                decode: str | None = None,
                obs: str | None = None) -> list:
    """Execute many queries, batching compatible ones into one program.

    Specs are grouped by `_group_key` (same algorithm family, columns
    and family statics); each multi-spec group runs through
    ``core.engine_prune_batch`` — one pass 1 over the shared stream:
    without a mesh (``tune="off"``) two-pass on the same
    ``core.meshless_lanes`` lanes a serial `run_query` uses, on a mesh
    one `shard_map` dispatch + one fused state collective for the
    whole group, with pass 2 resident on the workers.  Singleton
    groups and join/filter specs fall back to `run_query`.  Results
    come back in input order, one `run_query`-shaped dict per spec,
    bit-identical to a serial `run_query` loop.

    device_budget_bytes caps each group's per-device resident state
    (the paper's §8 switch-memory constraint); oversubscribed groups
    are split into sequential admission waves by
    ``planner.plan_query_batch``.

    tune: "off" | "cached" | "race" (meshless only). Each multi-spec
    group resolves ONE plan — tuned on the group's shared streams with
    the first query's params — and runs the whole batch through it;
    singletons tune per query. Exact results either way (superset
    safety), though a group's masks may differ from a per-query tuned
    serial loop since the group shares one lane count.
    """
    opts = ExecOptions.resolve(options, tune=tune, plan_cache=plan_cache,
                               decode=decode, obs=obs)
    opts.require_unset("run_queries", "mode", "shards", "pass2",
                       "apply_block")
    tune = opts.tune if opts.tune is not None else "off"
    plan_cache = opts.plan_cache
    decode = opts.decode if opts.decode is not None else "auto"
    obs = opts.obs
    _check_tune(tune, mesh)
    specs = list(specs)
    results: list = [None] * len(specs)
    groups: dict = {}
    for i, spec in enumerate(specs):
        key = _group_key(spec)
        if key is None:
            results[i] = run_query(spec, tables, mesh, axis,
                                   decode=decode, obs=obs)
        else:
            groups.setdefault(key, []).append(i)
    for idxs in groups.values():
        if len(idxs) == 1:
            i = idxs[0]
            results[i] = run_query(specs[i], tables, mesh, axis,
                                   tune, plan_cache, decode=decode,
                                   obs=obs)
            continue
        prepped = [_prepare(specs[i], tables, decode) for i in idxs]
        algo, streams, encs = prepped[0][0], prepped[0][1], prepped[0][2]
        queries = [pr[3] for pr in prepped]
        m = streams[0].shape[0]
        if mesh is None:
            plan = (core.meshless_plan(algo, streams, queries)
                    if tune == "off" else
                    core.resolve_plan(algo, streams, queries[0],
                                      tune_mode=tune, cache=plan_cache,
                                      obs=obs).plan)
            rb = core.execute_plan_batch(
                algo, queries, *streams, plan=plan, encoding=encs,
                device_budget_bytes=device_budget_bytes, obs=obs)
            keep = rb.keep
        else:
            rb = core.engine_prune_batch(
                algo, queries, *streams, mode="mesh",
                shards=mesh.shape[axis], mesh=mesh, mesh_axis=axis,
                pass2="mesh", encoding=encs,
                device_budget_bytes=device_budget_bytes, obs=obs)
            keep = core.unshard_mask_batch(rb.keep, m)
        w_cap = (max(int(q["w"]) for q in queries)
                 if algo == "groupby" else None)
        for j, i in enumerate(idxs):
            state_j = jax.tree_util.tree_map(lambda a: a[j], rb.state)
            if algo == "groupby":
                # trim batch-cap pads (always-invalid slots) back to the
                # query's own (d, w) so master completion and traffic
                # stats see the serial state shape; columns come in
                # per-shard blocks of the batch w-cap
                d, w = int(queries[j]["d"]), int(queries[j]["w"])
                state_j = jax.tree_util.tree_map(
                    lambda a: a.reshape(a.shape[0], -1, w_cap)
                               [:d, :, :w].reshape(d, -1), state_j)
            rj = core.PruneResult(
                keep=keep[j],
                state=state_j,
                emitted=(None if rb.emitted is None else
                         jax.tree_util.tree_map(lambda a: a[j],
                                                rb.emitted)))
            with obs_span("run_query.master", obs):
                results[i] = prepped[j][4](rj)
            # one batched dispatch served the whole group: every member
            # shares the group's ExecReport
            results[i]["report"] = rb.report
    return results


def _run_join(spec, tables, mesh, axis, p):
    ta, tb = tables
    ka_name, kb_name = spec.columns
    nw = _num_workers(mesh, axis)
    # the Bloom exchange hashes every key on both sides anyway (no
    # pass-1 pruning to defer behind), so encoded key columns decode
    # here; the two tables' dictionaries differ, making code spaces
    # incomparable across tables
    ka_full = ta.col(ka_name).decoded()
    kb_full = tb.col(kb_name).decoded()
    # pad fill = the first key: already a member, so the padded shards
    # build bit-identical Bloom filters and no tail row is dropped
    ka_st = ta.stacked_shards(nw, fills={ka_name: ka_full[0]})[ka_name]
    kb_st = tb.stacked_shards(nw, fills={kb_name: kb_full[0]})[kb_name]
    nbits, H = p["nbits"], p.get("num_hashes", 3)

    def worker(ka, kb):
        ka, kb = ka[0], kb[0]
        fa = core.bloom_build(ka, nbits, H, seed=0)
        fb = core.bloom_build(kb, nbits, H, seed=7919)
        bits_a, bits_b = fa.bits, fb.bits
        if mesh is not None:  # Bloom OR-merge == the shared switch filter
            bits_a = jax.lax.psum(bits_a.astype(jnp.int32), axis) > 0
            bits_b = jax.lax.psum(bits_b.astype(jnp.int32), axis) > 0
        FA = core.BloomFilter(bits=bits_a, num_hashes=H, seed=0)
        FB = core.BloomFilter(bits=bits_b, num_hashes=H, seed=7919)
        return core.bloom_query(FB, ka)[None], core.bloom_query(FA, kb)[None]

    if mesh is None:
        keep_a, keep_b = worker(ka_st[:1], kb_st[:1])
        keep_a, keep_b = keep_a[0], keep_b[0]
    else:
        sm = _shard_map(worker, mesh, P(axis), P(axis))
        keep_a, keep_b = sm(ka_st, kb_st)
        keep_a, keep_b = keep_a.reshape(-1), keep_b.reshape(-1)
    na, nb = min(ta.num_rows, keep_a.shape[0]), min(tb.num_rows,
                                                    keep_b.shape[0])
    keep_a, keep_b = keep_a[:na], keep_b[:nb]
    va = ta.col(p.get("payload_a", ka_name)).decoded()[:na]
    vb = tb.col(p.get("payload_b", kb_name)).decoded()[:nb]
    out = core.master_complete_join(ka_full[:na], va, keep_a,
                                    kb_full[:nb], vb, keep_b)
    stats_keep = jnp.concatenate([keep_a, keep_b])
    return _result(out, stats_keep)


def _result(output, keep) -> dict:
    keepf = jnp.asarray(keep).astype(jnp.float32)
    return {
        "output": output,
        # the pass-1 survivor mask: feed it to Table.gather_decoded to
        # materialize only surviving rows of encoded columns
        "keep": jnp.asarray(keep),
        "forwarded": int(keepf.sum()),
        "total": int(keepf.shape[0]),
        "pruned_fraction": float(1 - keepf.mean()),
        # telemetry (repro.obs.ExecReport) — overwritten by the engine
        # paths; None for the bespoke join/filter bodies and obs="off"
        "report": None,
    }
