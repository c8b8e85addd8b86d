"""Batched (multi-query) pruning bodies: Q queries, one program.

Cheetah's deployed switch serves many concurrent queries over the same
entry stream (§6); the engine analogue is ``engine_prune_batch`` packing
Q same-family queries into one traced program so they share the stream
scan, the ``shard_map`` dispatch and — on the mesh — a single fused
state collective. This module holds the per-algorithm bodies that make
that exact: each is the serial scan/merge/apply with every *shape*
parameter (w, d, sketch rows/width) padded to the batch maximum and
every *value* parameter (N, threshold, seed, effective widths) turned
into a traced per-query scalar that ``jax.vmap`` maps over.

The contract, tested per algorithm in tests/test_engine_batch.py, is
bit-identity: for every query q in the batch, the batched keep mask
row equals the mask a serial ``engine_prune`` call with q's own params
produces — pads are carved out with validity masking, never allowed to
change a comparison. The invariants that make this hold:

- TOP-N det: levels past the query's w never qualify (``counts >= N``
  is gated on ``i < w_eff``), so the ladder threshold is the serial one.
- TOP-N rand: matrix columns past w_eff are pinned to NEG (they lose
  every comparison and are re-masked after each insert); the keep test
  reads column ``w_eff - 1`` with a traced gather.
- DISTINCT: slots past w_eff never become valid (LRU shifts stop at
  ``limit < w_eff``; FIFO heads wrap at ``w_eff``), so they can't hit.
- SKYLINE: slots past w_eff hold the same (0, NEG) content as the
  serial state's empty slots, so dominance and insert-position math
  agree; they are re-pinned after every insert.
- GROUP BY: eviction reads slot ``w_eff - 1`` (traced gather); slots
  past w_eff are reset to the invalid init after every insert.
- HAVING: sketch rows past rows_eff are zeroed in the built table and
  masked to +inf before the min-query; hash indices stay inside the
  query's own width via the traced-mod ``multi_hash``.

Row-hash selection uses ``hashing.hash_mod_dyn`` — the multiply-shift
vs modulo branch is a Python-level choice on ``mod < 2**16``, so it must
be uniform per batch; ``build`` rejects mixed-smallness batches (the
query layer groups by it).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..constants import NEG, POS
from .distinct import DistinctState
from .groupby import _FOLD, GroupByState, acc_dtype, init_value
from .hashing import hash_mod_dyn, multi_hash
from .pruning import PruneResult
from .skyline import _SCORES, SkylineState
from .topn import TopNDetState, TopNRandState


@dataclasses.dataclass(frozen=True)
class BatchSpec:
    """How the batched engine runs one algorithm family.

    build(queries)                     -> (qp, caps): qp is a dict of
        [Q] arrays (one traced scalar per query under vmap), caps the
        static batch-max shape params + family statics (policy/score/
        agg/hash smallness). build validates that statics agree.
    scan(streams, qp1, caps)           -> PruneResult (one query's scan)
    merge(stacked_states, qp1, caps)   -> merged global state
    apply(merged, shard_streams, keep1, qp1, caps) -> keep bool[S, n]
        (qp1 additionally carries "_lane_ids" like the serial specs)

    chunkable mirrors the serial ``_AlgoSpec`` flag (pass-2 compares
    every entry against the S·w-column merged state).
    """

    build: Callable[[list], tuple[dict, dict]]
    scan: Callable[[tuple, dict, dict], PruneResult]
    merge: Callable[[Any, dict, dict], Any]
    apply: Callable[[Any, tuple, jnp.ndarray, dict, dict], jnp.ndarray]
    chunkable: bool = False


def _cols_by_shard(stacked: jnp.ndarray) -> jnp.ndarray:
    """[S, d, w] per-shard row state -> [d, S*w] cache-column union."""
    S, d, w = stacked.shape
    return jnp.moveaxis(stacked, 0, 1).reshape(d, S * w)


def _i32(vals) -> jnp.ndarray:
    return jnp.asarray(np.asarray(vals, np.int32))


def _u32(vals) -> jnp.ndarray:
    return jnp.asarray(np.asarray(vals, np.uint32))


def _num(vals) -> jnp.ndarray:
    """Numeric per-query column keeping integer-ness when possible.

    Integer thresholds stay int32 so the batched ``est > threshold``
    compares in the same dtype as the serial path; any float in the
    batch promotes the whole column to f32 (exact for |v| < 2^24).
    """
    a = np.asarray(vals)
    if np.issubdtype(a.dtype, np.integer):
        return jnp.asarray(a.astype(np.int32))
    return jnp.asarray(a.astype(np.float32))


def _uniform(queries: list, key: str, default, algo: str):
    vals = {q.get(key, default) for q in queries}
    if len(vals) > 1:
        raise ValueError(
            f"engine_prune_batch({algo!r}): {key} must agree across the "
            f"batch (got {sorted(map(str, vals))}); group by it first "
            f"(query.run_queries does)")
    return vals.pop()


def _small_mod(queries: list, key: str, algo: str) -> bool:
    smalls = {int(q[key]) < (1 << 16) for q in queries}
    if len(smalls) > 1:
        raise ValueError(
            f"engine_prune_batch({algo!r}): hash_mod's multiply-shift vs "
            f"modulo branch is static, so all {key} must sit on the same "
            f"side of 2^16; split the batch (query.run_queries groups by "
            f"this)")
    return smalls.pop()


def _dtype_big(dt):
    """Largest finite value of dt — masks inactive sketch rows out of
    the CMS min-query."""
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.asarray(jnp.finfo(dt).max, dt)
    return jnp.asarray(jnp.iinfo(dt).max, dt)


# ---------------------------------------------------- TOP-N deterministic
def _topn_det_build(queries):
    caps = {"w": max(int(q.get("w", 4)) for q in queries)}
    qp = {"N": _i32([int(q["N"]) for q in queries]),
          "w": _i32([int(q.get("w", 4)) for q in queries])}
    return qp, caps


def _topn_det_scan_b(streams, q, caps):
    v = streams[0].astype(jnp.float32)
    w = caps["w"]
    N = q["N"]
    iw = jnp.arange(w)
    valid_lvl = iw < q["w"]  # ladder levels the query actually has

    def body(s, x):
        warm = s.seen < N
        t0 = jnp.where(warm, jnp.minimum(s.t0, x), s.t0)
        levels = t0 * (2.0 ** iw.astype(jnp.float32))
        counts = s.counts + (x >= levels).astype(jnp.int32)
        qual = (counts >= N) & valid_lvl
        cur = jnp.max(jnp.where(qual, iw, -1))
        thr = jnp.where(cur >= 0, t0 * (2.0 ** cur.astype(jnp.float32)),
                        NEG)
        keep = warm | (x >= thr)
        return TopNDetState(t0=t0, counts=counts, seen=s.seen + 1,
                            cur_level=cur), keep

    init = TopNDetState(
        t0=jnp.float32(POS), counts=jnp.zeros(w, jnp.int32),
        seen=jnp.int32(0), cur_level=jnp.int32(-1))
    state, keep = jax.lax.scan(body, init, v)
    return PruneResult(keep=keep, state=state)


def _topn_det_merge_b(st, q, caps):
    from .engine import TopNDetMerged

    thr = jnp.where(st.cur_level >= 0,
                    st.t0 * (2.0 ** st.cur_level.astype(jnp.float32)),
                    NEG)
    return TopNDetMerged(threshold=jnp.max(thr))


def _topn_det_apply_b(merged, streams, keep1, q, caps):
    del keep1
    return streams[0].astype(jnp.float32) >= merged.threshold


# ------------------------------------------------------ TOP-N randomized
def _topn_rand_build(queries):
    caps = {"d": max(int(q["d"]) for q in queries),
            "w": max(int(q["w"]) for q in queries),
            "small": _small_mod(queries, "d", "topn_rand")}
    qp = {"d": _i32([int(q["d"]) for q in queries]),
          "w": _i32([int(q["w"]) for q in queries]),
          "seed": _u32([int(q.get("seed", 0)) for q in queries])}
    return qp, caps


def _topn_rand_scan_b(streams, q, caps):
    v = streams[0].astype(jnp.float32)
    m = v.shape[0]
    d, w = caps["d"], caps["w"]
    w_eff = q["w"]
    rows = hash_mod_dyn(jnp.arange(m, dtype=jnp.uint32), q["d"],
                        seed=q["seed"], small=caps["small"])
    idx = jnp.arange(w)

    def body(vals, xr):
        x, r = xr
        row = vals[r]
        keep = x >= jnp.take(row, w_eff - 1)
        pos = jnp.sum(x <= row)  # NEG pads lose to every real entry
        shifted = jnp.where(idx > pos, jnp.roll(row, 1), row)
        new_row = jnp.where(idx == pos, x, shifted)
        new_row = jnp.where(idx < w_eff, new_row, NEG)  # re-pin pads
        new_row = jnp.where(keep, new_row, row)
        return vals.at[r].set(new_row), keep

    init = jnp.full((d, w), NEG, jnp.float32)
    vals, keep = jax.lax.scan(body, init, (v, rows))
    return PruneResult(keep=keep, state=TopNRandState(vals))


def _topn_rand_merge_b(st, q, caps):
    # per-row top-w of the shard-column union; NEG pads sort to the back
    # so the first w_eff columns match the serial merge, and the rest
    # are re-pinned for a clean state
    merged = -jnp.sort(-_cols_by_shard(st.vals), axis=1)[:, : caps["w"]]
    merged = jnp.where(jnp.arange(caps["w"])[None, :] < q["w"],
                       merged, NEG)
    return TopNRandState(vals=merged)


def _topn_rand_apply_b(merged, streams, keep1, q, caps):
    del keep1
    x = streams[0].astype(jnp.float32)  # [S, n]
    n = x.shape[-1]
    rows = hash_mod_dyn(jnp.arange(n, dtype=jnp.uint32), q["d"],
                        seed=q["seed"], small=caps["small"])
    kth = jnp.take(merged.vals, q["w"] - 1, axis=1)  # [d]
    return x >= kth[rows][None, :]


# -------------------------------------------------------------- DISTINCT
def _distinct_build(queries):
    caps = {"d": max(int(q["d"]) for q in queries),
            "w": max(int(q["w"]) for q in queries),
            "policy": _uniform(queries, "policy", "lru", "distinct"),
            "small": _small_mod(queries, "d", "distinct")}
    qp = {"d": _i32([int(q["d"]) for q in queries]),
          "w": _i32([int(q["w"]) for q in queries]),
          "seed": _u32([int(q.get("seed", 0)) for q in queries])}
    return qp, caps


def _distinct_scan_b(streams, q, caps):
    values = streams[0]
    d, w = caps["d"], caps["w"]
    policy = caps["policy"]
    w_eff = q["w"]
    rows = hash_mod_dyn(values, q["d"], seed=q["seed"],
                        small=caps["small"])
    idx = jnp.arange(w)

    def body(state, xr):
        x, r = xr
        slots_r = state.slots[r]
        valid_r = state.valid[r]
        hitvec = (slots_r == x) & valid_r  # pads never valid → never hit
        hit = jnp.any(hitvec)
        if policy == "lru":
            hitpos = jnp.argmax(hitvec)
            limit = jnp.where(hit, hitpos, w_eff - 1)
            shifted = jnp.where((idx >= 1) & (idx <= limit),
                                jnp.roll(slots_r, 1), slots_r)
            shifted_v = jnp.where((idx >= 1) & (idx <= limit),
                                  jnp.roll(valid_r, 1), valid_r)
            new_slots = shifted.at[0].set(x)
            new_valid = shifted_v.at[0].set(True)
            new_head = state.head
        elif policy == "fifo":
            h = state.head[r]
            new_slots = jnp.where(hit, slots_r, slots_r.at[h].set(x))
            new_valid = jnp.where(hit, valid_r, valid_r.at[h].set(True))
            new_head = state.head.at[r].set(
                jnp.where(hit, h, jnp.remainder(h + 1, w_eff)))
        else:  # pragma: no cover
            raise ValueError(policy)
        state = DistinctState(
            slots=state.slots.at[r].set(new_slots),
            valid=state.valid.at[r].set(new_valid),
            head=new_head)
        return state, ~hit

    init = DistinctState(slots=jnp.zeros((d, w), jnp.uint32),
                         valid=jnp.zeros((d, w), jnp.bool_),
                         head=jnp.zeros((d,), jnp.int32))
    state, keep = jax.lax.scan(body, init, (values, rows))
    return PruneResult(keep=keep, state=state)


def _distinct_merge_b(st, q, caps):
    from .engine import DistinctMerged

    S, _, w = st.slots.shape
    return DistinctMerged(
        slots=_cols_by_shard(st.slots),
        valid=_cols_by_shard(st.valid),
        shard=jnp.repeat(jnp.arange(S, dtype=jnp.int32), w))


def _distinct_apply_b(merged, streams, keep1, q, caps):
    x = streams[0]
    rows = hash_mod_dyn(x, q["d"], seed=q["seed"], small=caps["small"])
    slots_g = merged.slots[rows]
    valid_g = merged.valid[rows]
    sidx = q["_lane_ids"][:, None, None]
    dup_lower = jnp.any((slots_g == x[..., None]) & valid_g
                        & (merged.shard[None, None, :] < sidx), axis=-1)
    return keep1 & ~dup_lower


# --------------------------------------------------------------- SKYLINE
def _skyline_build(queries):
    caps = {"w": max(int(q["w"]) for q in queries),
            "score": _uniform(queries, "score", "aph", "skyline")}
    qp = {"w": _i32([int(q["w"]) for q in queries])}
    return qp, caps


def _skyline_scan_b(streams, q, caps):
    pts_in = streams[0].astype(jnp.float32)
    w = caps["w"]
    h = _SCORES[caps["score"]]
    D = pts_in.shape[-1]
    idx = jnp.arange(w)
    w_eff = q["w"]

    def body(state, x):
        hx = h(x)
        pts, scs = state.points, state.scores
        # pads carry the same (0, NEG) content as empty serial slots,
        # so pos/dominance agree with the serial w_eff-stage pipeline
        pos = jnp.sum(hx <= scs)
        before = idx < pos
        dom = (before & jnp.all(x <= pts, axis=-1)
               & jnp.any(x < pts, axis=-1))
        pruned = jnp.any(dom)
        shift = idx[:, None] > pos
        new_pts = jnp.where(idx[:, None] == pos, x,
                            jnp.where(shift, jnp.roll(pts, 1, axis=0),
                                      pts))
        new_scs = jnp.where(idx == pos, hx,
                            jnp.where(idx > pos, jnp.roll(scs, 1), scs))
        new_pts = jnp.where(idx[:, None] < w_eff, new_pts, 0.0)
        new_scs = jnp.where(idx < w_eff, new_scs, NEG)
        return SkylineState(new_pts, new_scs), ~pruned

    init = SkylineState(points=jnp.zeros((w, D), jnp.float32),
                        scores=jnp.full((w,), NEG, jnp.float32))
    state, keep = jax.lax.scan(body, init, pts_in)
    return PruneResult(keep=keep, state=state)


def _skyline_merge_b(st, q, caps):
    S, w, D = st.points.shape
    pts = st.points.reshape(S * w, D)
    scs = st.scores.reshape(S * w)
    order = jnp.argsort(-scs)
    return SkylineState(points=pts[order], scores=scs[order])


def _skyline_apply_b(merged, streams, keep1, q, caps):
    del keep1
    x = streams[0].astype(jnp.float32)  # [S, n, D]
    Pm, Sc = merged.points, merged.scores
    dom = (jnp.all(x[:, :, None, :] <= Pm[None, None], axis=-1)
           & jnp.any(x[:, :, None, :] < Pm[None, None], axis=-1)
           & (Sc > NEG)[None, None, :])  # pads score NEG → can't dominate
    return ~jnp.any(dom, axis=-1)


# -------------------------------------------------------------- GROUP BY
def _groupby_build(queries):
    caps = {"d": max(int(q["d"]) for q in queries),
            "w": max(int(q["w"]) for q in queries),
            "agg": _uniform(queries, "agg", "sum", "groupby"),
            "small": _small_mod(queries, "d", "groupby")}
    qp = {"d": _i32([int(q["d"]) for q in queries]),
          "w": _i32([int(q["w"]) for q in queries]),
          "seed": _u32([int(q.get("seed", 0)) for q in queries])}
    return qp, caps


def _groupby_scan_b(streams, q, caps):
    keys, values = streams[0], streams[1]
    valid = (streams[2] if len(streams) > 2
             else jnp.ones(keys.shape[0], jnp.bool_))
    d, w = caps["d"], caps["w"]
    fold = _FOLD[caps["agg"]]
    acc = acc_dtype(values.dtype, caps["agg"])
    init_v = init_value(caps["agg"], acc)
    w_eff = q["w"]
    last = w_eff - 1
    idx = jnp.arange(w)
    rows = hash_mod_dyn(keys, q["d"], seed=q["seed"],
                        small=caps["small"])

    def body(state, krvo):
        k, r, v, ok = krvo
        krow, arow, vrow = state.keys[r], state.aggs[r], state.valid[r]
        hitvec = (krow == k) & vrow  # pads never valid → never hit
        hit = jnp.any(hitvec)
        hitpos = jnp.argmax(hitvec)
        arow_hit = arow.at[hitpos].set(fold(arow[hitpos], v))
        # eviction reads the query's own last slot (traced gather)
        ev_k = jnp.take(krow, last)
        ev_a = jnp.take(arow, last)
        ev_valid = jnp.take(vrow, last) & ~hit & ok
        # insert at front; slots past w_eff are reset to the invalid init
        krow_miss = jnp.where(idx < w_eff,
                              jnp.roll(krow, 1).at[0].set(k),
                              jnp.uint32(0))
        arow_miss = jnp.where(idx < w_eff,
                              jnp.roll(arow, 1).at[0].set(fold(init_v, v)),
                              init_v)
        vrow_miss = jnp.where(idx < w_eff,
                              jnp.roll(vrow, 1).at[0].set(True), False)
        new_k = jnp.where(ok, jnp.where(hit, krow, krow_miss), krow)
        new_a = jnp.where(ok, jnp.where(hit, arow_hit, arow_miss), arow)
        new_vld = jnp.where(ok, jnp.where(hit, vrow, vrow_miss), vrow)
        state = GroupByState(
            keys=state.keys.at[r].set(new_k),
            aggs=state.aggs.at[r].set(new_a),
            valid=state.valid.at[r].set(new_vld))
        return state, (jnp.bool_(False), ev_k, ev_a, ev_valid)

    init = GroupByState(keys=jnp.zeros((d, w), jnp.uint32),
                        aggs=jnp.full((d, w), init_v, acc),
                        valid=jnp.zeros((d, w), jnp.bool_))
    state, (keep, ev_k, ev_a, ev_valid) = jax.lax.scan(
        body, init, (keys, rows, values.astype(acc), valid))
    return PruneResult(keep=keep, state=state,
                       emitted=(ev_k, ev_a, ev_valid))


def _groupby_merge_b(st, q, caps):
    return GroupByState(keys=_cols_by_shard(st.keys),
                        aggs=_cols_by_shard(st.aggs),
                        valid=_cols_by_shard(st.valid))


def _groupby_apply_b(merged, streams, keep1, q, caps):
    del merged, streams
    return keep1  # all-False: every entry is absorbed into switch state


# ---------------------------------------------------------------- HAVING
def _having_build(queries):
    caps = {"rows": max(int(q.get("rows", 3)) for q in queries),
            "width": max(int(q.get("width", 1024)) for q in queries),
            "agg": _uniform(queries, "agg", "sum", "having")}
    qp = {"rows": _i32([int(q.get("rows", 3)) for q in queries]),
          "width": _i32([int(q.get("width", 1024)) for q in queries]),
          "seed": _u32([int(q.get("seed", 0)) for q in queries]),
          "threshold": _num([q["threshold"] for q in queries])}
    return qp, caps


def _having_query_b(table, keys, q):
    """CMS min-query with traced width/rows: rows past the query's own
    are masked to the dtype max so they never win the min."""
    rows_cap = table.shape[0]
    idx = multi_hash(keys, q["width"], rows_cap, seed=q["seed"])
    est = table[jnp.arange(rows_cap)[None, :], idx]  # [m, rows_cap]
    est = jnp.where(jnp.arange(rows_cap)[None, :] < q["rows"], est,
                    _dtype_big(est.dtype))
    return jnp.min(est, axis=-1)


def _having_scan_b(streams, q, caps):
    keys = streams[0]
    rows_cap, width_cap = caps["rows"], caps["width"]
    if caps["agg"] == "count":
        weights = jnp.ones(keys.shape[0], jnp.int32)
    else:
        weights = streams[1]
    # the first rows_eff derived seeds match the serial multi_hash, and
    # indices stay < the query's width, so rows < rows_eff of the table
    # are bit-identical to the serial sketch
    idx = multi_hash(keys, q["width"], rows_cap, seed=q["seed"])
    table = jnp.zeros((rows_cap, width_cap), weights.dtype)
    for r in range(rows_cap):  # rows_cap is small (2-4)
        table = table.at[r].add(
            jnp.zeros(width_cap, weights.dtype).at[idx[:, r]].add(weights))
    table = jnp.where(jnp.arange(rows_cap)[:, None] < q["rows"],
                      table, jnp.zeros((), weights.dtype))
    est = _having_query_b(table, keys, q)
    keep = est > q["threshold"]
    return PruneResult(keep=keep, state=table)


def _having_merge_b(st, q, caps):
    # sketch addition; inactive rows are zero in every shard's table
    return jnp.sum(st, axis=0)


def _having_apply_b(merged, streams, keep1, q, caps):
    del keep1
    keys = streams[0]
    est = _having_query_b(merged, keys.reshape(-1), q).reshape(keys.shape)
    return est > q["threshold"]


BSPECS: dict[str, BatchSpec] = {
    "topn_det": BatchSpec(_topn_det_build, _topn_det_scan_b,
                          _topn_det_merge_b, _topn_det_apply_b),
    "topn_rand": BatchSpec(_topn_rand_build, _topn_rand_scan_b,
                           _topn_rand_merge_b, _topn_rand_apply_b),
    "distinct": BatchSpec(_distinct_build, _distinct_scan_b,
                          _distinct_merge_b, _distinct_apply_b,
                          chunkable=True),
    "skyline": BatchSpec(_skyline_build, _skyline_scan_b,
                         _skyline_merge_b, _skyline_apply_b,
                         chunkable=True),
    "groupby": BatchSpec(_groupby_build, _groupby_scan_b,
                         _groupby_merge_b, _groupby_apply_b),
    "having": BatchSpec(_having_build, _having_scan_b,
                        _having_merge_b, _having_apply_b),
}
