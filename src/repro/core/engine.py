"""Sharded pruning engine: superset-safe parallel execution (paper §3/§7.2).

Cheetah's correctness contract is *superset safety*: forwarding any
superset of a pruner's keep set leaves the query answer unchanged. That
property makes pruning embarrassingly parallelizable — running S
independent pruners over S shards of the stream and unioning the
survivors still yields a correct superset — and this module exploits it
behind one API, ``engine_prune(algo, *streams, mode=..., shards=S)``.

Execution modes → the paper's deployment story:

``scan``
    The sequential oracle: one switch on the data path streaming every
    entry through ``jax.lax.scan`` (the paper's single-ToR deployment,
    §2/§8). Exact per-packet semantics; O(m) sequential steps.

``sharded``
    S switch replicas, each seeing a contiguous 1/S slice of the stream
    (the paper's multi-rack scale-out sketch: one Cheetah switch per
    ToR, partitioned tables — cf. §9 "Deployment"). Implemented as
    ``jax.vmap`` of the existing scan bodies over S shards; the keep
    masks are disjoint so their union is just the concatenation. Pure
    O(m/S) speedup; pruning is looser because no shard sees another
    shard's state. (HAVING is the exception: its keep rule compares a
    *global* aggregate against the threshold, so shard-local decisions
    are unsafe and ``sharded`` transparently runs the two-pass merge —
    the algorithm is inherently two-pass even on one switch.)

``two_pass``
    The master-assisted variant (paper §4.3's two-round refinement
    generalized): pass 1 builds shard-local switch states in parallel,
    a per-algorithm ``merge_states`` combinator folds them into one
    global state at the master (max over TOP-N ladder thresholds /
    per-row top-w union, FIFO-cache union for DISTINCT, dominance-set
    merge for SKYLINE, sketch/cache addition for HAVING / GROUP BY),
    and pass 2 applies the merged state as a fully vectorized,
    scan-free filter. Tighter pruning than ``sharded`` at near-parallel
    cost.

``mesh``
    ``two_pass`` lifted onto a ``jax.sharding`` device mesh — the
    paper's §9 multi-rack deployment (one pruning switch per ToR)
    mapped to one accelerator per group of switch lanes. Pass 1 runs
    each shard's scan body inside ``shard_map`` (S lanes split evenly
    over the mesh axis, vmapped within each device). Where pass 2 runs
    is the ``pass2`` parameter:

    ``pass2="master"`` (default) gathers the per-shard states *and*
    keep masks to the master, folds the states with ``merge_states``,
    and applies the merged state there — the master touches the full
    [S, n] stream again, costing m·f filter work.

    ``pass2="mesh"`` keeps pass 2 resident on the data path (the
    paper's multi-rack principle: only compact state moves upward).
    Inside the same ``shard_map``, the per-lane states are all-gathered
    across the mesh axis — state_bytes·D wire traffic, the only thing
    that leaves a device — every device folds them into the identical
    merged state (the broadcast), and applies the scan-free filter
    (chunked via ``apply_block`` for DISTINCT/SKYLINE) to its own
    resident m/D entries. The keep mask comes back **device-sharded in
    the stacked [S, n] layout** (use ``unshard_mask(keep, m)`` for the
    flat mask — an O(m)-bool gather, never the entry stream); the
    master's peak materialization is O(m/D + S·state), not O(m).

    ``pass2="auto"`` picks the placement from the planner's cost rule:
    master-apply m·f vs broadcast state_bytes·D + (m/D)·f
    (``planner.optimal_pass2``).

    Either placement yields the exact same mask bits: with the default
    mesh the keep mask is identical to ``two_pass`` at the same S (lane
    count is the semantic parameter; the device count only spreads the
    lanes); an explicit mesh requires ``shards`` to be a multiple of
    its axis size.

Memory note: the DISTINCT/SKYLINE pass-2 filters compare every entry
against the S·w-column merged state — an [S·n, S·w] intermediate that
bounds S on one device. ``apply_block`` chunks that compare with
``jax.lax.map`` over blocks of entries (mesh mode defaults to
block=4096), trading one materialization for nb sequential block
filters of bounded size.

Correctness note (tested in tests/test_engine.py and
tests/test_superset_safety.py): the parallel modes are *not*
mask-supersets of the sequential scan — e.g. a shard whose first N
entries are large advances its TOP-N ladder faster than the global scan
would. What holds, and what the paper's contract actually requires, is
that every mode's keep mask is a superset of the *minimal correct
survivor set* (OPT: the true top-N / first occurrences / skyline /
qualifying keys), so master completion over any mode's survivors — or
any superset of them, §7.2 — reproduces Q(D) exactly.

The Pallas analogue (grid-parallel kernels with one state replica per
grid program + a merge step) lives in ``repro.kernels.parallel``;
multi-switch placement/cost modeling lives in ``repro.core.planner``
(``plan_multi_switch``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import compat
from ..constants import NEG
from ..obs import report as obsreport
from .distinct import distinct_prune
from .distinct import init_state as distinct_init
from .encoding import DictEncoding, normalize_encodings
from .options import ExecOptions
from .groupby import GroupByState, groupby_init, groupby_prune
from .hashing import hash_mod
from .having import having_init, having_prune
from .pruning import PruneResult
from .sketches import CountMin
from .skyline import SkylineState, skyline_init, skyline_prune
from .topn import (TopNRandState, topn_det_init, topn_det_prune,
                   topn_rand_init, topn_rand_prune)
from . import batched, planner

MODES = ("scan", "sharded", "two_pass", "mesh")
ALGORITHMS = ("topn_det", "topn_rand", "distinct", "skyline", "groupby",
              "having")
# pass-2 placements for mode="mesh": apply the merged state at the
# master (full-stream filter), on each device's resident shard, or let
# the planner's cost rule choose (planner.optimal_pass2)
PASS2 = ("master", "mesh", "auto")

# pass-2 chunk size used when mode="mesh" and the caller didn't pick one
# (only consulted for the chunkable algorithms, DISTINCT / SKYLINE)
DEFAULT_MESH_APPLY_BLOCK = 4096


# ---------------------------------------------------------- merged states
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TopNDetMerged:
    """Global TOP-N filter state: one threshold, provably query-safe.

    Each shard ladder only advances to t_i after observing >= N entries
    >= t_i, so >= N entries globally are >= any shard's threshold — the
    N-th largest global value is >= it, and filtering x < threshold can
    never drop a true top-N entry. The max over shards is therefore the
    tightest safe merge.
    """

    threshold: jnp.ndarray  # f32 scalar


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistinctMerged:
    """Union of the shard FIFO/LRU caches, with column-owner shard ids.

    Pass 2 prunes a shard-kept entry iff its value sits in a *lower*
    ranked shard's final cache: caches have no false positives, so the
    lowest shard in which a value ever appeared keeps its shard-first
    occurrence — at least one copy of every distinct value survives.
    """

    slots: jnp.ndarray  # uint32[d, S*w]
    valid: jnp.ndarray  # bool[d, S*w]
    shard: jnp.ndarray  # int32[S*w] — owner shard of each cache column


# ------------------------------------------------------------- algorithms
@dataclasses.dataclass(frozen=True)
class _AlgoSpec:
    """How the engine runs one pruning algorithm.

    scan(streams, params)            -> PruneResult (sequential body)
    pads(streams, params)            -> per-stream pad fill values
    merge(stacked_states, params)    -> merged global state
    apply(merged, shard_streams, shard_keep, params) -> keep bool[S, n]
    resume(state, streams, params)   -> PruneResult (scan from `state`;
        bit-identical continuation — the streaming fold step)
    init(streams, params)            -> one lane's empty switch state
        (streams are example arrays consulted for dtypes/trailing dims)
    """

    scan: Callable[[tuple, dict], PruneResult]
    pads: Callable[[tuple, dict], tuple]
    merge: Callable[[Any, dict], Any]
    apply: Callable[[Any, tuple, jnp.ndarray, dict], jnp.ndarray]
    resume: Callable[[Any, tuple, dict], PruneResult] | None = None
    init: Callable[[tuple, dict], Any] | None = None
    # True when shard-local keep decisions are unsafe without the merged
    # global state (HAVING: a key's global sum can clear the threshold
    # while every shard-local estimate stays below it). `sharded` then
    # runs the merge+apply anyway — the algorithm is inherently
    # two-pass, even sequentially.
    sharded_needs_merge: bool = False
    # True when `apply` compares each entry against the full S·w-column
    # merged state (an [S, n, S*w] intermediate) and therefore benefits
    # from `apply_block` chunking. The apply must be elementwise over
    # entries (no positional dependence on the in-shard index).
    chunkable: bool = False
    # True when tail pads need an explicit validity column appended to
    # the streams (GROUP BY: COUNT folds +1 per entry, so no pad *value*
    # is neutral — only a valid=False flag is).
    pad_validity: bool = False
    # True when pass 1 is one vectorised build over the whole stream,
    # not a sequential scan (HAVING's Count-Min sketch): more lanes only
    # copy its state, so the meshless default runs it on one lane.
    vector_pass1: bool = False


def _cols_by_shard(stacked: jnp.ndarray) -> jnp.ndarray:
    """[S, d, w] per-shard row state -> [d, S*w] cache-column union."""
    S, d, w = stacked.shape
    return jnp.moveaxis(stacked, 0, 1).reshape(d, S * w)


# TOP-N deterministic (threshold ladder, Ex. 3) --------------------------
def _topn_det_scan(streams, p):
    return topn_det_prune(streams[0], N=p["N"], w=p.get("w", 4))


def _topn_det_resume(state, streams, p):
    return topn_det_prune(streams[0], N=p["N"], w=p.get("w", 4),
                          state=state)


def _topn_det_init(streams, p):
    return topn_det_init(p.get("w", 4))


def _topn_det_merge(st, p):
    # same math as the scan body: thr = t0 * 2^cur_level (NEG: no level)
    thr = jnp.where(st.cur_level >= 0,
                    st.t0 * (2.0 ** st.cur_level.astype(jnp.float32)),
                    NEG)
    return TopNDetMerged(threshold=jnp.max(thr))


def _topn_det_apply(merged, streams, keep1, p):
    del keep1
    return streams[0].astype(jnp.float32) >= merged.threshold


# TOP-N randomized (d×w rolling matrix, Ex. 7) ---------------------------
def _topn_rand_scan(streams, p):
    return topn_rand_prune(streams[0], d=p["d"], w=p["w"],
                           seed=p.get("seed", 0))


def _topn_rand_resume(state, streams, p):
    # the row hash is positional over the lane-local stream index, so the
    # resumed scan needs the per-lane entry count consumed so far
    return topn_rand_prune(streams[0], d=p["d"], w=p["w"],
                           seed=p.get("seed", 0), state=state,
                           index_offset=p.get("_index_offset", 0))


def _topn_rand_init(streams, p):
    return topn_rand_init(p["d"], p["w"])


def _topn_rand_merge(st, p):
    # per-row top-w of the union of the shard rows (descending), i.e.
    # exactly the state a single switch holding d rows of width w would
    # converge to after seeing every shard's survivors.
    merged = -jnp.sort(-_cols_by_shard(st.vals), axis=1)[:, : p["w"]]
    return TopNRandState(vals=merged)


def _topn_rand_apply(merged, streams, keep1, p):
    del keep1
    x = streams[0].astype(jnp.float32)  # [S, n]
    n = x.shape[-1]
    # shards replay the scan's shard-local row assignment (stream index);
    # a streaming refresh applies to one micro-batch's chunk, whose lane-
    # local positions start at _index_offset, not 0
    idx = (jnp.arange(n, dtype=jnp.uint32)
           + jnp.asarray(p.get("_index_offset", 0), jnp.uint32))
    rows = hash_mod(idx, p["d"], seed=p.get("seed", 0))
    return x >= merged.vals[:, -1][rows][None, :]


# DISTINCT (d×w fingerprint cache, Ex. 2) --------------------------------
def _distinct_scan(streams, p):
    return distinct_prune(streams[0], d=p["d"], w=p["w"],
                          policy=p.get("policy", "lru"),
                          seed=p.get("seed", 0))


def _distinct_resume(state, streams, p):
    return distinct_prune(streams[0], d=p["d"], w=p["w"],
                          policy=p.get("policy", "lru"),
                          seed=p.get("seed", 0), state=state)


def _distinct_init(streams, p):
    return distinct_init(p["d"], p["w"])


def _distinct_merge(st, p):
    S, _, w = st.slots.shape
    return DistinctMerged(
        slots=_cols_by_shard(st.slots),
        valid=_cols_by_shard(st.valid),
        shard=jnp.repeat(jnp.arange(S, dtype=jnp.int32), w),
    )


def _distinct_apply(merged, streams, keep1, p):
    x = streams[0]  # uint32[S, n]
    rows = hash_mod(x, p["d"], seed=p.get("seed", 0))
    slots_g = merged.slots[rows]  # [S, n, S*w]
    valid_g = merged.valid[rows]
    # the "lower-ranked shard owns it" test needs *global* lane ranks:
    # a resident pass 2 only sees its device's lanes, so the caller
    # passes their global ids; at the master the leading axis is global
    lanes = p.get("_lane_ids")
    if lanes is None:
        lanes = jnp.arange(x.shape[0], dtype=jnp.int32)
    sidx = lanes[:, None, None]
    dup_lower = jnp.any((slots_g == x[..., None]) & valid_g
                        & (merged.shard[None, None, :] < sidx), axis=-1)
    return keep1 & ~dup_lower


# SKYLINE (w stored points, Ex. 6) ---------------------------------------
def _skyline_scan(streams, p):
    return skyline_prune(streams[0], w=p["w"], score=p.get("score", "aph"))


def _skyline_resume(state, streams, p):
    return skyline_prune(streams[0], w=p["w"],
                         score=p.get("score", "aph"), state=state)


def _skyline_init(streams, p):
    return skyline_init(p["w"], streams[0].shape[-1])


def _skyline_merge(st, p):
    S, w, D = st.points.shape
    pts = st.points.reshape(S * w, D)
    scs = st.scores.reshape(S * w)
    order = jnp.argsort(-scs)  # keep the SkylineState descending invariant
    return SkylineState(points=pts[order], scores=scs[order])


def _skyline_apply(merged, streams, keep1, p):
    del keep1
    x = streams[0].astype(jnp.float32)  # [S, n, D]
    P, Sc = merged.points, merged.scores
    dom = (jnp.all(x[:, :, None, :] <= P[None, None], axis=-1)
           & jnp.any(x[:, :, None, :] < P[None, None], axis=-1)
           & (Sc > NEG)[None, None, :])
    # a true skyline point is dominated by nothing, so it always survives
    return ~jnp.any(dom, axis=-1)


# GROUP BY (d×w key/aggregate cache, §4.2/§8) ----------------------------
def _groupby_scan(streams, p):
    valid = streams[2] if len(streams) > 2 else None
    return groupby_prune(streams[0], streams[1], valid=valid,
                         d=p["d"], w=p["w"],
                         agg=p.get("agg", "sum"), seed=p.get("seed", 0))


def _groupby_resume(state, streams, p):
    valid = streams[2] if len(streams) > 2 else None
    return groupby_prune(streams[0], streams[1], valid=valid,
                         d=p["d"], w=p["w"],
                         agg=p.get("agg", "sum"), seed=p.get("seed", 0),
                         state=state)


def _groupby_init(streams, p):
    return groupby_init(p["d"], p["w"], p.get("agg", "sum"),
                        streams[1].dtype)


def _groupby_merge(st, p):
    # cache-column union: the master's fold is a commutative monoid, so
    # duplicate keys across shard columns fold exactly in completion.
    return GroupByState(keys=_cols_by_shard(st.keys),
                        aggs=_cols_by_shard(st.aggs),
                        valid=_cols_by_shard(st.valid))


def _groupby_apply(merged, streams, keep1, p):
    del merged, streams, p
    return keep1  # all-False: every entry is absorbed into switch state


# HAVING (Count-Min + threshold, Ex. 5) ----------------------------------
def _having_scan(streams, p):
    values = streams[1] if len(streams) > 1 else None
    return having_prune(streams[0], values, p["threshold"],
                        rows=p.get("rows", 3), width=p.get("width", 1024),
                        agg=p.get("agg", "sum"), seed=p.get("seed", 0))


def _having_resume(state, streams, p):
    values = streams[1] if len(streams) > 1 else None
    return having_prune(streams[0], values, p["threshold"],
                        rows=p.get("rows", 3), width=p.get("width", 1024),
                        agg=p.get("agg", "sum"), seed=p.get("seed", 0),
                        state=state)


def _having_init(streams, p):
    dtype = (jnp.int32 if p.get("agg", "sum") == "count"
             or len(streams) < 2 else streams[1].dtype)
    return having_init(rows=p.get("rows", 3), width=p.get("width", 1024),
                       seed=p.get("seed", 0), dtype=dtype)


def _having_merge(st, p):
    # sketch addition: CMS build is order-independent scatter-add, so the
    # summed table is bit-identical to a single sequential build.
    return CountMin(table=jnp.sum(st.table, axis=0), seed=st.seed)


def _having_apply(merged, streams, keep1, p):
    del keep1
    from .sketches import cms_query

    keys = streams[0]
    est = cms_query(merged, keys.reshape(-1)).reshape(keys.shape)
    return est > p["threshold"]


# ------------------------------------------------------------------- pads
def _value_pads(streams, p):
    return (NEG,)


def _fingerprint_pads(streams, p):
    return (jnp.uint32(0),)


def _skyline_pads(streams, p):
    # a (NEG, ..., NEG) point dominates nothing and scores below/at every
    # real point, so tail pads only (at worst) loosen the last shard.
    return (NEG,)


def _groupby_pads(streams, p):
    # pads carry valid=False, so the gated fold ignores key and value
    # entirely — any fill works, including for agg="count" (which has no
    # neutral pad *value*: every entry would add 1 without the flag)
    return (streams[0][0], jnp.zeros((), streams[1].dtype),
            jnp.bool_(False))[: len(streams)]


def _having_pads(streams, p):
    # pads only inflate CMS estimates; the overestimate stays one-sided,
    # which is the direction HAVING's superset safety relies on.
    return (streams[0][0],) + ((0,) if len(streams) > 1 else ())


_SPECS: dict[str, _AlgoSpec] = {
    "topn_det": _AlgoSpec(_topn_det_scan, _value_pads,
                          _topn_det_merge, _topn_det_apply,
                          resume=_topn_det_resume, init=_topn_det_init),
    "topn_rand": _AlgoSpec(_topn_rand_scan, _value_pads,
                           _topn_rand_merge, _topn_rand_apply,
                           resume=_topn_rand_resume, init=_topn_rand_init),
    "distinct": _AlgoSpec(_distinct_scan, _fingerprint_pads,
                          _distinct_merge, _distinct_apply,
                          resume=_distinct_resume, init=_distinct_init,
                          chunkable=True),
    "skyline": _AlgoSpec(_skyline_scan, _skyline_pads,
                         _skyline_merge, _skyline_apply,
                         resume=_skyline_resume, init=_skyline_init,
                         chunkable=True),
    "groupby": _AlgoSpec(_groupby_scan, _groupby_pads,
                         _groupby_merge, _groupby_apply,
                         resume=_groupby_resume, init=_groupby_init,
                         pad_validity=True),
    "having": _AlgoSpec(_having_scan, _having_pads,
                        _having_merge, _having_apply,
                        resume=_having_resume, init=_having_init,
                        sharded_needs_merge=True, vector_pass1=True),
}


# ------------------------------------------------------- encoded streams
# Streams whose plain-path pad fill is the stream's own first element
# (GROUP BY / HAVING keys): their encoded pad is the stream's first
# *code*, which decodes to exactly the plain fill — no pad slot needed.
# All other encoded streams pad with the ``with_pad`` slot code, which
# decodes to the plain path's constant fill (NEG / 0).
_FIRST_ELEMENT_PADS: dict[str, tuple[int, ...]] = {
    "groupby": (0,),
    "having": (0,),
}


def _decode_streams(streams, encs):
    """Gather each encoded stream through its dictionary (fused decode)."""
    return tuple(s if e is None else e.decode(s)
                 for s, e in zip(streams, encs))


def _pads_probe(streams, encs):
    """Length-1 decoded slices: enough for every pads fn (they consult
    only ``stream[0]`` and dtypes) without materializing a full decode."""
    return tuple(s[:1] if e is None else e.decode(s[:1])
                 for s, e in zip(streams, encs))


def _padded_encodings(algo: str, spec: _AlgoSpec, encs, streams, params):
    """Grow each constant-fill encoding by one pad slot (see above)."""
    first_elem = _FIRST_ELEMENT_PADS.get(algo, ())
    plain = spec.pads(_pads_probe(streams, encs), params)
    return tuple(
        e if e is None or i in first_elem else e.with_pad(plain[i])
        for i, e in enumerate(encs))


def _encoded_spec(algo: str, spec: _AlgoSpec, encs) -> _AlgoSpec:
    """Wrap an _AlgoSpec so its bodies run on dictionary-encoded streams.

    ``encs`` is a per-stream tuple of pad-slot-ready ``DictEncoding``
    (from ``_padded_encodings``) or ``None``.  The wrapped scan/apply/
    resume/init decode each encoded stream via the O(1) ``lut[code]``
    gather fused into the (jitted) body, so the masks are bit-identical
    to running the original spec on eagerly decoded streams — while the
    decoded column is never stored.  The wrapped ``pads`` returns
    code-space fills that decode to exactly the plain path's fills, so
    ragged shards, chunked applies and ragged streaming micro-batches
    stay bit-identical too.
    """
    first_elem = _FIRST_ELEMENT_PADS.get(algo, ())

    def dec(streams):
        return _decode_streams(streams, encs)

    def pads(streams, p):
        plain = spec.pads(_pads_probe(streams, encs), p)
        return tuple(
            plain[i] if encs[i] is None
            else (streams[i][0] if i in first_elem else encs[i].pad_code)
            for i in range(len(plain)))

    return dataclasses.replace(
        spec,
        scan=lambda st, p: spec.scan(dec(st), p),
        apply=lambda mg, st, k1, p: spec.apply(mg, dec(st), k1, p),
        pads=pads,
        resume=None if spec.resume is None else
        (lambda s0, st, p: spec.resume(s0, dec(st), p)),
        init=None if spec.init is None else
        (lambda st, p: spec.init(dec(st), p)),
    )


def _encoded_bspec(bspec, encs):
    """Batched counterpart: decode streams inside the BatchSpec bodies."""
    def dec(streams):
        return _decode_streams(streams, encs)

    return dataclasses.replace(
        bspec,
        scan=lambda st, qp, caps: bspec.scan(dec(st), qp, caps),
        apply=lambda mg, st, k1, qp, caps: bspec.apply(
            mg, dec(st), k1, qp, caps),
    )


# ------------------------------------------------------------------ engine
def shard_stack(arr: jnp.ndarray, shards: int, fill=0) -> jnp.ndarray:
    """[m, ...] -> [S, ceil(m/S), ...] contiguous chunks, tail-padded.

    The canonical shard layout shared with ``query.tables.Table
    .stacked_shards``: shard i holds entries [i*n, (i+1)*n) of the
    stream, the final shard tail-padded with ``fill`` when S ∤ m.
    """
    m = arr.shape[0]
    n = -(-m // shards)
    pad = shards * n - m
    if pad:
        row = jnp.broadcast_to(jnp.asarray(fill, arr.dtype),
                               (pad,) + arr.shape[1:])
        arr = jnp.concatenate([arr, row])
    return arr.reshape((shards, n) + arr.shape[1:])


def _unshard(x: jnp.ndarray, m: int) -> jnp.ndarray:
    return x.reshape((-1,) + x.shape[2:])[:m]


def _place_lanes(shard_streams, mesh, axis: str, rec):
    """Lay the stacked [S, n, ...] lanes out on the mesh, each device
    holding its S/D contiguous lanes, before pass 1 reads them.

    Annotates ``stream_bytes_per_device`` (stream bytes resident on each
    mesh device, in mesh order) so a report shows where the stream
    lives. Streams of a multi-process mesh arrive as global arrays
    already laid out by the caller and are left as they are.
    """
    devices = list(mesh.devices.flat)
    if any(d.process_index != jax.process_index() for d in devices):
        return shard_streams
    sharding = NamedSharding(compat.auto_mesh(mesh), P(axis))
    placed = tuple(jax.device_put(s, sharding) for s in shard_streams)
    if rec.active:
        held = dict.fromkeys((d.id for d in devices), 0)
        for s in placed:
            for shard in s.addressable_shards:
                held[shard.device.id] += shard.data.nbytes
        rec.annotate(stream_bytes_per_device=list(held.values()))
    return placed


def _pad_axis1(a: jnp.ndarray, pad: int, fill) -> jnp.ndarray:
    block = jnp.broadcast_to(jnp.asarray(fill, a.dtype),
                             a.shape[:1] + (pad,) + a.shape[2:])
    return jnp.concatenate([a, block], axis=1)


def _apply_chunked(apply_fn, pads_fn, merged, shard_streams, keep1, params,
                   block: int) -> jnp.ndarray:
    """Run an apply body over blocks of entries with ``lax.map``.

    Bounds the [S, n, S*w] pass-2 intermediate at [S, block, S*w]: the
    per-entry compare against the merged state is elementwise over
    entries, so filtering nb blocks sequentially is exact (tested:
    chunked == unchunked in tests/test_mesh_engine.py). Shared between
    the serial specs (``apply_fn=spec.apply``) and the batched engine
    (which closes the batch caps over ``batched.BatchSpec.apply``); the
    pad fills always come from the serial ``spec.pads``.
    """
    S, n = keep1.shape
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        flat = tuple(s.reshape((-1,) + s.shape[2:]) for s in shard_streams)
        fills = pads_fn(flat, params)
        shard_streams = tuple(_pad_axis1(s, pad, f)
                              for s, f in zip(shard_streams, fills))
        keep1 = _pad_axis1(keep1, pad, False)
    # [S, nb*block, ...] -> [nb, S, block, ...] so lax.map walks blocks
    streams_b = tuple(
        jnp.moveaxis(s.reshape((S, nb, block) + s.shape[2:]), 1, 0)
        for s in shard_streams)
    keep_b = jnp.moveaxis(keep1.reshape(S, nb, block), 1, 0)
    out = jax.lax.map(
        lambda xs: apply_fn(merged, xs[0], xs[1], params),
        (streams_b, keep_b))
    return jnp.moveaxis(out, 0, 1).reshape(S, nb * block)[:, :n]


def default_mesh(axis: str = "shards", num_devices: int | None = None):
    """1-D mesh over the first ``num_devices`` (default: all) devices —
    the multi-ToR rack row."""
    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return jax.sharding.Mesh(np.asarray(devs), (axis,))


def _mesh_for_shards(shards: int, axis: str):
    """Largest mesh whose axis size divides S: S lanes spread evenly.

    Using a divisor submesh (rather than rejecting S) keeps mesh mode's
    keep mask identical to two_pass at the same S for every S — the
    lane count, not the device count, is the semantic parameter.
    """
    ndev = len(jax.devices())
    d = max(k for k in range(1, min(ndev, shards) + 1) if shards % k == 0)
    return default_mesh(axis, d)


def _mesh_lanes(shards: int, ndev: int) -> int:
    """Lanes per device (S/D); the one place the mesh modes validate
    that an explicit mesh's axis size divides the lane count."""
    if shards % ndev:
        raise ValueError(
            f"mode='mesh' needs shards divisible by the mesh axis size "
            f"({shards} lanes over {ndev} devices); use shards='auto'")
    return shards // ndev


def _mesh_pass1(spec: _AlgoSpec, shard_streams, params, mesh, axis: str):
    """Pass 1 on the device mesh: S lanes split over the mesh axis.

    Each device scans its S/D contiguous lanes with the vmapped scan
    body; ``out_specs=P(axis)`` all-gathers the per-lane states (and
    keep masks / emissions) back to the caller — the master — in the
    same [S, ...] stacked layout the single-device vmap produces.
    """
    _mesh_lanes(shard_streams[0].shape[0], mesh.shape[axis])
    worker = lambda *local: jax.vmap(
        lambda *sh: spec.scan(sh, params))(*local)
    sm = compat.shard_map(worker, mesh, P(axis), P(axis))
    return sm(*shard_streams)


def _mesh_two_pass_resident(spec: _AlgoSpec, shard_streams, params, mesh,
                            axis: str, apply_block: int | None):
    """Both passes on the mesh: the master never touches the stream.

    One ``shard_map`` covers pass 1 *and* pass 2. Each device scans its
    resident S/D lanes, ``all_gather``s only the compact per-lane states
    across the mesh axis (state_bytes·D wire bytes — the paper's
    "ship state upward, not entries"), folds them into the merged state
    locally (every device computes the identical fold: that *is* the
    broadcast, with the gather and the broadcast fused into one
    collective), and applies the scan-free filter to its own resident
    entries. ``out_specs=P(axis)`` keeps the keep mask device-sharded in
    the stacked [S, n] layout; only the merged state (replicated, O(S·
    state)) and the emissions come back whole.
    """
    ndev = mesh.shape[axis]
    lanes = _mesh_lanes(shard_streams[0].shape[0], ndev)
    # the output structure (does this algorithm emit?) must be known
    # before tracing the shard_map body, so probe it shape-only
    local_shapes = tuple(
        jax.ShapeDtypeStruct((lanes,) + s.shape[1:], s.dtype)
        for s in shard_streams)
    r1_shape = jax.eval_shape(
        lambda *sh: jax.vmap(lambda *x: spec.scan(x, params))(*sh),
        *local_shapes)
    has_emitted = r1_shape.emitted is not None

    def worker(*local):
        r1 = jax.vmap(lambda *sh: spec.scan(sh, params))(*local)
        gathered = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True),
            r1.state)
        merged = spec.merge(gathered, params)
        lane0 = jax.lax.axis_index(axis) * lanes
        p2 = dict(params,
                  _lane_ids=lane0 + jnp.arange(lanes, dtype=jnp.int32))
        if apply_block and spec.chunkable \
                and apply_block < local[0].shape[1]:
            keep2 = _apply_chunked(spec.apply, spec.pads, merged, local,
                                   r1.keep, p2, apply_block)
        else:
            keep2 = spec.apply(merged, local, r1.keep, p2)
        return ((keep2, merged, r1.emitted) if has_emitted
                else (keep2, merged))

    out_specs = (P(axis), P()) + ((P(axis),) if has_emitted else ())
    sm = compat.shard_map(worker, mesh, P(axis), out_specs)
    out = sm(*shard_streams)
    emitted = None
    if has_emitted:
        emitted = jax.tree_util.tree_map(
            lambda e: e.reshape((-1,) + e.shape[2:]), out[2])
    return out[0], out[1], emitted


def unshard_mask(keep: jnp.ndarray, m: int) -> jnp.ndarray:
    """Stacked [S, n] keep mask (possibly device-sharded) -> flat bool[m].

    The inverse of ``shard_stack`` for masks: concatenate the lanes in
    stream order and drop the tail pads. This is the only gather a
    ``pass2="mesh"`` consumer ever needs — O(m) mask bools cross to the
    master, never the entry stream itself.
    """
    return _unshard(keep, m)


def apply_merged(algo: str, merged, shard_streams, keep1, **params):
    """The scan-free pass-2 filter body for `algo` on stacked lanes.

    keep = filter(merged_state, entries) — elementwise over entries, no
    positional state. Exposed because three callers share it: the
    master-side two_pass/mesh apply, the per-device resident pass 2
    (``pass2="mesh"``), and the jnp mirrors of the Pallas grid-parallel
    kernels (``kernels.parallel.*_parallel_ref``). ``keep1`` is the
    pass-1 mask (only DISTINCT and GROUP BY consult it).
    """
    return _SPECS[algo].apply(merged, tuple(shard_streams), keep1, params)


def _per_shard_state_bytes(spec: _AlgoSpec, shard_streams, params) -> int:
    """Shape-only probe of one lane's switch-state footprint.

    The planner's pass-2 placement rule charges the *merged* S-lane
    state (that is what the resident broadcast ships), so callers scale
    this by the lane count before handing it to ``optimal_pass2``."""
    shapes = jax.eval_shape(
        lambda *sh: jax.vmap(lambda *x: spec.scan(x, params))(*sh).state,
        *shard_streams)
    total = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(shapes))
    return total // shard_streams[0].shape[0]


def _obs_mask_counts(rec, keep, m: int, *, encoded: bool = False,
                     queries: int = 1) -> None:
    """Feed the per-call mask counters from a materialized keep mask.

    Handles every layout the engine returns: flat bool[m], stacked
    [S, n] (tail pads dropped), and either with a leading Q axis.
    Host-side only — the recorder is NULL under jit — and the kept
    count is skipped when the mask spans non-addressable devices
    (multi-process mesh pass 2), where a host read is illegal.
    """
    scanned = int(m) * queries
    rec.count("entries_scanned", scanned)
    if not getattr(keep, "is_fully_addressable", True):
        return
    with rec.span("to_host"):
        flat = np.asarray(rec.sync(keep)).reshape(queries, -1)[:, :m]
    kept = int(flat.sum())
    rec.count("entries_kept", kept)
    if encoded and scanned:
        # late materialization: only survivors' codes are ever decoded
        rec.count("decode_skipped_ratio", 1.0 - kept / scanned)


def _state_nbytes(state) -> int:
    """Total bytes of a state pytree (sync-free: nbytes is metadata)."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(state))


def merge_states(algo: str, stacked_states, **params):
    """Fold S shard-local switch states into one global state.

    ``stacked_states`` is the pytree a vmapped scan returns: every array
    leaf carries a leading shard axis. Exposed for tests and for callers
    that run pass 1 themselves (e.g. the Pallas grid-parallel kernels).
    """
    return _SPECS[algo].merge(stacked_states, params)


# -------------------------------------------------- adaptive S selection
# (algo, param signature) -> (merge_byte_cost c, per-shard state_bytes).
# c is in the planner's units: master cost of folding one shipped state
# byte, measured in per-entry stream work — T(S) = m/S + c·S·state_bytes.
_CALIBRATION: dict[tuple, tuple[float, int]] = {}

_PROBE_SHARDS = 4
_PROBE_N = 256  # entries per probe shard


def _probe_streams(streams, algo: str) -> tuple:
    """Concrete miniature streams with the real dtypes/trailing shapes.

    Built from shapes only — never from values — so calibration also
    works when ``engine_prune`` is called under ``jax.jit`` and the
    streams are tracers.
    """
    rng = np.random.default_rng(0)
    m = _PROBE_SHARDS * _PROBE_N
    out = []
    for s in streams:
        shape = (m,) + tuple(s.shape[1:])
        if jnp.issubdtype(s.dtype, jnp.floating):
            out.append(jnp.asarray(
                (rng.random(shape) * 100 + 1).astype(np.float32)
            ).astype(s.dtype))
        elif s.dtype == jnp.bool_:
            out.append(jnp.ones(shape, jnp.bool_))
        else:
            out.append(jnp.asarray(
                rng.integers(1, 1000, shape)).astype(s.dtype))
    return tuple(out)


def _time_us(fn, *args) -> float:
    fn(*args)  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return sorted(times)[1]


def calibrate_merge_cost(algo: str, streams, params) -> tuple[float, int]:
    """Measure the real merge cost for `algo` once; cached per signature.

    Runs pass 1 on a tiny synthetic stream, times (a) the per-entry scan
    and (b) the S-state merge, and returns (c, state_bytes) where c is
    the measured merge cost per shipped state byte in per-entry units —
    the empirical constant for ``planner.optimal_shards``. The result is
    recorded in ``planner.MEASURED_MERGE_COSTS`` so planning code (and
    ROADMAP bookkeeping) can see the constants the engine actually uses.
    """
    key = (algo,
           tuple((str(s.dtype), tuple(s.shape[1:])) for s in streams),
           tuple(sorted(
               (k, v) for k, v in params.items()
               if isinstance(v, (int, float, str, bool)))))
    if key in _CALIBRATION:
        return _CALIBRATION[key]
    spec = _SPECS[algo]
    probes = _probe_streams(streams, algo)
    shard_probes = tuple(shard_stack(s, _PROBE_SHARDS) for s in probes)
    pass1 = jax.jit(lambda *sh: jax.vmap(
        lambda *x: spec.scan(x, params))(*sh).state)
    stacked = pass1(*shard_probes)
    state_bytes = int(sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(stacked))
        // _PROBE_SHARDS)
    us_scan = _time_us(
        jax.jit(lambda *x: spec.scan(x, params).keep), *probes)
    us_merge = _time_us(
        jax.jit(lambda st: spec.merge(st, params)), stacked)
    per_entry = max(us_scan / (_PROBE_SHARDS * _PROBE_N), 1e-9)
    c = (us_merge / max(_PROBE_SHARDS * state_bytes, 1)) / per_entry
    _CALIBRATION[key] = (c, state_bytes)
    planner.MEASURED_MERGE_COSTS[algo] = c
    return c, state_bytes


def _resolve_shards(algo: str, streams, params, mode: str, shards,
                    ndev: int) -> int:
    """Turn shards=None/"auto" into a concrete lane count for `mode`.

    ndev is the mesh axis size (1 outside mesh mode). Auto-resolved
    counts are clamped to the stream length (a multiple of ndev in mesh
    mode); explicit ints are passed through and validated by
    ``engine_prune`` / ``_mesh_pass1``.
    """
    m = streams[0].shape[0]
    if isinstance(shards, int):
        return shards
    if shards is None:
        return ndev if mode == "mesh" else min(8, m)
    if shards != "auto":
        raise ValueError(
            f"shards must be an int, None or 'auto', got {shards!r}")
    if mode == "scan":
        return 1
    c, state_bytes = calibrate_merge_cost(algo, streams, params)
    s = planner.optimal_shards(m, state_bytes, merge_byte_cost=c)
    if mode == "mesh":
        if m < ndev:
            raise ValueError(
                f"stream length {m} is shorter than the mesh axis "
                f"({ndev} devices)")
        s = -(-s // ndev) * ndev           # round up to a lane multiple
        s = min(s, m // ndev * ndev)       # ...but never past the stream
        return max(s, ndev)
    return max(1, min(s, m))


def engine_prune(algo: str, *streams, options: ExecOptions | None = None,
                 mode: str | None = None,
                 shards: int | str | None = None, mesh=None,
                 mesh_axis: str = "shards", apply_block: int | None = None,
                 pass2: str | None = None, tune: str | None = None,
                 plan_cache=None, encoding=None, decode: str | None = None,
                 obs: str | None = None, **params) -> PruneResult:
    """Run pruner `algo` over its stream(s) in the requested mode.

    streams: the algorithm's data arrays, all sharing leading dim m
    (topn/distinct/skyline: one array; groupby: keys, values and an
    optional bool validity column; having: keys, values — having
    accepts values=None for COUNT). Non-divisible m is handled by
    tail-padding the final shard with algorithm-safe neutral entries.

    shards: lane count S. ``None`` keeps the historical defaults (8 for
    sharded/two_pass, one lane per device for mesh); ``"auto"`` sizes S
    from the planner's T(S) = m/S + c·S·state_bytes model with the
    measured (cached) per-algorithm merge cost c.

    mesh / mesh_axis: for ``mode="mesh"`` — the ``jax.sharding`` mesh to
    run pass 1 on. Default: a 1-D mesh over the largest device count
    that divides S, so any S works. An explicit mesh requires S to be
    a multiple of its axis size; each device scans S/D lanes.

    apply_block: chunk size for the DISTINCT/SKYLINE pass-2 filter
    (``lax.map`` over entry blocks). Defaults to unchunked except in
    mesh mode, where large S is the point and the [S·n, S·w] compare
    would otherwise bound it.

    tune: ``"off"`` (default) runs exactly the mode/shards/pass2/
    apply_block given here. ``"cached"`` replays a previously raced
    plan from the persisted plan cache (miss -> the analytic plan);
    ``"race"`` additionally races the planner's mask-preserving
    candidate grid on a stream prefix on a miss and persists the
    winner. Both override mode/shards/pass2/apply_block entirely and
    need concrete (non-traced) streams; the keep mask is always
    returned flat over m and is bit-identical to the analytic plan's
    mask — tuning changes speed, never results. ``plan_cache``: a
    ``plancache.PlanCache`` (default: the ``REPRO_PLAN_CACHE`` file).

    pass2: where mode="mesh" applies the merged state — ``"master"``
    (gather everything, filter the full stream there), ``"mesh"``
    (broadcast the merged state, filter each device's resident shard;
    the keep mask stays device-sharded in the stacked [S, n] layout —
    flatten with ``unshard_mask``), or ``"auto"`` (the planner's
    m·f vs state_bytes·D + (m/D)·f placement rule).

    options: an ``ExecOptions`` bundling mode/shards/pass2/apply_block/
    tune/plan_cache/decode/obs; the individual kwargs keep working and
    conflicts warn (options= wins).

    obs: telemetry level (``"off"``/``"counters"``/``"trace"``, default
    the process-wide ``repro.obs.default_level()``, normally
    "counters"). Anything above "off" attaches an ``ExecReport`` to
    ``result.report`` (pruning counters, shipped-state bytes, merge
    collectives; "trace" adds wall-clock spans to ``repro.obs.TRACER``
    for Chrome-trace export). Instruments run strictly host-side on
    materialized outputs — never inside jit — so the keep mask is
    bit-identical at every level; under ``jax.jit`` the recorder
    degrades to a no-op and ``result.report`` is None.

    encoding / decode: prune-before-decode. ``encoding`` is a
    ``DictEncoding`` (stream 0) or a per-stream tuple of
    ``DictEncoding | None``; encoded streams carry uint32 codes and the
    engine fuses the ``lut[code]`` gather into pass 1, so the keep mask
    is bit-identical to pruning the eagerly decoded streams while the
    decoded column is never materialized. ``decode="eager"`` decodes
    everything up front instead (the differential baseline);
    ``"auto"``/``"late"`` (default) prune on codes.

    Returns a PruneResult whose keep mask is over the original m
    entries (stacked [S, n] over the padded stream when pass2 resolves
    to "mesh"). state is the stacked per-shard states (`sharded`), the
    merged global state (`two_pass`/`mesh`), or the final scan state
    (`scan`).
    """
    opts = ExecOptions.resolve(options, mode=mode, shards=shards,
                               pass2=pass2, apply_block=apply_block,
                               tune=tune, plan_cache=plan_cache,
                               decode=decode, obs=obs)
    mode = opts.mode if opts.mode is not None else "scan"
    shards = opts.shards
    pass2 = opts.pass2 if opts.pass2 is not None else "master"
    apply_block = opts.apply_block
    tune = opts.tune if opts.tune is not None else "off"
    plan_cache = opts.plan_cache
    decode = opts.decode if opts.decode is not None else "auto"

    streams = tuple(s for s in streams if s is not None)
    encs = normalize_encodings(encoding, len(streams))
    encoded = any(e is not None for e in encs)
    if encoded and decode == "eager":
        streams = _decode_streams(streams, encs)
        encs = (None,) * len(streams)
        encoded = False

    if tune != "off":
        if tune not in planner.TUNE_MODES:
            raise ValueError(f"tune must be one of {planner.TUNE_MODES}, "
                             f"got {tune!r}")
        if any(isinstance(s, jax.core.Tracer) for s in streams):
            raise ValueError(
                "tune= needs concrete streams (the race times real "
                "executions) — call outside jit, or pass tune='off'")
        # the race runs candidates on the raw code streams (uniform
        # across candidates, so the comparison is fair); the winning
        # plan then executes with the decode gather fused in
        resolved = planner.resolve_plan(algo, streams, params,
                                        tune_mode=tune, cache=plan_cache,
                                        obs=opts.obs)
        return execute_plan(algo, *streams, plan=resolved.plan,
                            encoding=encs if encoded else None,
                            obs=opts.obs, **params)
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if pass2 not in PASS2:
        raise ValueError(f"pass2 must be one of {PASS2}, got {pass2!r}")
    if pass2 != "master" and mode != "mesh":
        raise ValueError(
            f"pass2={pass2!r} only applies to mode='mesh' (got {mode!r})")
    spec = _SPECS[algo]  # KeyError = unknown algorithm
    m = streams[0].shape[0]

    if mode == "mesh":
        ndev = (mesh.shape[mesh_axis] if mesh is not None
                else len(jax.devices()))
    else:
        ndev = 1
    shards = _resolve_shards(algo, streams, params, mode, shards, ndev)
    rec = obsreport.recorder("engine_prune", opts.obs, streams=streams)
    if rec.active:
        rec.annotate(algo=algo, mode=mode, shards=shards, m=int(m),
                     encoded=encoded)
        if mode == "mesh":
            rec.annotate(pass2=pass2, num_devices=ndev)
    if mode == "scan" or (shards <= 1 and mode != "mesh"):
        # mesh keeps its documented output contract even at S=1 (the
        # degenerate 1-lane mesh: stacked mask, merged state) instead of
        # silently returning the scan's flat mask and raw scan state
        if encoded:
            spec = _encoded_spec(
                algo, spec, _padded_encodings(algo, spec, encs, streams,
                                              params))
        with rec.span("scan", m=int(m)):
            res = rec.sync(spec.scan(streams, params))
        if rec.active:
            _obs_mask_counts(rec, res.keep, m, encoded=encoded)
            res.report = rec.finish()
        return res
    if shards > m:
        raise ValueError(f"shards={shards} exceeds stream length {m}")
    if mode == "mesh" and mesh is None:
        mesh = _mesh_for_shards(shards, mesh_axis)

    if m % shards and spec.pad_validity and len(streams) < 3:
        # pads must be inert under *any* aggregate: append a validity
        # column (True for real entries) the scan body gates folds on
        streams = streams + (jnp.ones(m, jnp.bool_),)
        encs = encs + (None,)
    if encoded:
        # from here on every body (scan/apply/resume/pads) runs on the
        # wrapped spec: decode is fused into pass 1, and pads become
        # code-space fills that decode to the plain path's fills
        encs = _padded_encodings(algo, spec, encs, streams, params)
        spec = _encoded_spec(algo, spec, encs)
    # pads are only consulted when the final shard actually needs filling
    fills = (spec.pads(streams, params) if m % shards
             else (0,) * len(streams))
    shard_streams = tuple(shard_stack(s, shards, f)
                          for s, f in zip(streams, fills))
    if mode == "mesh":
        shard_streams = _place_lanes(shard_streams, mesh, mesh_axis, rec)
    if apply_block is None and mode == "mesh" and spec.chunkable:
        apply_block = DEFAULT_MESH_APPLY_BLOCK

    if mode == "mesh" and pass2 == "auto":
        # the broadcast ships the merged state: S x the per-lane bytes
        # (same units as plan_multi_switch's merge_bytes)
        state_bytes = shards * _per_shard_state_bytes(
            spec, shard_streams, params)
        pass2 = planner.optimal_pass2(m, mesh.shape[mesh_axis],
                                      state_bytes)
    if mode == "mesh" and pass2 == "mesh":
        with rec.span("resident_fused", shards=shards,
                      num_devices=mesh.shape[mesh_axis]):
            keep2, merged, emitted = _mesh_two_pass_resident(
                spec, shard_streams, params, mesh, mesh_axis, apply_block)
            rec.sync(keep2)
        res = PruneResult(keep=keep2, state=merged, emitted=emitted)
        if rec.active:
            # the resident all_gather ships every lane's state to every
            # device: S x per-lane bytes x D wire traffic (the merged
            # state's nbytes would undercount — e.g. TOP-N folds S
            # thresholds into one scalar)
            rec.count("merge_collective_count", 1)
            rec.count("state_bytes_shipped",
                      shards * _per_shard_state_bytes(
                          spec, shard_streams, params)
                      * mesh.shape[mesh_axis])
            _obs_mask_counts(rec, res.keep, m, encoded=encoded)
            res.report = rec.finish()
        return res

    with rec.span("pass1", mode=mode, shards=shards):
        if mode == "mesh":
            r1 = _mesh_pass1(spec, shard_streams, params, mesh, mesh_axis)
        else:
            r1 = jax.vmap(lambda *sh: spec.scan(sh, params))(
                *shard_streams)
        rec.sync(r1.state)
    # emissions are switch→master traffic, not per-entry masks: keep the
    # full padded length — a tail pad can evict a REAL partial (GROUP BY)
    # whose emission sits past position m and must still reach the master
    emitted = (None if r1.emitted is None
               else jax.tree_util.tree_map(
                   lambda e: e.reshape((-1,) + e.shape[2:]), r1.emitted))

    if mode == "sharded" and not spec.sharded_needs_merge:
        res = PruneResult(keep=_unshard(r1.keep, m), state=r1.state,
                          emitted=emitted)
        if rec.active:
            _obs_mask_counts(rec, res.keep, m, encoded=encoded)
            res.report = rec.finish()
        return res

    if rec.active:
        # the stacked pass-1 states are exactly what crosses the wire
        # to the master: S lanes x per-lane state bytes
        rec.count("merge_collective_count", 1)
        rec.count("state_bytes_shipped", _state_nbytes(r1.state))
    with rec.span("gather_merge", shards=shards):
        merged = rec.sync(spec.merge(r1.state, params))
    with rec.span("pass2_apply", apply_block=apply_block or 0):
        if apply_block and spec.chunkable \
                and apply_block < shard_streams[0].shape[1]:
            keep2 = _apply_chunked(spec.apply, spec.pads, merged,
                                   shard_streams, r1.keep, params,
                                   apply_block)
        else:
            keep2 = spec.apply(merged, shard_streams, r1.keep, params)
        rec.sync(keep2)
    res = PruneResult(keep=_unshard(keep2, m), state=merged,
                      emitted=emitted)
    if rec.active:
        _obs_mask_counts(rec, res.keep, m, encoded=encoded)
        res.report = rec.finish()
    return res


def execute_plan(algo: str, *streams, plan, encoding=None,
                 obs: str | None = None, **params) -> PruneResult:
    """Run one tuned/analytic ``planner.Plan`` through the engine.

    The uniform execution contract behind `tune=`: every plan in the
    tuner's universe maps onto the two-pass family at the plan's fixed
    lane count, so the returned keep mask is bit-identical across all
    plans for the same stream — and it is ALWAYS returned flat over the
    original m entries (resident pass-2 masks are unstacked here), so
    callers never see plan-dependent layouts.
    """
    streams = tuple(s for s in streams if s is not None)
    m = int(streams[0].shape[0])
    if plan.mode == "mesh":
        mesh = default_mesh("shards", num_devices=plan.num_devices)
        res = engine_prune(algo, *streams, mode="mesh",
                           shards=plan.shards, mesh=mesh,
                           apply_block=plan.apply_block,
                           pass2=plan.pass2, encoding=encoding, obs=obs,
                           **params)
        keep = res.keep
        if keep.ndim == 2:  # resident pass 2: stacked [S, n]
            keep = unshard_mask(keep, m)
        # masks from different device spreads must compose: commit the
        # flat mask to the default device instead of leaving it sharded
        # over whatever mesh this plan happened to run on
        out = dataclasses.replace(
            res, keep=jax.device_put(keep, jax.devices()[0]))
        # replace() rebuilds from fields only; re-attach the telemetry
        out.report = res.report
        return out
    return engine_prune(algo, *streams, mode="two_pass",
                        shards=plan.shards, encoding=encoding,
                        apply_block=plan.apply_block, obs=obs, **params)


def execute_plan_batch(algo: str, queries, *streams, plan,
                       encoding=None,
                       device_budget_bytes: int | None = None,
                       obs: str | None = None) -> BatchPruneResult:
    """Batched counterpart of ``execute_plan``: one tuned plan for Q
    same-family queries over shared streams. The keep mask comes back
    flat bool[Q, m] regardless of where pass 2 ran."""
    streams = tuple(s for s in streams if s is not None)
    m = int(streams[0].shape[0])
    kwargs = dict(shards=plan.shards, apply_block=plan.apply_block,
                  encoding=encoding, obs=obs,
                  device_budget_bytes=device_budget_bytes)
    if plan.mode == "mesh":
        mesh = default_mesh("shards", num_devices=plan.num_devices)
        res = engine_prune_batch(algo, queries, *streams, mode="mesh",
                                 mesh=mesh, pass2=plan.pass2, **kwargs)
    else:
        res = engine_prune_batch(algo, queries, *streams,
                                 mode="two_pass", **kwargs)
    if res.keep.ndim == 3:  # resident pass 2: stacked [Q, S, n]
        report = res.report
        res = dataclasses.replace(res,
                                  keep=unshard_mask_batch(res.keep, m))
        res.report = report  # replace() rebuilds from fields only
    return res


def reset_caches() -> None:
    """Forget every measured constant this process has accumulated:
    the merge-cost calibration table and the planner's mirror of it.
    Tests reset these between cases (autouse fixture in conftest) so no
    test's plan depends on which test calibrated first. The *persisted*
    plan cache is per-file — point ``REPRO_PLAN_CACHE`` at a temp dir
    or call ``plancache.PlanCache().clear()``."""
    _CALIBRATION.clear()
    planner.MEASURED_MERGE_COSTS.clear()


# ------------------------------------------------- multi-query batching
MODES_BATCH = ("scan", "two_pass", "mesh")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BatchPruneResult:
    """Q queries' worth of ``PruneResult``: leading axis Q on every leaf.

    keep: bool[Q, m] (stacked bool[Q, S, n] when pass 2 ran resident —
    flatten with ``unshard_mask_batch``). state/emitted follow the same
    per-mode contract as ``engine_prune`` with a leading Q axis; shape
    params are padded to the batch max, so e.g. a query with w=3 in a
    w_max=8 batch reports an 8-wide state whose slots past 3 are inert
    pads. ``plan`` is the admission plan the batch ran under (static
    metadata — waves, per-query byte charges, budget).
    """

    keep: jnp.ndarray
    state: Any = None
    emitted: Any = None
    plan: Any = dataclasses.field(default=None,
                                  metadata=dict(static=True))

    # telemetry (repro.obs.ExecReport), attached host-side. A class
    # attribute rather than a field so pytree flattening never sees it
    # (a static field would have to be hashable and would split jit
    # caches on telemetry contents).
    report = None


def unshard_mask_batch(keep: jnp.ndarray, m: int) -> jnp.ndarray:
    """Stacked [Q, S, n] batch keep masks -> flat bool[Q, m].

    The batch analogue of ``unshard_mask``: per query, concatenate the
    lanes in stream order and drop the tail pads.
    """
    return keep.reshape(keep.shape[0], -1)[:, :m]


def _batch_query_bytes(bspec, qp, caps, lane_shapes, lanes: int) -> int:
    """One query's device-resident state charge: padded per-lane switch
    state (shape-only probe of the *batched* scan, so batch-max caps are
    what is charged) times the lane count the resident broadcast ships.
    """
    qp0 = jax.tree_util.tree_map(lambda a: a[0], qp)
    shapes = jax.eval_shape(
        lambda *sh: bspec.scan(sh, qp0, caps).state, *lane_shapes)
    per_lane = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(shapes))
    return per_lane * lanes


def _batch_pass2_host(bspec, pads_fn, shard_streams, qp_w, caps, r1,
                      apply_block):
    """Host-side merge + scan-free filter, vmapped over the wave's
    queries. Shared by mode="two_pass" and mesh pass2="master"."""
    S = shard_streams[0].shape[0]
    lane_ids = jnp.arange(S, dtype=jnp.int32)
    apply_fn = lambda mg, xs, kp, p: bspec.apply(mg, xs, kp, p, caps)

    def pass2(qp1, st1, keep1):
        merged = bspec.merge(st1, qp1, caps)
        qp2 = dict(qp1, _lane_ids=lane_ids)
        if apply_block and bspec.chunkable \
                and apply_block < shard_streams[0].shape[1]:
            keep2 = _apply_chunked(apply_fn, pads_fn, merged,
                                   shard_streams, keep1, qp2, apply_block)
        else:
            keep2 = bspec.apply(merged, shard_streams, keep1, qp2, caps)
        return keep2, merged

    keep2, merged = jax.vmap(pass2)(qp_w, r1.state, r1.keep)
    return keep2, merged, r1.emitted


def _run_wave_two_pass(bspec, pads_fn, shard_streams, qp_w, caps,
                       apply_block):
    r1 = jax.vmap(lambda qp1: jax.vmap(
        lambda *sh: bspec.scan(sh, qp1, caps))(*shard_streams))(qp_w)
    return _batch_pass2_host(bspec, pads_fn, shard_streams, qp_w, caps,
                             r1, apply_block)


def _run_wave_mesh_master(bspec, pads_fn, shard_streams, qp_w, caps,
                          mesh, axis, apply_block):
    _mesh_lanes(shard_streams[0].shape[0], mesh.shape[axis])
    worker = lambda qp, *local: jax.vmap(lambda qp1: jax.vmap(
        lambda *sh: bspec.scan(sh, qp1, caps))(*local))(qp)
    in_specs = (P(),) + (P(axis),) * len(shard_streams)
    sm = compat.shard_map(worker, mesh, in_specs, P(None, axis))
    r1 = sm(qp_w, *shard_streams)
    return _batch_pass2_host(bspec, pads_fn, shard_streams, qp_w, caps,
                             r1, apply_block)


def _run_wave_mesh_resident(bspec, pads_fn, shard_streams, qp_w, caps,
                            mesh, axis, apply_block):
    """Both passes on the mesh for a whole admission wave.

    The batch analogue of ``_mesh_two_pass_resident``, with the fused
    collective the tentpole is about: pass 1 vmaps the per-query scan
    over the wave *outside* the per-lane vmap, so every per-lane state
    leaf carries a leading Q axis, and the single ``all_gather`` per
    leaf ships all Q queries' states in one collective instead of Q
    separate dispatches. Every device then folds + applies each query's
    merged state against its resident entries once.
    """
    ndev = mesh.shape[axis]
    lanes = _mesh_lanes(shard_streams[0].shape[0], ndev)
    local_shapes = tuple(
        jax.ShapeDtypeStruct((lanes,) + s.shape[1:], s.dtype)
        for s in shard_streams)
    qp_probe = jax.tree_util.tree_map(lambda a: a[:1], qp_w)
    r1_shape = jax.eval_shape(
        lambda *sh: jax.vmap(lambda qp1: jax.vmap(
            lambda *x: bspec.scan(x, qp1, caps))(*sh))(qp_probe),
        *local_shapes)
    has_emitted = r1_shape.emitted is not None
    apply_fn = lambda mg, xs, kp, p: bspec.apply(mg, xs, kp, p, caps)

    def worker(qp, *local):
        r1 = jax.vmap(lambda qp1: jax.vmap(
            lambda *sh: bspec.scan(sh, qp1, caps))(*local))(qp)
        # ONE fused collective: each state leaf is [Q, lanes, ...], so a
        # single all_gather per leaf moves every query's states at once
        gathered = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, axis, axis=1, tiled=True),
            r1.state)
        lane0 = jax.lax.axis_index(axis) * lanes
        lane_ids = lane0 + jnp.arange(lanes, dtype=jnp.int32)

        def pass2(qp1, st1, keep1):
            merged = bspec.merge(st1, qp1, caps)
            qp2 = dict(qp1, _lane_ids=lane_ids)
            if apply_block and bspec.chunkable \
                    and apply_block < local[0].shape[1]:
                keep2 = _apply_chunked(apply_fn, pads_fn, merged, local,
                                       keep1, qp2, apply_block)
            else:
                keep2 = bspec.apply(merged, local, keep1, qp2, caps)
            return keep2, merged

        keep2, merged = jax.vmap(pass2)(qp, gathered, r1.keep)
        return ((keep2, merged, r1.emitted) if has_emitted
                else (keep2, merged))

    in_specs = (P(),) + (P(axis),) * len(shard_streams)
    out_specs = ((P(None, axis), P())
                 + ((P(None, axis),) if has_emitted else ()))
    sm = compat.shard_map(worker, mesh, in_specs, out_specs)
    out = sm(qp_w, *shard_streams)
    return out[0], out[1], (out[2] if has_emitted else None)


def _concat_waves(parts):
    if len(parts) == 1:
        return parts[0]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *parts)


def engine_prune_batch(algo: str, queries, *streams,
                       options: ExecOptions | None = None,
                       mode: str | None = None,
                       shards: int | None = None, mesh=None,
                       mesh_axis: str = "shards",
                       apply_block: int | None = None,
                       pass2: str | None = None,
                       encoding=None, decode: str | None = None,
                       obs: str | None = None,
                       device_budget_bytes: int | None = None
                       ) -> BatchPruneResult:
    """Run Q same-family queries over shared stream(s) as one program.

    queries: list of per-query param dicts (the ``**params`` a serial
    ``engine_prune`` call would take — mixed N/w/d/thresholds/seeds are
    fine; shape params are padded to the batch max with validity masking
    so every query's mask stays bit-identical to its serial run).
    Family-static params (policy/score/agg, and which side of 2^16 the
    hash modulus sits on) must agree across the batch —
    ``query.run_queries`` groups specs so they do.

    mode: "scan" (vmapped sequential scans), "two_pass" (host merge +
    filter) or "mesh". ``pass2`` applies to mode="mesh" only and
    defaults to "mesh" — the resident path is the point of batching:
    one ``shard_map`` dispatch, one fused state collective, one
    resident filter sweep per device for all Q queries. ``shards`` must
    be a concrete lane count (``"auto"`` calibration is per-query).

    device_budget_bytes: the §8 per-device memory budget. Every query
    is charged its all-gathered padded state (S × per-lane bytes);
    ``planner.plan_query_batch`` splits the batch into sequential
    admission waves when the charges don't fit together. All waves run
    with the *global* batch caps so their results concatenate along Q.

    Returns ``BatchPruneResult`` — keep bool[Q, m], stacked
    bool[Q, S, n] when pass 2 ran resident (``unshard_mask_batch``
    flattens), with the admission plan attached.
    """
    opts = ExecOptions.resolve(options, mode=mode, shards=shards,
                               pass2=pass2, apply_block=apply_block,
                               decode=decode, obs=obs)
    opts.require_unset("engine_prune_batch", "tune", "plan_cache")
    mode = opts.mode if opts.mode is not None else "two_pass"
    shards = opts.shards
    pass2 = opts.pass2
    apply_block = opts.apply_block
    decode = opts.decode if opts.decode is not None else "auto"
    if mode not in MODES_BATCH:
        raise ValueError(
            f"mode must be one of {MODES_BATCH}, got {mode!r} "
            f"(mode='sharded' has no batched variant: use 'two_pass')")
    if pass2 is not None:
        if pass2 not in PASS2:
            raise ValueError(
                f"pass2 must be one of {PASS2}, got {pass2!r}")
        if mode != "mesh":
            raise ValueError(
                f"pass2={pass2!r} only applies to mode='mesh' "
                f"(got {mode!r})")
    bspec = batched.BSPECS[algo]  # KeyError = unknown algorithm
    spec = _SPECS[algo]
    queries = list(queries)
    if not queries:
        raise ValueError("engine_prune_batch needs at least one query")
    qp, caps = bspec.build(queries)
    streams = tuple(s for s in streams if s is not None)
    encs = normalize_encodings(encoding, len(streams))
    encoded = any(e is not None for e in encs)
    if encoded and decode == "eager":
        streams = _decode_streams(streams, encs)
        encs = (None,) * len(streams)
        encoded = False
    m = streams[0].shape[0]

    ndev = ((mesh.shape[mesh_axis] if mesh is not None
             else len(jax.devices())) if mode == "mesh" else 1)
    if shards is None:
        shards = ndev if mode == "mesh" else min(8, m)
    if not isinstance(shards, int):
        raise ValueError(
            f"engine_prune_batch needs a concrete lane count, got "
            f"shards={shards!r} ('auto' calibration is per-query)")
    scan_only = mode == "scan" or (shards <= 1 and mode != "mesh")

    if scan_only:
        if encoded:
            encs = _padded_encodings(algo, spec, encs, streams, {})
            bspec = _encoded_bspec(bspec, encs)
        lane_shapes = tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                            for s in streams)
        per_query = _batch_query_bytes(bspec, qp, caps, lane_shapes, 1)
        shard_streams = None
    else:
        if shards > m:
            raise ValueError(
                f"shards={shards} exceeds stream length {m}")
        if mode == "mesh" and mesh is None:
            mesh = _mesh_for_shards(shards, mesh_axis)
        if m % shards and spec.pad_validity and len(streams) < 3:
            streams = streams + (jnp.ones(m, jnp.bool_),)
            encs = encs + (None,)
        if encoded:
            encs = _padded_encodings(algo, spec, encs, streams, {})
            spec = _encoded_spec(algo, spec, encs)
            bspec = _encoded_bspec(bspec, encs)
        fills = (spec.pads(streams, {}) if m % shards
                 else (0,) * len(streams))
        shard_streams = tuple(shard_stack(s, shards, f)
                              for s, f in zip(streams, fills))
        if apply_block is None and mode == "mesh" and bspec.chunkable:
            apply_block = DEFAULT_MESH_APPLY_BLOCK
        lane_shapes = tuple(
            jax.ShapeDtypeStruct(s.shape[1:], s.dtype)
            for s in shard_streams)
        per_query = _batch_query_bytes(bspec, qp, caps, lane_shapes,
                                       shards)

    plan = planner.plan_query_batch([per_query] * len(queries),
                                    device_budget_bytes)
    rec = obsreport.recorder("engine_prune_batch", opts.obs,
                             streams=(streams, qp))
    if rec.active:
        rec.annotate(algo=algo, mode=mode, queries=len(queries),
                     shards=int(shards), m=int(m), encoded=encoded,
                     waves=len(plan.waves))

    if mode == "mesh":
        shard_streams = _place_lanes(shard_streams, mesh, mesh_axis, rec)
        p2 = pass2 or "mesh"
        if p2 == "auto":
            # charge the largest wave's resident broadcast; one global
            # placement keeps the keep-mask layout uniform across waves
            wave_bytes = per_query * max(len(w) for w in plan.waves)
            p2 = planner.optimal_pass2(m, mesh.shape[mesh_axis],
                                       wave_bytes)
    else:
        p2 = None

    parts = []
    for wi, wave in enumerate(plan.waves):
        idx = np.asarray(wave)
        qp_w = jax.tree_util.tree_map(lambda a: a[idx], qp)
        with rec.span(f"wave{wi}", queries=len(wave), mode=mode):
            if scan_only:
                r = jax.vmap(
                    lambda qp1: bspec.scan(streams, qp1, caps))(qp_w)
                parts.append((r.keep, r.state, r.emitted))
            elif mode == "mesh" and p2 == "mesh":
                parts.append(_run_wave_mesh_resident(
                    bspec, spec.pads, shard_streams, qp_w, caps, mesh,
                    mesh_axis, apply_block))
            elif mode == "mesh":
                parts.append(_run_wave_mesh_master(
                    bspec, spec.pads, shard_streams, qp_w, caps, mesh,
                    mesh_axis, apply_block))
            else:
                parts.append(_run_wave_two_pass(
                    bspec, spec.pads, shard_streams, qp_w, caps,
                    apply_block))
            rec.sync(parts[-1][0])
        if rec.active and not scan_only:
            # per wave: one merge collective over all its queries'
            # states. per_query is already S x per-lane bytes (the
            # admission charge); the resident all_gather additionally
            # lands a copy on every device.
            rec.count("merge_collective_count", 1)
            rec.count("state_bytes_shipped",
                      per_query * len(wave)
                      * (ndev if (mode == "mesh" and p2 == "mesh")
                         else 1))
    keep, state, emitted = _concat_waves(parts)

    order = np.concatenate([np.asarray(w, np.int64) for w in plan.waves])
    if not np.array_equal(order, np.arange(len(queries))):
        inv = np.argsort(order)
        keep = keep[inv]
        state = jax.tree_util.tree_map(lambda a: a[inv], state)
        emitted = jax.tree_util.tree_map(lambda a: a[inv], emitted)

    if not scan_only:
        # emissions keep the full padded length, flattened per query
        emitted = (None if emitted is None else jax.tree_util.tree_map(
            lambda e: e.reshape(e.shape[:1] + (-1,) + e.shape[3:]),
            emitted))
        if not (mode == "mesh" and p2 == "mesh"):
            keep = unshard_mask_batch(keep, m)
    res = BatchPruneResult(keep=keep, state=state, emitted=emitted,
                           plan=plan)
    if rec.active:
        _obs_mask_counts(rec, keep, m, encoded=encoded,
                         queries=len(queries))
        res.report = rec.finish()
    return res
