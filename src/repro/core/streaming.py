"""Streaming pruning engine: donated, mesh-resident switch state.

``core/engine.py`` is one-shot: every arrival pattern must be buffered
into a materialized ``[m]`` stream before any pruning happens. The
paper's deployment is the opposite shape — a continuous packet stream
flowing *through* resident switch state — and so is the serving traffic
the ROADMAP targets. ``PruneStream`` / ``engine_prune_stream`` bring
that shape to the mesh engine:

fold
    Each micro-batch is split into S contiguous chunks (chunk j extends
    lane j's stream) and folded into the per-lane switch states by the
    algorithms' *resumed* scan bodies inside one ``shard_map``. The fold
    is compiled with ``jax.jit(..., donate_argnums=(0,))`` so the
    per-lane state buffers are reused in place — state never
    re-allocates across micro-batches, the streaming analogue of switch
    registers. Dispatch is asynchronous: the hot path never calls
    ``jax.block_until_ready``; emitted masks join a bounded in-flight
    window drained by a ready-poll, and only a full window blocks (on
    the oldest entry).

merge
    Every K micro-batches (``merge_every``; ``"auto"`` uses the
    planner's merge-period model) the per-lane states are cross-merged:
    one fused ``all_gather`` + ``merge_states`` fold inside
    ``shard_map`` — the same resident pass-2 machinery as
    ``engine_prune(..., pass2="mesh")``, amortized over the stream
    instead of paid once at the end.

emit
    Each fold emits a *live* keep mask for its micro-batch from the
    same scan-free ``_SPECS`` apply bodies, judged against the latest
    merged snapshot (lane-local pass-1 masks before the first merge).
    A stale snapshot only loosens the mask — every algorithm's merged
    state is superset-safe at *any* time point (a TOP-N threshold was
    witnessed by >= N entries whenever it was read; a cached DISTINCT
    value was really seen by that lane; a stored SKYLINE point is a
    real stream point) — with one exception: HAVING's running sketch
    *under*-estimates the final count, so pruning on it mid-stream
    could drop an eventually-qualifying key. Its live mask is
    all-True; the pruning happens at close.

close
    One final merge, then every stored micro-batch is re-filtered
    against the final merged state (the per-batch ``_index_offset``
    keeps positional hashes aligned). Because the resumed scans are
    bit-identical continuations and the apply bodies are elementwise,
    ``close().keep`` equals one-shot ``engine_prune(mode="two_pass")``
    on the lane-view concatenation **bit for bit, at every merge
    interval** (``lane_view`` below reconstructs that stream and the
    arrival-order permutation; tests/test_stream_engine.py pins it for
    all six algorithms).

Ragged micro-batches (b not divisible by S) are tail-padded per batch
with the algorithms' neutral pads, exactly like one-shot sharding; the
pads count as lane-stream entries, so equivalence includes them.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import compat
from ..obs import report as obsreport
from . import planner
from .encoding import normalize_encodings
from .engine import (_SPECS, DEFAULT_MESH_APPLY_BLOCK, _apply_chunked,
                     _decode_streams, _encoded_spec, _mesh_for_shards,
                     _mesh_lanes, _padded_encodings, _state_nbytes,
                     calibrate_merge_cost)
from .options import ExecOptions


@dataclasses.dataclass
class StreamResult:
    """What a drained stream hands the master.

    keep:      bool[m] final masks in arrival order — bit-identical to
               one-shot ``engine_prune`` over the lane-view stream.
    live_keep: bool[m] the provisional masks emitted on the hot path
               (superset of ``keep`` for the merge-safe algorithms).
    state:     the final merged global state (``merge_states`` output).
    emitted:   concatenated per-batch emissions (GROUP BY evictions),
               padded lane layout per batch like the one-shot engine.
    stats:     batches/entries/merges/window counters.
    report:    telemetry (``repro.obs.ExecReport``) for the stream's
               whole life — per-merge shipped bytes, snapshot staleness
               and window-occupancy histograms; None when obs="off".
    """

    keep: jnp.ndarray
    live_keep: jnp.ndarray
    state: Any = None
    emitted: Any = None
    stats: dict = dataclasses.field(default_factory=dict)
    report: Any = None


class PruneStream:
    """S resident switch lanes folding micro-batches as they arrive.

    Usage::

        stream = PruneStream("topn_det", shards=8, N=100, w=8)
        for batch in arrivals:
            stream.fold(batch)        # async; returns the batch index
        res = stream.close()          # final merge + exact refresh

    merge_every: cross-lane merge period K in micro-batches; 1 merges
    after every fold (tightest live masks), ``"auto"`` resolves K from
    the measured merge cost via ``planner.optimal_merge_interval``.
    window: max in-flight (not-yet-ready) live masks before the fold
    blocks on the oldest. donate=False keeps a fresh state allocation
    per fold (benchmark baseline — never faster).
    """

    def __init__(self, algo: str, *, options: ExecOptions | None = None,
                 shards: int | None = None, mesh=None,
                 mesh_axis: str = "shards", merge_every: int | str = "auto",
                 window: int = 4, donate: bool = True,
                 apply_block: int | None = None, retain: bool = True,
                 encoding=None, obs: str | None = None, **params):
        opts = ExecOptions.resolve(options, shards=shards,
                                   apply_block=apply_block, obs=obs)
        opts.require_unset("PruneStream", "mode", "pass2", "tune",
                           "plan_cache")
        shards = opts.shards
        apply_block = opts.apply_block
        self.algo = algo
        self._spec = _SPECS[algo]  # KeyError = unknown algorithm
        self._encoding = encoding
        self._decode = opts.decode if opts.decode is not None else "auto"
        self._enc_wrapped = encoding is None
        if self._spec.resume is None or self._spec.init is None:
            raise ValueError(f"{algo!r} has no streaming fold")
        if shards is not None and not isinstance(shards, int):
            raise ValueError(
                f"PruneStream needs a concrete lane count, got "
                f"shards={shards!r}")
        if shards is None:
            shards = (mesh.shape[mesh_axis] if mesh is not None
                      else len(jax.devices()))
        if mesh is None:
            mesh = _mesh_for_shards(shards, mesh_axis)
        self.shards = int(shards)
        self.mesh = compat.auto_mesh(mesh)
        self.axis = mesh_axis
        self._lanes = _mesh_lanes(self.shards, mesh.shape[mesh_axis])
        self._sharding = NamedSharding(self.mesh, P(mesh_axis))
        self._replicated = NamedSharding(self.mesh, P())
        self.params = dict(params)
        if apply_block is None and self._spec.chunkable:
            apply_block = DEFAULT_MESH_APPLY_BLOCK
        self._apply_block = apply_block
        self.merge_every = merge_every
        self.window = int(window)
        self.donate = bool(donate)
        # retain=False drops each micro-batch's entries after the fold —
        # for unbounded streams (a serving queue) where only the live
        # masks and the resident state matter. close() then skips the
        # exact refresh and returns the live masks as `keep`.
        self.retain = bool(retain)
        # --- mutable stream state
        self._state = None          # [S, ...] per-lane states (donated)
        self._merged = None         # latest cross-lane merged snapshot
        self._offset = 0            # per-lane positions consumed so far
        self._batches: list[dict] = []
        self._pending: collections.deque = collections.deque()
        self._merge_k: int | None = None
        self._closed = False
        self._result: StreamResult | None = None
        # --- compiled executables (keyed by chunk shape)
        self._fold_fns: dict = {}
        self._apply_fns: dict = {}
        self._merge_fn = None
        self.stats = dict(batches=0, entries=0, merges=0,
                          window_blocks=0)
        # gloo TCP pairs match sends/recvs FIFO per slot, and two
        # in-flight dispatches of the same collective executable (the
        # merge all_gather, close()'s mask replications) can pair ops
        # across program instances — silently corrupting data or
        # deadlocking. With >1 process, block on each collective-bearing
        # dispatch before issuing the next; single-process keeps the
        # fully async pipeline (shard_map "collectives" never leave the
        # process there).
        self._multiprocess = jax.process_count() > 1
        # one recorder for the stream's whole life (close() reports it);
        # reset() starts a fresh one
        self._obs_level = opts.obs
        self._rec = obsreport.recorder("prune_stream", opts.obs)
        self._last_merge_t = -1

    # ------------------------------------------------------------ plumbing
    def _put(self, arr: np.ndarray, sharding=None):
        sharding = sharding or self._sharding
        if jax.process_count() > 1:
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])
        return jax.device_put(arr, sharding)

    def _rep_scalar(self, v: int):
        return self._put(np.asarray(v, np.uint32), self._replicated)

    def _serialize(self, x):
        """Fence a collective-bearing dispatch in multiprocess runs
        (see ``_multiprocess`` above); identity single-process."""
        if self._multiprocess:
            jax.block_until_ready(x)
        return x

    def _name(self, worker, stage: str) -> None:
        """Name a program body ``<algo>_stream_<stage>``: the profiler
        then reports it as ``jit_<algo>_stream_<stage>``."""
        worker.__name__ = worker.__qualname__ = f"{self.algo}_stream_{stage}"

    def _init_state(self, example_chunks: tuple):
        lane_streams = tuple(jnp.asarray(c[0, :1]) for c in example_chunks)
        lane = self._spec.init(lane_streams, self.params)
        return jax.tree_util.tree_map(
            lambda l: self._put(np.broadcast_to(
                np.asarray(l), (self.shards,) + np.shape(l)).copy()),
            lane)

    def _get_fold(self, nb: int, nstreams: int):
        key = (nb, nstreams)
        fn = self._fold_fns.get(key)
        if fn is not None:
            return fn
        spec, axis, params = self._spec, self.axis, self.params

        def lane_fold(st, off, *local):
            p = dict(params, _index_offset=off)
            return jax.vmap(lambda s, *sh: spec.resume(s, sh, p))(st, *local)

        # the output structure (does this algorithm emit?) must be known
        # before tracing the shard_map body — probe it shape-only
        lane_state = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((self._lanes,) + a.shape[1:],
                                           a.dtype), self._state)

        def worker(st, off, *local):
            r = lane_fold(st, off, *local)
            if r.emitted is None:
                return r.state, r.keep
            return r.state, r.keep, r.emitted

        self._name(worker, "fold")

        local = tuple(
            jax.ShapeDtypeStruct((self._lanes, nb) + shape[2:], dtype)
            for shape, dtype in self._last_chunk_shapes)
        r_shape = jax.eval_shape(lane_fold, lane_state,
                                 jax.ShapeDtypeStruct((), np.uint32),
                                 *local)
        has_emitted = r_shape.emitted is not None
        out_specs = ((P(axis), P(axis), P(axis)) if has_emitted
                     else (P(axis), P(axis)))
        sm = compat.shard_map(
            worker, self.mesh,
            (P(axis), P()) + (P(axis),) * nstreams, out_specs)
        fn = jax.jit(sm, donate_argnums=(0,) if self.donate else ())
        self._fold_fns[key] = fn
        return fn

    def _get_apply(self, nb: int, nstreams: int):
        key = (nb, nstreams)
        fn = self._apply_fns.get(key)
        if fn is not None:
            return fn
        spec, axis, params = self._spec, self.axis, self.params
        lanes, block = self._lanes, self._apply_block

        def worker(merged, keep1, off, *local):
            lane0 = jax.lax.axis_index(axis) * lanes
            p2 = dict(params, _index_offset=off,
                      _lane_ids=lane0 + jnp.arange(lanes, dtype=jnp.int32))
            if block and spec.chunkable and block < local[0].shape[1]:
                return _apply_chunked(spec.apply, spec.pads, merged, local,
                                      keep1, p2, block)
            return spec.apply(merged, local, keep1, p2)

        self._name(worker, "apply")
        fn = jax.jit(compat.shard_map(
            worker, self.mesh,
            (P(), P(axis), P()) + (P(axis),) * nstreams, P(axis)))
        self._apply_fns[key] = fn
        return fn

    def _get_merge(self):
        if self._merge_fn is not None:
            return self._merge_fn
        spec, axis, params = self._spec, self.axis, self.params

        def worker(st):
            gathered = jax.tree_util.tree_map(
                lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True),
                st)
            return spec.merge(gathered, params)

        self._name(worker, "merge")
        self._merge_fn = jax.jit(
            compat.shard_map(worker, self.mesh, P(axis), P()))
        return self._merge_fn

    def _resolve_merge_k(self, batch_entries: int, np_streams) -> int:
        if self._merge_k is None:
            if isinstance(self.merge_every, int):
                self._merge_k = max(1, self.merge_every)
            elif self.merge_every == "auto":
                probes = tuple(jnp.asarray(s[:1]) for s in np_streams)
                c, state_bytes = calibrate_merge_cost(
                    self.algo, probes, self.params)
                self._merge_k = planner.optimal_merge_interval(
                    batch_entries,
                    merge_cost_entries=c * self.shards * state_bytes)
            else:
                raise ValueError(
                    f"merge_every must be an int or 'auto', "
                    f"got {self.merge_every!r}")
        return self._merge_k

    # ------------------------------------------------------------- hot path
    def fold(self, *streams) -> int:
        """Fold one micro-batch into the lane states. Returns its index.

        Async: the call dispatches fold (+ merge when due) and the live
        mask, then returns without blocking unless the in-flight window
        is full. The live mask lands in ``live_masks()[idx]``.
        """
        if self._closed:
            raise RuntimeError("stream is closed")
        rec = self._rec
        streams = tuple(s for s in streams if s is not None)
        if not self._enc_wrapped and self._decode == "eager":
            streams = _decode_streams(
                streams, normalize_encodings(self._encoding, len(streams)))
        with rec.span("prepare"):
            np_streams = [np.asarray(s) for s in streams]
            b = int(np_streams[0].shape[0])
            if b == 0:
                raise ValueError("empty micro-batch")
            S = self.shards
            nb = -(-b // S)
            if self._spec.pad_validity and len(np_streams) < 3:
                # always appended (not just on ragged batches) so every
                # micro-batch runs the same 3-stream executable and the
                # lane-view stream matches a one-shot call with the column
                np_streams.append(np.ones(b, np.bool_))
            if not self._enc_wrapped and self._decode != "eager":
                # wrap once, at the final stream count (validity
                # included): every later fold/merge/apply body decodes in
                # place and the per-batch ragged pads become code-space
                # fills
                encs = normalize_encodings(self._encoding, len(np_streams))
                encs = _padded_encodings(
                    self.algo, self._spec, encs,
                    tuple(jnp.asarray(s[:1]) for s in np_streams),
                    self.params)
                self._spec = _encoded_spec(self.algo, self._spec, encs)
                self._enc_wrapped = True
            pad = S * nb - b
            if pad:
                fills = self._spec.pads(tuple(np_streams), self.params)
                np_streams = [
                    np.concatenate([s, np.broadcast_to(
                        np.asarray(f).astype(s.dtype, copy=False),
                        (pad,) + s.shape[1:])])
                    for s, f in zip(np_streams, fills)]
            chunks_np = tuple(s.reshape((S, nb) + s.shape[1:])
                              for s in np_streams)
        self._last_chunk_shapes = tuple(
            (c.shape, c.dtype) for c in chunks_np)
        off = self._offset
        with rec.span("to_device"):
            chunks = tuple(self._put(c) for c in chunks_np)
            off_arr = self._rep_scalar(off)
        if self._state is None:
            self._state = self._init_state(chunks_np)
        K = self._resolve_merge_k(S * nb, np_streams)

        fold_fn = self._get_fold(nb, len(chunks))
        t = len(self._batches)
        # spans on the hot path time the *dispatch* only — syncing here
        # would serialize the async pipeline the stream exists for
        with rec.span("fold_dispatch", batch=t, entries=b):
            out = fold_fn(self._state, off_arr, *chunks)
        self._state = out[0]
        keep1 = out[1]
        emitted = out[2] if len(out) > 2 else None

        if (t + 1) % K == 0:
            # fused all_gather + merge fold; dispatched before the next
            # fold donates the state buffers it reads
            with rec.span("merge_dispatch", batch=t):
                self._merged = self._serialize(
                    self._get_merge()(self._state))
            self._count_merge(t)
        with rec.span("apply_dispatch"):
            keep_live = self._live_mask(chunks, keep1, off_arr, nb,
                                        len(chunks))
        if rec.active:
            rec.count("entries_scanned", b)
            staleness = (t - self._last_merge_t if self._last_merge_t >= 0
                         else t + 1)
            rec.observe("snapshot_staleness_batches", staleness)

        self._batches.append(dict(
            chunks=chunks if self.retain else None,
            keep1=keep1 if self.retain else None,
            keep_live=keep_live, emitted=emitted,
            b=b, nb=nb, offset=off))
        self._offset += nb
        self.stats["batches"] += 1
        self.stats["entries"] += b
        self._enqueue(keep_live)
        if rec.active:
            rec.observe("window_occupancy", len(self._pending))
        return t

    def _live_mask(self, chunks, keep1, off_arr, nb, nstreams):
        if self._spec.sharded_needs_merge:
            # HAVING: the running sketch underestimates the final count —
            # pruning on it could drop an eventually-qualifying key
            return jnp.ones_like(keep1)
        if self._merged is None:
            return keep1
        return self._get_apply(nb, nstreams)(
            self._merged, keep1, off_arr, *chunks)

    def _enqueue(self, arr):
        self._pending.append(arr)
        self._drain()
        while len(self._pending) > self.window:
            self.stats["window_blocks"] += 1
            with self._rec.span("window_block"):
                jax.block_until_ready(self._pending.popleft())
            self._drain()

    def _drain(self):
        while self._pending:
            arr = self._pending[0]
            if hasattr(arr, "is_ready") and not arr.is_ready():
                break
            self._pending.popleft()

    def _count_merge(self, t: int | None = None) -> None:
        """Book one cross-lane merge: stats + telemetry.

        The fused all_gather lands every lane's state on every device:
        S x per-lane bytes x D wire traffic (``_state_nbytes`` of the
        stacked per-lane states is exactly S x per-lane).
        """
        self.stats["merges"] += 1
        if t is not None:
            self._last_merge_t = t
        rec = self._rec
        if rec.active and self._state is not None:
            rec.count("merge_collective_count", 1)
            rec.count("state_bytes_shipped",
                      _state_nbytes(self._state)
                      * self.mesh.shape[self.axis])

    # ------------------------------------------------------------- queries
    def merge(self):
        """Force a cross-lane merge now; returns the merged state."""
        if self._state is None:
            raise RuntimeError("nothing folded yet")
        with self._rec.span("merge_dispatch", forced=True):
            self._merged = self._serialize(
                self._get_merge()(self._state))
        self._count_merge(len(self._batches) - 1)
        return self._merged

    def live_masks(self) -> list:
        """Per-batch live keep masks in arrival order, flattened."""
        return [b["keep_live"].reshape(-1)[:b["b"]] for b in self._batches]

    def live_mask(self, idx: int) -> jnp.ndarray:
        """One batch's live keep mask (arrival order, real entries)."""
        rec = self._batches[idx]
        return rec["keep_live"].reshape(-1)[: rec["b"]]

    @property
    def in_flight(self) -> int:
        self._drain()
        return len(self._pending)

    def reset(self):
        """Drop stream state; keeps the compiled executables warm."""
        self._state = None
        self._merged = None
        self._offset = 0
        self._batches = []
        self._pending.clear()
        self._closed = False
        self._result = None
        self._last_merge_t = -1
        self._rec = obsreport.recorder("prune_stream", self._obs_level)

    # --------------------------------------------------------------- close
    def close(self) -> StreamResult:
        """Final merge + exact refresh of every stored micro-batch.

        The refresh re-applies the scan-free filter with the *final*
        merged state and each batch's positional offset, which is why
        the result is bit-identical to one-shot ``engine_prune`` on the
        lane-view stream at any merge interval.
        """
        if self._result is not None:
            return self._result
        self._closed = True
        orec = self._rec
        if not self._batches:
            empty = jnp.zeros(0, jnp.bool_)
            self._result = StreamResult(
                keep=empty, live_keep=empty, stats=dict(self.stats),
                report=orec.finish() if orec.active else None)
            return self._result
        merged = self.merge()
        rep = lambda x: self._serialize(jax.jit(
            jnp.asarray, out_shardings=self._replicated)(x))
        keeps, lives = [], []
        with orec.span("close_refresh", batches=len(self._batches)):
            for rec in self._batches:
                live = rep(rec["keep_live"]).reshape(-1)[: rec["b"]]
                if self.retain:
                    fn = self._get_apply(rec["nb"], len(rec["chunks"]))
                    keep = fn(merged, rec["keep1"],
                              self._rep_scalar(rec["offset"]),
                              *rec["chunks"])
                    keeps.append(rep(keep).reshape(-1)[: rec["b"]])
                else:
                    keeps.append(live)
                lives.append(live)
            orec.sync(keeps)
        emitted = None
        if self._batches[0]["emitted"] is not None:
            # emissions keep the full padded lane layout per batch, like
            # the one-shot engine (a pad can evict a REAL partial)
            emitted = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(
                    [rep(x).reshape((-1,) + x.shape[2:]) for x in xs]),
                *[rec["emitted"] for rec in self._batches])
        keep_cat = jnp.concatenate(keeps)
        report = None
        if orec.active:
            orec.annotate(algo=self.algo, shards=self.shards,
                          merge_every=self._merge_k,
                          batches=self.stats["batches"],
                          window_blocks=self.stats["window_blocks"])
            # the concatenated masks are replicated (rep() above), so
            # this host read is safe even across processes
            orec.count("entries_kept", int(np.asarray(keep_cat).sum()))
            report = orec.finish()
        self._result = StreamResult(
            keep=keep_cat,
            live_keep=jnp.concatenate(lives),
            state=merged, emitted=emitted, stats=dict(self.stats),
            report=report)
        return self._result


def engine_prune_stream(algo: str, *streams, micro_batch: int = 4096,
                        options: ExecOptions | None = None,
                        shards: int | None = None, mesh=None,
                        mesh_axis: str = "shards",
                        merge_every: int | str = "auto", window: int = 4,
                        donate: bool = True, apply_block: int | None = None,
                        encoding=None, obs: str | None = None,
                        **params) -> StreamResult:
    """One-shot convenience driver: chop ``streams`` into micro-batches
    and run them through a ``PruneStream``. The returned ``keep`` is in
    arrival order over the original m entries."""
    stream = PruneStream(algo, options=options, shards=shards, mesh=mesh,
                         mesh_axis=mesh_axis, merge_every=merge_every,
                         window=window, donate=donate,
                         apply_block=apply_block, encoding=encoding,
                         obs=obs, **params)
    np_streams = [np.asarray(s) for s in streams if s is not None]
    m = np_streams[0].shape[0]
    for lo in range(0, m, micro_batch):
        stream.fold(*(s[lo:lo + micro_batch] for s in np_streams))
    return stream.close()


def lane_view(algo: str, streams, batch_sizes, shards: int, **params):
    """Host-side reconstruction of the lane-major stream a PruneStream
    folds, for equivalence checks against the one-shot engine.

    Returns ``(lane_streams, valid, arrival)``: the concatenated
    per-lane streams (length S·L, mid-stream pad entries included, the
    GROUP BY validity column appended), a bool mask of real entries, and
    each lane-view entry's original arrival index (-1 for pads). With
    ``one = engine_prune(algo, *lane_streams, mode="two_pass",
    shards=S)``::

        one.keep[valid] == close().keep[arrival[valid]]
    """
    spec = _SPECS[algo]
    np_streams = [np.asarray(s) for s in streams if s is not None]
    m = np_streams[0].shape[0]
    sizes = list(batch_sizes)
    if sum(sizes) != m:
        raise ValueError(f"batch_sizes sum {sum(sizes)} != stream length {m}")
    n_cols = len(np_streams) + (1 if spec.pad_validity
                                and len(np_streams) < 3 else 0)
    per_lane = [[[] for _ in range(shards)] for _ in range(n_cols)]
    idx_lane: list[list] = [[] for _ in range(shards)]
    lo = 0
    for b in sizes:
        batch = [s[lo:lo + b] for s in np_streams]
        if spec.pad_validity and len(batch) < 3:
            batch.append(np.ones(b, np.bool_))
        nb = -(-b // shards)
        pad = shards * nb - b
        if pad:
            fills = spec.pads(tuple(batch), params)
            batch = [np.concatenate([s, np.broadcast_to(
                np.asarray(f).astype(s.dtype, copy=False),
                (pad,) + s.shape[1:])]) for s, f in zip(batch, fills)]
        arrival = np.concatenate([np.arange(lo, lo + b, dtype=np.int64),
                                  np.full(pad, -1, np.int64)])
        for j in range(shards):
            for si, s in enumerate(batch):
                per_lane[si][j].append(s[j * nb:(j + 1) * nb])
            idx_lane[j].append(arrival[j * nb:(j + 1) * nb])
        lo += b
    lane_streams = tuple(
        jnp.asarray(np.concatenate([np.concatenate(per_lane[si][j])
                                    for j in range(shards)]))
        for si in range(len(per_lane)))
    arrival = np.concatenate([np.concatenate(idx_lane[j])
                              for j in range(shards)])
    return lane_streams, arrival >= 0, arrival
