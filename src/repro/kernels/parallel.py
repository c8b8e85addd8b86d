"""Grid-parallel Pallas pruning kernels (the engine's two_pass on TPU).

Each kernel family follows the engine's two-pass structure:

  pass 1  grid ``(S, n/B)``: program ``(i, j)`` folds block ``j`` of shard
          ``i`` into that shard's state replica, with the block
          semantics of ``ref.py`` (decisions against the pre-block
          state, one insertion per state row per block). The shard axis
          is ``"parallel"`` and the block axis ``"arbitrary"``, so the
          state lives in VMEM scratch across a shard's blocks and the
          final replica is written to the output at the shard's last
          block. ``shards=1`` is the sequential switch pipeline:
          ``ops.topn_prune`` / ``distinct_prune`` / ``skyline_prune``.
  merge   plain-XLA fold of the S replicas (per-row top-w union for
          TOP-N, cache-column union + owner ranks for DISTINCT,
          dominance-set concat for SKYLINE): a tiny [d, S·w] op next to
          the stream, run between the two pallas_calls.
  pass 2  an embarrassingly parallel filter applying the merged state
          to every block (grid m/B, ``("parallel",)``).

Layouts are those of ``kernels.common``: entries as (1, B) lane rows,
SKYLINE points as (D, B) (one dimension per sublane), state one hash
row per sublane. Every kernel has a pure-jnp mirror (vmapped block
oracles from ref.py + the engine's own scan-free filter,
``core.engine.apply_merged``) used for differential testing; keep
masks are supersets of the minimal correct survivor set, not of the
sequential scan's mask.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.skyline import _SCORES
from . import ref
from .common import (CHUNK, F32_LOW, I32_LOW, NEG, any_rows, as_u32,
                     compiler_params, gather, hash_mod, lane_iota, onehot,
                     over_rows, padded_rows, row_max, shift_down)

# pass-1 grid: shard axis parallel, block axis carries the state
_PASS1 = compiler_params(("parallel", "arbitrary"))
_PASS2 = compiler_params(("parallel",))


def _stream_spec(block: int, nb: int) -> pl.BlockSpec:
    """(1, block) row of shard i's j-th block on a (S, nb) grid."""
    return pl.BlockSpec((1, block), lambda i, j: (0, i * nb + j))


def _full_spec(shape: tuple) -> pl.BlockSpec:
    """The whole array in every program of a 1-D grid."""
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _check_pass1(m: int, shards: int, block: int) -> int:
    assert m % (shards * block) == 0, "pad to a multiple of shards*block"
    return m // shards // block


# ======================================================= TOP-N (rand, Ex. 7)
def _topn_shard_kernel(d, w, block, nb, seed, x_ref, keep_ref, sout_ref,
                       s_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.full_like(s_ref, NEG)

    x = x_ref[...]
    # rows hash the shard-local stream index
    rows = hash_mod(as_u32(j * block + lane_iota(block)), d, seed)

    def rows_slice(r0, C, my_min):
        oh = onehot(rows, r0, C)
        S = s_ref[pl.ds(r0, C), :]
        row_min = S[:, w - 1:]
        my_min = jnp.maximum(my_min, gather(oh, row_min, F32_LOW))
        # per-row best block candidate → one sorted insert per row
        cand = row_max(oh, x, NEG)
        pos = jnp.sum((cand <= S).astype(jnp.int32), axis=1, keepdims=True)
        wcols = jax.lax.broadcasted_iota(jnp.int32, S.shape, 1)
        ins = jnp.where(wcols == pos, cand,
                        jnp.where(wcols > pos, shift_down(S, 1), S))
        s_ref[pl.ds(r0, C), :] = jnp.where(cand > row_min, ins, S)
        return my_min

    my_min = over_rows(s_ref.shape[0], rows_slice,
                       jnp.full(x.shape, F32_LOW, jnp.float32))
    keep_ref[...] = (x >= my_min).astype(jnp.int32)

    @pl.when(j == nb - 1)
    def _emit():
        sout_ref[0] = s_ref[...]


@partial(jax.jit, static_argnames=("d", "w", "shards", "block", "seed",
                                   "interpret"))
def topn_shard_states_kernel(values: jnp.ndarray, *, d: int, w: int,
                             shards: int, block: int, seed: int,
                             interpret: bool):
    """Pass 1: keep int32[m] + per-shard states f32[shards, d, w]."""
    m = values.shape[0]
    nb = _check_pass1(m, shards, block)
    dp = padded_rows(d)
    keep, states = pl.pallas_call(
        partial(_topn_shard_kernel, d, w, block, nb, seed),
        grid=(shards, nb),
        in_specs=[_stream_spec(block, nb)],
        out_specs=[_stream_spec(block, nb),
                   pl.BlockSpec((1, dp, w), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, m), jnp.int32),
                   jax.ShapeDtypeStruct((shards, dp, w), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dp, w), jnp.float32)],
        compiler_params=_PASS1,
        interpret=interpret,
    )(values.astype(jnp.float32).reshape(1, m))
    return keep.reshape(m), states[:, :d]


def merge_topn_states(states: jnp.ndarray, w: int) -> jnp.ndarray:
    """[S, d, w] shard matrices -> [d, w] per-row top-w of the union."""
    S, d, _ = states.shape
    cols = jnp.moveaxis(states, 0, 1).reshape(d, -1)
    return -jnp.sort(-cols, axis=1)[:, :w]


def _topn_apply_kernel(d, block, bpshard, seed, x_ref, rmin_ref, keep_ref):
    x = x_ref[...]
    c = pl.program_id(0) % bpshard  # block index within the owning shard
    rows = hash_mod(as_u32(c * block + lane_iota(block)), d, seed)
    my_min = over_rows(
        rmin_ref.shape[0],
        lambda r0, C, acc: jnp.maximum(
            acc, gather(onehot(rows, r0, C), rmin_ref[pl.ds(r0, C), :],
                        F32_LOW)),
        jnp.full(x.shape, F32_LOW, jnp.float32))
    keep_ref[...] = (x >= my_min).astype(jnp.int32)


@partial(jax.jit, static_argnames=("d", "shards", "block", "seed",
                                   "interpret"))
def topn_apply_kernel(values: jnp.ndarray, merged: jnp.ndarray, *, d: int,
                      shards: int, block: int, seed: int,
                      interpret: bool) -> jnp.ndarray:
    """Pass 2: keep = value >= merged row minimum. Fully parallel grid."""
    m = values.shape[0]
    dp = padded_rows(d)
    rmin = jnp.full((dp, 1), NEG, jnp.float32).at[:d, 0].set(merged[:, -1])
    return pl.pallas_call(
        partial(_topn_apply_kernel, d, block, m // shards // block, seed),
        grid=(m // block,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (0, i)),
                  _full_spec((dp, 1))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        compiler_params=_PASS2,
        interpret=interpret,
    )(values.astype(jnp.float32).reshape(1, m), rmin).reshape(m)


def topn_parallel_ref(values, *, d, w, shards, block, seed=0):
    """jnp mirror of pass1+merge+pass2 (vmapped block oracle; pass 2 is
    the engine's shared filter body)."""
    from ..core.engine import apply_merged
    from ..core.topn import TopNRandState

    m = values.shape[0]
    sh = values.reshape(shards, m // shards)
    _, states = jax.vmap(lambda v: ref.topn_block_ref(
        v, d=d, w=w, block=block, seed=seed, return_state=True))(sh)
    merged = merge_topn_states(states, w)
    keep = apply_merged("topn_rand", TopNRandState(vals=merged), (sh,),
                        None, d=d, w=w, seed=seed)
    return keep.reshape(-1).astype(jnp.int32), states


# ==================================================== DISTINCT (FIFO, Ex. 2)
def _distinct_shard_kernel(d, w, nb, seed, x_ref, keep_ref, sl_out, vl_out,
                           slot_ref, val_ref, head_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        slot_ref[...] = jnp.zeros_like(slot_ref)
        val_ref[...] = jnp.zeros_like(val_ref)
        head_ref[...] = jnp.zeros_like(head_ref)

    x = x_ref[...]                       # uint32 fingerprints
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    rows = hash_mod(x, d, seed)
    iota = lane_iota(x.shape[1])

    def rows_slice(r0, C, hits):
        oh = onehot(rows, r0, C)
        slots = slot_ref[pl.ds(r0, C), :]
        valid = val_ref[pl.ds(r0, C), :]
        hit_c = jnp.zeros(x.shape, jnp.bool_)
        for k in range(w):
            hit_c = hit_c | ((gather(oh, slots[:, k:k + 1], I32_LOW) == xi)
                             & (gather(oh, valid[:, k:k + 1], 0) > 0))
        # one insertion per row per block: the row's first missing entry
        miss = oh & ~hit_c
        first = jnp.min(jnp.where(miss, iota, x.shape[1]), axis=1,
                        keepdims=True)
        ins = miss & (iota == first)
        row_ins = any_rows(ins, 1)
        v = row_max(ins, xi, I32_LOW)
        head = head_ref[pl.ds(r0, C), :]
        wcols = jax.lax.broadcasted_iota(jnp.int32, slots.shape, 1)
        hmask = (wcols == head) & row_ins
        slot_ref[pl.ds(r0, C), :] = jnp.where(hmask, v, slots)
        val_ref[pl.ds(r0, C), :] = jnp.where(hmask, 1, valid)
        head_ref[pl.ds(r0, C), :] = jnp.where(
            row_ins, jnp.where(head == w - 1, 0, head + 1), head)
        # counted in int32: Mosaic loops carry no bool vectors
        return hits + hit_c.astype(jnp.int32)

    hits = over_rows(slot_ref.shape[0], rows_slice,
                     jnp.zeros(x.shape, jnp.int32))
    keep_ref[...] = (hits == 0).astype(jnp.int32)

    @pl.when(j == nb - 1)
    def _emit():
        sl_out[0] = slot_ref[...]
        vl_out[0] = val_ref[...]


@partial(jax.jit, static_argnames=("d", "w", "shards", "block", "seed",
                                   "interpret"))
def distinct_shard_states_kernel(values: jnp.ndarray, *, d: int, w: int,
                                 shards: int, block: int, seed: int,
                                 interpret: bool):
    """Pass 1: keep int32[m] + per-shard caches: slots uint32[S, d, w]
    and valid bool[S, d, w]."""
    m = values.shape[0]
    nb = _check_pass1(m, shards, block)
    dp = padded_rows(d)
    state_spec = pl.BlockSpec((1, dp, w), lambda i, j: (i, 0, 0))
    state_shape = jax.ShapeDtypeStruct((shards, dp, w), jnp.int32)
    keep, slots, valid = pl.pallas_call(
        partial(_distinct_shard_kernel, d, w, nb, seed),
        grid=(shards, nb),
        in_specs=[_stream_spec(block, nb)],
        out_specs=[_stream_spec(block, nb), state_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((1, m), jnp.int32),
                   state_shape, state_shape],
        scratch_shapes=[pltpu.VMEM((dp, w), jnp.int32),   # slots
                        pltpu.VMEM((dp, w), jnp.int32),   # valid
                        pltpu.VMEM((dp, 1), jnp.int32)],  # FIFO head
        compiler_params=_PASS1,
        interpret=interpret,
    )(values.reshape(1, m))
    return (keep.reshape(m),
            jax.lax.bitcast_convert_type(slots[:, :d], jnp.uint32),
            valid[:, :d] > 0)


def merge_distinct_states(slots, valid):
    """[S, d, w] shard caches -> [d, S*w] union + int32 owner codes.

    Owner code per column: shard_rank + 1 where the slot is valid, else 0
    — lets pass 2 test "cached by a lower-ranked shard" with one compare.
    """
    S, d, w = slots.shape
    cat = lambda a: jnp.moveaxis(a, 0, 1).reshape(d, S * w)
    owner = jnp.repeat(jnp.arange(1, S + 1, dtype=jnp.int32), w)
    return cat(slots), jnp.where(cat(valid), owner[None, :], 0)


def _byte_planes(x: jnp.ndarray) -> list:
    """uint32 → four f32 byte planes: exact MXU operands at any matmul
    precision (every value < 256 is a bf16)."""
    return [jax.lax.bitcast_convert_type((x >> (8 * b)) & np.uint32(0xFF),
                                         jnp.int32).astype(jnp.float32)
            for b in range(4)]


def _distinct_apply_kernel(d, bpshard, seed, x_ref, keep1_ref, p_ref,
                           keep_ref):
    x = x_ref[...]
    shard = pl.program_id(0) // bpshard + 1     # owner code of this shard
    rows = hash_mod(x, d, seed)
    nplanes, K = p_ref.shape[1], p_ref.shape[2]
    C = p_ref.shape[3]

    def rows_slice(c, acc):
        # one-hot matmul gathers each entry's row of the K merged columns
        oh = onehot(rows, c * C, C).astype(jnp.float32)
        return tuple(
            a + jnp.dot(p_ref[c, q], oh, preferred_element_type=jnp.float32)
            for q, a in enumerate(acc))

    zero = jnp.zeros((K, x.shape[1]), jnp.float32)
    g = jax.lax.fori_loop(0, p_ref.shape[0], rows_slice, (zero,) * nplanes)
    same = g[4] > 0.5
    for gb, xb in zip(g[:4], _byte_planes(x)):
        same = same & (gb == xb)
    dup_lower = any_rows(same & (g[4] < shard - 0.5), 0)
    keep_ref[...] = ((keep1_ref[...] > 0) & ~dup_lower).astype(jnp.int32)


@partial(jax.jit, static_argnames=("d", "shards", "block", "seed",
                                   "interpret"))
def distinct_apply_kernel(values, keep1, mslots, owner, *, d: int,
                          shards: int, block: int, seed: int,
                          interpret: bool) -> jnp.ndarray:
    """Pass 2: drop shard-kept entries cached by a lower-ranked shard."""
    m = values.shape[0]
    assert shards < 256, "owner codes must stay exact MXU operands"
    K = -(-mslots.shape[1] // 8) * 8
    dp = padded_rows(d)
    C = min(dp, CHUNK)
    # [nchunks, 5, K, C]: byte planes + owner codes, transposed so the
    # gather is a plain (K, C) @ (C, B) matmul per row slice
    pad = lambda a: jnp.zeros((dp, K), a.dtype).at[:d, :a.shape[1]].set(a)
    planes = jnp.stack(_byte_planes(pad(mslots))
                       + [pad(owner).astype(jnp.float32)])
    planes = planes.reshape(5, dp // C, C, K).transpose(1, 0, 3, 2)
    return pl.pallas_call(
        partial(_distinct_apply_kernel, d, m // shards // block, seed),
        grid=(m // block,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (0, i)),
                  pl.BlockSpec((1, block), lambda i: (0, i)),
                  _full_spec(planes.shape)],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        compiler_params=_PASS2,
        interpret=interpret,
    )(values.reshape(1, m), keep1.reshape(1, m), planes).reshape(m)


def distinct_parallel_ref(values, *, d, w, shards, block, seed=0):
    """jnp mirror: vmapped FIFO block oracle + the engine's cache-union
    apply body (same "cached by a lower-ranked shard" rule as the
    kernel's owner codes)."""
    from ..core.engine import DistinctMerged, _cols_by_shard, apply_merged

    m = values.shape[0]
    sh = values.reshape(shards, m // shards)
    keep1, (slots, valid, _) = jax.vmap(lambda v: ref.distinct_block_ref(
        v, d=d, w=w, block=block, seed=seed, return_state=True))(sh)
    merged = DistinctMerged(
        slots=_cols_by_shard(slots),
        valid=_cols_by_shard(valid.astype(bool)),
        shard=jnp.repeat(jnp.arange(shards, dtype=jnp.int32), w))
    keep = apply_merged("distinct", merged, (sh,),
                        keep1.reshape(shards, -1).astype(bool),
                        d=d, seed=seed)
    return keep.reshape(-1).astype(jnp.int32), (slots, valid)


# ===================================================== SKYLINE (Ex. 6)
def _dominated(x, P, S):
    """(1, B): some valid stored point dominates the entry. x (D, B),
    P (K, D) stored points, S (K, 1) scores (NEG = empty slot)."""
    le = lt = None
    for j in range(x.shape[0]):
        xj, pj = x[j:j + 1, :], P[:, j:j + 1]
        le = (xj <= pj) if le is None else le & (xj <= pj)
        lt = (xj < pj) if lt is None else lt | (xj < pj)
    return any_rows(le & lt & (S > NEG), 0)


def _skyline_shard_kernel(w, score, nb, x_ref, keep_ref, p_out, s_out,
                          p_ref, s_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref)
        s_ref[...] = jnp.full_like(s_ref, NEG)

    x = x_ref[...]                               # (D, B)
    D, B = x.shape
    keep_ref[...] = (~_dominated(x, p_ref[...], s_ref[...])).astype(
        jnp.int32)
    iota = lane_iota(B)
    iw = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)
    icol = jax.lax.broadcasted_iota(jnp.int32, (w, D), 1)

    def stage(_, carry):  # w switch stages: extract block max, insert
        P, S, hx = carry
        best = jnp.max(hx, axis=1, keepdims=True)
        sel = hx == best
        first = jnp.min(jnp.where(sel, iota, B), axis=1, keepdims=True)
        pick = sel & (iota == first)             # ties → lowest index
        pos = jnp.sum((best <= S).astype(jnp.int32), axis=0, keepdims=True)
        do = best > S[w - 1:, :]    # beats the last stored score
        at, after = (iw == pos) & do, (iw > pos) & do
        S2 = jnp.where(at, best, jnp.where(after, shift_down(S, 0), S))
        Pd = shift_down(P, 0)
        P2 = P
        for k in range(D):
            bx = jnp.sum(jnp.where(pick, x[k:k + 1, :], 0.0), axis=1,
                         keepdims=True)
            colk = jnp.where(at, bx, jnp.where(after, Pd[:, k:k + 1],
                                               P[:, k:k + 1]))
            P2 = jnp.where(icol == k, colk, P2)
        return P2, S2, jnp.where(pick, NEG, hx)

    P, S, _ = jax.lax.fori_loop(
        0, w, stage, (p_ref[...], s_ref[...],
                      _SCORES[score](x, axis=0, keepdims=True)))
    p_ref[...] = P
    s_ref[...] = S

    @pl.when(j == nb - 1)
    def _emit():
        p_out[0] = P
        s_out[0] = S


@partial(jax.jit, static_argnames=("w", "shards", "block", "score",
                                   "interpret"))
def skyline_shard_states_kernel(points: jnp.ndarray, *, w: int, shards: int,
                                block: int, score: str, interpret: bool):
    """Pass 1: keep int32[m] + per-shard stores: points f32[S, w, D],
    scores f32[S, w]."""
    m, D = points.shape
    nb = _check_pass1(m, shards, block)
    keep, P, S = pl.pallas_call(
        partial(_skyline_shard_kernel, w, score, nb),
        grid=(shards, nb),
        in_specs=[pl.BlockSpec((D, block), lambda i, j: (0, i * nb + j))],
        out_specs=[_stream_spec(block, nb),
                   pl.BlockSpec((1, w, D), lambda i, j: (i, 0, 0)),
                   pl.BlockSpec((1, w, 1), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, m), jnp.int32),
                   jax.ShapeDtypeStruct((shards, w, D), jnp.float32),
                   jax.ShapeDtypeStruct((shards, w, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((w, D), jnp.float32),
                        pltpu.VMEM((w, 1), jnp.float32)],
        compiler_params=_PASS1,
        interpret=interpret,
    )(points.astype(jnp.float32).T)
    return keep.reshape(m), P, S[..., 0]


def merge_skyline_states(points, scores):
    """[S, w, D]+[S, w] shard stores -> [S*w, D]+[S*w] dominance set."""
    S, w, D = points.shape
    return points.reshape(S * w, D), scores.reshape(S * w)


def _skyline_apply_kernel(x_ref, p_ref, s_ref, keep_ref):
    keep_ref[...] = (~_dominated(x_ref[...], p_ref[...], s_ref[...])
                     ).astype(jnp.int32)


@partial(jax.jit, static_argnames=("block", "interpret"))
def skyline_apply_kernel(points, mpoints, mscores, *, block: int,
                         interpret: bool) -> jnp.ndarray:
    """Pass 2: keep a point iff no merged stored point dominates it."""
    m, D = points.shape
    K = mpoints.shape[0]
    return pl.pallas_call(
        _skyline_apply_kernel,
        grid=(m // block,),
        in_specs=[pl.BlockSpec((D, block), lambda i: (0, i)),
                  _full_spec((K, D)), _full_spec((K, 1))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        compiler_params=_PASS2,
        interpret=interpret,
    )(points.astype(jnp.float32).T, mpoints,
      mscores.reshape(K, 1)).reshape(m)


def skyline_parallel_ref(points, *, w, shards, block, score="aph"):
    """jnp mirror: vmapped block oracle + the engine's dominance-set
    apply body."""
    from ..core.engine import apply_merged
    from ..core.skyline import SkylineState

    m, D = points.shape
    sh = points.reshape(shards, m // shards, D).astype(jnp.float32)
    _, (P, S) = jax.vmap(lambda p: ref.skyline_block_ref(
        p, w=w, block=block, score=score, return_state=True))(sh)
    mp, ms = merge_skyline_states(P, S)
    keep = apply_merged("skyline", SkylineState(points=mp, scores=ms),
                        (sh,), None)
    return keep.reshape(-1).astype(jnp.int32), (P, S)
