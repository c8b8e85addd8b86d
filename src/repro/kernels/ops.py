"""Public jit'd dispatch for the Cheetah pruning kernels.

On TPU the Pallas kernels run compiled; elsewhere they run in interpret
mode so the *kernel bodies* execute (and are validated) on CPU.
``_interpret()`` is the one place that decides: the kernel functions
take ``interpret`` as a required argument. ``use_ref=True`` routes to
the pure-jnp oracles in ref.py (same block semantics), for
differential testing; it is never taken as a fallback.

The sequential entry points (``topn_prune``, ``distinct_prune``,
``skyline_prune``) are the grid-parallel pass-1 kernels with one shard:
one state replica walking the whole stream. The two-pass
``*_prune_parallel`` entry points mirror the engine's two_pass/mesh
structure kernel-side: grid-parallel pass-1 state replicas, a plain-XLA
merge, and a grid-parallel scan-free apply. Their ``use_ref`` mirrors
share the apply bodies with ``core.engine`` (via ``apply_merged``) —
the same per-device filter the engine's mesh-resident pass 2
(``engine_prune(..., pass2="mesh")``) runs on each device's resident
shard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import parallel, ref
from .bloom_filter import bloom_build_kernel, bloom_query_kernel
from .common import NEG, POS
from .cms_sketch import cms_build_kernel, cms_query_kernel
from .rle_scan import rle_topn_det_kernel, rle_topn_det_ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, block: int, fill):
    m = x.shape[0]
    pad = (-m) % block
    if pad == 0:
        return x, m
    padshape = (pad,) + x.shape[1:]
    return jnp.concatenate([x, jnp.full(padshape, fill, x.dtype)]), m


def distinct_prune(values: jnp.ndarray, *, d: int, w: int, block: int = 256,
                   seed: int = 0, use_ref: bool = False) -> jnp.ndarray:
    """bool[m] keep mask (FIFO d×w cache, block semantics)."""
    v, m = _pad_to(values, block, 0)
    if use_ref:
        keep = ref.distinct_block_ref(v, d=d, w=w, block=block, seed=seed)
    else:
        keep = parallel.distinct_shard_states_kernel(
            v, d=d, w=w, shards=1, block=block, seed=seed,
            interpret=_interpret())[0]
    return keep[:m].astype(bool)


def topn_prune(values: jnp.ndarray, *, d: int, w: int, block: int = 256,
               seed: int = 0, use_ref: bool = False) -> jnp.ndarray:
    v, m = _pad_to(values.astype(jnp.float32), block, NEG)
    if use_ref:
        keep = ref.topn_block_ref(v, d=d, w=w, block=block, seed=seed)
    else:
        keep = parallel.topn_shard_states_kernel(
            v, d=d, w=w, shards=1, block=block, seed=seed,
            interpret=_interpret())[0]
    return keep[:m].astype(bool)


def distinct_prune_parallel(values: jnp.ndarray, *, d: int, w: int,
                            shards: int = 8, block: int = 256, seed: int = 0,
                            use_ref: bool = False) -> jnp.ndarray:
    """Grid-parallel two-pass DISTINCT: S state replicas + cache-union merge.

    Same correctness contract as engine_prune(..., mode="two_pass"): the
    keep mask is a superset of the true first occurrences, not of the
    sequential kernel's mask.
    """
    v, m = _pad_to(values, shards * block, 0)
    if use_ref:
        keep, _ = parallel.distinct_parallel_ref(v, d=d, w=w, shards=shards,
                                                 block=block, seed=seed)
    else:
        it = _interpret()
        keep1, slots, valid = parallel.distinct_shard_states_kernel(
            v, d=d, w=w, shards=shards, block=block, seed=seed, interpret=it)
        mslots, owner = parallel.merge_distinct_states(slots, valid)
        keep = parallel.distinct_apply_kernel(
            v, keep1, mslots, owner, d=d, shards=shards, block=block,
            seed=seed, interpret=it)
    return keep[:m].astype(bool)


def topn_prune_parallel(values: jnp.ndarray, *, d: int, w: int,
                        shards: int = 8, block: int = 256, seed: int = 0,
                        use_ref: bool = False) -> jnp.ndarray:
    """Grid-parallel two-pass TOP-N: per-shard matrices + top-w union."""
    v, m = _pad_to(values.astype(jnp.float32), shards * block, NEG)
    if use_ref:
        keep, _ = parallel.topn_parallel_ref(v, d=d, w=w, shards=shards,
                                             block=block, seed=seed)
    else:
        it = _interpret()
        _, states = parallel.topn_shard_states_kernel(
            v, d=d, w=w, shards=shards, block=block, seed=seed, interpret=it)
        merged = parallel.merge_topn_states(states, w)
        keep = parallel.topn_apply_kernel(v, merged, d=d, shards=shards,
                                          block=block, seed=seed,
                                          interpret=it)
    return keep[:m].astype(bool)


def skyline_prune_parallel(points: jnp.ndarray, *, w: int, shards: int = 8,
                           block: int = 256, score: str = "aph",
                           use_ref: bool = False) -> jnp.ndarray:
    """Grid-parallel two-pass SKYLINE: shard stores + dominance-set merge."""
    # NEG pads (not 0.0): a (NEG,..,NEG) point dominates nothing even for
    # non-positive data, while a zero point dominates all-negative points
    p, m = _pad_to(points.astype(jnp.float32), shards * block, NEG)
    if use_ref:
        keep, _ = parallel.skyline_parallel_ref(p, w=w, shards=shards,
                                                block=block, score=score)
    else:
        it = _interpret()
        _, P, S = parallel.skyline_shard_states_kernel(
            p, w=w, shards=shards, block=block, score=score, interpret=it)
        mp, ms = parallel.merge_skyline_states(P, S)
        keep = parallel.skyline_apply_kernel(p, mp, ms, block=block,
                                             interpret=it)
    return keep[:m].astype(bool)


def cms_build(keys: jnp.ndarray, weights: jnp.ndarray, *, rows: int,
              width: int, block: int = 256, seed: int = 0,
              use_ref: bool = False) -> jnp.ndarray:
    k, _ = _pad_to(keys, block, 0)
    wts, _ = _pad_to(weights.astype(jnp.float32), block, 0.0)  # 0-weight pad
    if use_ref:
        return ref.cms_build_ref(k, wts, rows=rows, width=width, seed=seed)
    return cms_build_kernel(k, wts, rows=rows, width=width, block=block,
                            seed=seed, interpret=_interpret())


def cms_query(table: jnp.ndarray, keys: jnp.ndarray, *, block: int = 256,
              seed: int = 0, use_ref: bool = False) -> jnp.ndarray:
    k, m = _pad_to(keys, block, 0)
    if use_ref:
        est = ref.cms_query_ref(table, k, seed=seed)
    else:
        est = cms_query_kernel(table, k, block=block, seed=seed,
                               interpret=_interpret())
    return est[:m]


def bloom_build(keys: jnp.ndarray, *, nbits: int, num_hashes: int = 3,
                block: int = 256, seed: int = 0,
                use_ref: bool = False) -> jnp.ndarray:
    k, m = _pad_to(keys, block, 0)
    if m != k.shape[0]:
        # padding would pollute the filter with key 0; pad by repeating a
        # real key instead (idempotent inserts)
        k = jnp.where(jnp.arange(k.shape[0]) < m, k, keys[0])
    if use_ref:
        return ref.bloom_build_ref(k, nbits=nbits, num_hashes=num_hashes, seed=seed)
    return bloom_build_kernel(k, nbits=nbits, num_hashes=num_hashes,
                              block=block, seed=seed, interpret=_interpret())


def bloom_query(bits: jnp.ndarray, keys: jnp.ndarray, *, num_hashes: int = 3,
                block: int = 256, seed: int = 0,
                use_ref: bool = False) -> jnp.ndarray:
    k, m = _pad_to(keys, block, 0)
    if use_ref:
        ok = ref.bloom_query_ref(bits, k, num_hashes=num_hashes, seed=seed)
    else:
        ok = bloom_query_kernel(bits, k, num_hashes=num_hashes, block=block,
                                seed=seed, interpret=_interpret())
    return ok[:m].astype(bool)


def rle_topn_prune(run_values: jnp.ndarray, run_lengths: jnp.ndarray, *,
                   N: int, w: int = 4, block: int = 256,
                   use_ref: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Run-level deterministic TOP-N over an RLE column — no expansion.

    Returns per-run ``(head, tstar)`` int32[R]: within a run of length L
    the flat keep mask is ``(pos < head) | (pos + 1 >= tstar)``
    (``rle_expand_mask``), bit-identical to ``core.topn.topn_det_prune``
    on the expanded stream. Work is O(R·w) instead of O(m·w).
    """
    rv, R = _pad_to(run_values.astype(jnp.float32), block, POS)
    rl, _ = _pad_to(run_lengths.astype(jnp.int32), block, 0)  # (POS, 0) pads
    if use_ref:
        head, tstar = rle_topn_det_ref(rv, rl, N=N, w=w)
    else:
        head, tstar = rle_topn_det_kernel(rv, rl, N=N, w=w, block=block,
                                          interpret=_interpret())
    return head[:R], tstar[:R]


def rle_distinct_prune(run_values: jnp.ndarray, *, d: int, w: int,
                       policy: str = "lru", seed: int = 0) -> jnp.ndarray:
    """Run-level DISTINCT: bool[R] keep mask over run *heads*.

    Within a run every entry after the first hits the cache, and the hit
    leaves the d×w state unchanged (FIFO skips the insert; the LRU
    move-to-front of the just-inserted head slot is a no-op), so the
    flat sequential scan's state evolution only depends on run heads.
    Feeding run values through ``core.distinct.distinct_prune`` is
    therefore *exact*: the flat mask is the run-head scatter
    ``run_keep[rid] & (pos == 0)`` (``rle_expand_mask`` with
    ``tstar=None``) — O(R) cache probes instead of O(m).
    """
    from ..core.distinct import distinct_prune as seq_distinct
    return seq_distinct(jnp.asarray(run_values, jnp.uint32),
                        d=d, w=w, policy=policy, seed=seed).keep.astype(bool)


def rle_expand_mask(head: jnp.ndarray, tstar: jnp.ndarray | None,
                    run_lengths: jnp.ndarray, total: int) -> jnp.ndarray:
    """Flat bool[total] mask from per-run prefix∪suffix descriptors.

    ``head`` is the per-run keep-prefix length (a bool run mask works:
    True → 1). ``tstar=None`` drops the suffix term (DISTINCT head-only
    scatter). ``total`` must equal ``sum(run_lengths)``.
    """
    rl = jnp.asarray(run_lengths, jnp.int32)
    starts = jnp.cumsum(rl) - rl
    rid = jnp.repeat(jnp.arange(rl.shape[0], dtype=jnp.int32), rl,
                     total_repeat_length=total)
    pos = jnp.arange(total, dtype=jnp.int32) - starts[rid]
    keep = pos < jnp.asarray(head, jnp.int32)[rid]
    if tstar is not None:
        keep = keep | ((pos + 1) >= tstar[rid])
    return keep


def skyline_prune(points: jnp.ndarray, *, w: int, block: int = 256,
                  score: str = "aph", use_ref: bool = False) -> jnp.ndarray:
    p, m = _pad_to(points.astype(jnp.float32), block, NEG)  # see parallel note
    if use_ref:
        keep = ref.skyline_block_ref(p, w=w, block=block, score=score)
    else:
        keep = parallel.skyline_shard_states_kernel(
            p, w=w, shards=1, block=block, score=score,
            interpret=_interpret())[0]
    return keep[:m].astype(bool)
