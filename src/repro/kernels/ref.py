"""Pure-jnp oracles for the Pallas pruning kernels.

These implement the kernels' *block semantics* exactly (same math, plain
gathers instead of one-hot masks) so tests can assert allclose/equal.
Block semantics = the paper's §9 multi-entry-per-packet rule: per block,
prune decisions use the pre-block state; at most one state insertion per
row per block (conservative, correctness-preserving).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .common import NEG, hash_mod


# ------------------------------------------------------------- DISTINCT
@partial(jax.jit, static_argnames=("d", "w", "block", "seed", "return_state"))
def distinct_block_ref(values: jnp.ndarray, *, d: int, w: int, block: int,
                       seed: int = 0, return_state: bool = False):
    """FIFO d×w cache with block semantics. Returns keep mask int32[m]
    (plus the final (slots, valid, head) state when return_state)."""
    m = values.shape[0]
    nb = m // block
    vals = values[: nb * block].reshape(nb, block)

    def step(state, x):
        S, valid, head = state
        rows = hash_mod(x, d, seed)
        g = S[rows]                       # [B, w]
        gv = valid[rows]
        hit = jnp.any((g == x[:, None]) & gv, axis=1)
        miss = ~hit
        # first missing entry per row
        iota = jnp.arange(block)
        cand = jnp.where(miss, iota, block)
        per_row_first = jnp.full((d,), block).at[rows].min(cand)
        insert = miss & (per_row_first[rows] == iota)
        ins_rows = jnp.where(insert, rows, d)  # d = dump row (sliced off)
        ins_cols = jnp.where(insert, head[rows], 0)
        Spad = jnp.concatenate([S, jnp.zeros((1, w), S.dtype)], 0)
        Vpad = jnp.concatenate([valid, jnp.zeros((1, w), jnp.bool_)], 0)
        S2 = Spad.at[ins_rows, ins_cols].set(x)[:d]
        valid2 = Vpad.at[ins_rows, ins_cols].set(True)[:d]
        row_inserted = jnp.zeros((d + 1,), jnp.bool_).at[ins_rows].max(insert)[:d]
        head2 = jnp.where(row_inserted, (head + 1) % w, head)
        return (S2, valid2, head2), miss

    init = (jnp.zeros((d, w), jnp.uint32), jnp.zeros((d, w), jnp.bool_),
            jnp.zeros((d,), jnp.int32))
    state, keep = jax.lax.scan(step, init, vals)
    keep = keep.reshape(-1).astype(jnp.int32)
    return (keep, state) if return_state else keep


# ---------------------------------------------------------------- TOP-N
@partial(jax.jit, static_argnames=("d", "w", "block", "seed", "return_state"))
def topn_block_ref(values: jnp.ndarray, *, d: int, w: int, block: int,
                   seed: int = 0, return_state: bool = False):
    """Randomized TOP-N matrix, block semantics. keep mask int32[m]
    (plus the final f32[d, w] matrix when return_state)."""
    m = values.shape[0]
    nb = m // block
    vals = values[: nb * block].reshape(nb, block).astype(jnp.float32)

    def step(S, xb):
        x, gidx = xb
        rows = hash_mod(gidx.astype(jnp.uint32), d, seed)
        row_min = S[:, -1]
        keep = x >= row_min[rows]
        # per-row max candidate from this block
        cand = jnp.full((d,), NEG).at[rows].max(x)
        do = cand > row_min  # also handles NEG empty rows
        pos = jnp.sum(cand[:, None] <= S, axis=1)  # [d] insert positions
        idxw = jnp.arange(w)
        shifted = jnp.where(idxw[None, :] > pos[:, None],
                            jnp.roll(S, 1, axis=1), S)
        inserted = jnp.where(idxw[None, :] == pos[:, None], cand[:, None], shifted)
        S2 = jnp.where(do[:, None], inserted, S)
        return S2, keep

    gidx = jnp.arange(nb * block).reshape(nb, block)
    init = jnp.full((d, w), NEG, jnp.float32)
    state, keep = jax.lax.scan(step, init, (vals, gidx))
    keep = keep.reshape(-1).astype(jnp.int32)
    return (keep, state) if return_state else keep


# ------------------------------------------------------------ Count-Min
@partial(jax.jit, static_argnames=("rows", "width", "seed"))
def cms_build_ref(keys: jnp.ndarray, weights: jnp.ndarray, *, rows: int,
                  width: int, seed: int = 0) -> jnp.ndarray:
    """Exact CMS table f32[rows, width] (block order irrelevant: sums)."""
    t = []
    for r in range(rows):
        idx = hash_mod(keys, width, seed + r * 101)
        t.append(jnp.zeros((width,), jnp.float32).at[idx].add(
            weights.astype(jnp.float32)))
    return jnp.stack(t)


@partial(jax.jit, static_argnames=("seed",))
def cms_query_ref(table: jnp.ndarray, keys: jnp.ndarray, *, seed: int = 0) -> jnp.ndarray:
    rows, width = table.shape
    ests = []
    for r in range(rows):
        idx = hash_mod(keys, width, seed + r * 101)
        ests.append(table[r][idx])
    return jnp.min(jnp.stack(ests), axis=0)


# ---------------------------------------------------------------- Bloom
@partial(jax.jit, static_argnames=("nbits", "num_hashes", "seed"))
def bloom_build_ref(keys: jnp.ndarray, *, nbits: int, num_hashes: int,
                    seed: int = 0) -> jnp.ndarray:
    bits = jnp.zeros((nbits,), jnp.float32)
    for h in range(num_hashes):
        idx = hash_mod(keys, nbits, seed + h * 101)
        bits = bits.at[idx].max(1.0)
    return bits


@partial(jax.jit, static_argnames=("num_hashes", "seed"))
def bloom_query_ref(bits: jnp.ndarray, keys: jnp.ndarray, *, num_hashes: int,
                    seed: int = 0) -> jnp.ndarray:
    ok = jnp.ones(keys.shape[0], jnp.bool_)
    for h in range(num_hashes):
        idx = hash_mod(keys, bits.shape[0], seed + h * 101)
        ok = ok & (bits[idx] > 0.5)
    return ok.astype(jnp.int32)


# -------------------------------------------------------------- SKYLINE
@partial(jax.jit, static_argnames=("w", "block", "score", "return_state"))
def skyline_block_ref(points: jnp.ndarray, *, w: int, block: int,
                      score: str = "aph", return_state: bool = False):
    """w-point store, block semantics: keep vs pre-block state; insert the
    top-w block candidates by score. keep mask int32[m]."""
    from repro.core.skyline import _SCORES

    h = _SCORES[score]
    m, D = points.shape
    nb = m // block
    pts = points[: nb * block].reshape(nb, block, D).astype(jnp.float32)

    def step(state, x):
        P, S = state  # [w, D] points, [w] scores desc (NEG empty)
        hx = h(x)     # [B]
        dom = (jnp.all(x[:, None, :] <= P[None], axis=-1)
               & jnp.any(x[:, None, :] < P[None], axis=-1)
               & (S > NEG)[None, :])
        keep = ~jnp.any(dom, axis=1)
        # insert top-w block candidates by score (iterative, w rounds)
        hxm = hx
        for _ in range(w):
            best = jnp.max(hxm)
            bidx = jnp.argmax(hxm)
            bx = x[bidx]
            do = best > S[-1]
            pos = jnp.sum(best <= S)
            idxw = jnp.arange(w)
            P2 = jnp.where((idxw[:, None] == pos), bx[None, :],
                           jnp.where(idxw[:, None] > pos, jnp.roll(P, 1, 0), P))
            S2 = jnp.where(idxw == pos, best,
                           jnp.where(idxw > pos, jnp.roll(S, 1), S))
            P = jnp.where(do, P2, P)
            S = jnp.where(do, S2, S)
            hxm = hxm.at[bidx].set(NEG)
        return (P, S), keep

    init = (jnp.zeros((w, D), jnp.float32), jnp.full((w,), NEG, jnp.float32))
    state, keep = jax.lax.scan(step, init, pts)
    keep = keep.reshape(-1).astype(jnp.int32)
    return (keep, state) if return_state else keep
