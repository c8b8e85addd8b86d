"""TOP-N pruning (paper §4.3 Ex. 3 deterministic, §5 Ex. 7 randomized).

Deterministic: an exponential threshold ladder t_i = 2^i * t0 where t0 is
the min of the first N entries; once >= N entries above t_i are seen, the
prune threshold advances to t_i. Never prunes a true top-N entry.

Randomized: a d×w matrix; each entry is hashed to a row keeping a rolling
top-w; an entry smaller than all w cached in its row is pruned. Succeeds
(no top-N entry pruned) w.p. >= 1-δ with w per Theorem 2; expected
forwarded count bounded by Theorem 3.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..constants import NEG, POS
from .hashing import hash_mod
from .pruning import PruneResult


# ---------------------------------------------------------------- randomized
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TopNRandState:
    vals: jnp.ndarray  # f32[d, w] per-row descending rolling top-w


def topn_rand_init(d: int, w: int) -> TopNRandState:
    return TopNRandState(vals=jnp.full((d, w), NEG, jnp.float32))


@partial(jax.jit, static_argnames=("d", "w", "seed"))
def topn_rand_prune(values: jnp.ndarray, *, d: int, w: int, seed: int = 0,
                    state: TopNRandState | None = None,
                    index_offset=0) -> PruneResult:
    """Randomized TOP-N matrix (Fig. 2). values: f32[m] (larger = better).

    state/index_offset: resume a prior scan. The row assignment hashes the
    *stream index*, so a resumed call must know how many entries the
    carried state has already consumed — pass the running count as
    ``index_offset`` (traced, so varying offsets reuse one executable).
    """
    m = values.shape[0]
    # the paper assigns each entry a uniformly random row; we hash the
    # stream index (not the value) so duplicates spread across rows.
    rows = hash_mod(jnp.arange(m, dtype=jnp.uint32)
                    + jnp.asarray(index_offset, jnp.uint32), d, seed=seed)

    def body(vals, xr):
        x, r = xr
        row = vals[r]  # descending
        # paper: prune iff strictly smaller than all w cached → keep on >=
        keep = x >= row[-1]
        # rolling insert keeping descending order (switch: w compare stages)
        pos = jnp.sum(x <= row)  # insert position among w (0 = new max)
        idx = jnp.arange(w)
        shifted = jnp.where(idx > pos, jnp.roll(row, 1), row)
        new_row = jnp.where(idx == pos, x, shifted)
        new_row = jnp.where(keep, new_row, row)
        return vals.at[r].set(new_row), keep

    init = (topn_rand_init(d, w) if state is None else state).vals
    vals, keep = jax.lax.scan(body, init, (values.astype(jnp.float32), rows))
    return PruneResult(keep=keep, state=TopNRandState(vals))


def thm2_w(d: int, N: int, delta: float) -> int:
    """Theorem 2: matrix columns for success probability 1-δ given d rows."""
    num = 1.3 * math.log(d / delta)
    den = math.log((d / (N * math.e)) * math.log(d / delta))
    if den <= 0:
        raise ValueError("d too small: need d > N*e/ln(d/δ) (Thm 2 precondition)")
    return math.ceil(num / den)


def thm2_opt_d(N: int, delta: float) -> int:
    """Space-optimal d = δ·e^{W(N·e²/δ)} (§5 'Optimizing the Space')."""
    # Lambert W via Newton iterations on we^w = z
    z = N * math.e**2 / delta
    wv = math.log(z) - math.log(max(math.log(z), 1e-9))
    for _ in range(50):
        ew = math.exp(wv)
        wv -= (wv * ew - z) / (ew * (wv + 1))
    return max(1, round(delta * math.exp(wv)))


def thm3_forwarded_bound(m: int, d: int, w: int) -> float:
    """Theorem 3: expected forwarded count <= w*d*ln(m*e/(w*d))."""
    return w * d * math.log(m * math.e / (w * d))


# -------------------------------------------------------------- deterministic
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TopNDetState:
    t0: jnp.ndarray        # f32 — min of first N entries
    counts: jnp.ndarray    # int32[w] — #entries >= t_i seen so far
    seen: jnp.ndarray      # int32 — #entries processed
    cur_level: jnp.ndarray # int32 — highest i with counts[i] >= N (-1: none)


def topn_det_init(w: int = 4) -> TopNDetState:
    return TopNDetState(
        t0=jnp.float32(POS), counts=jnp.zeros(w, jnp.int32),
        seen=jnp.int32(0), cur_level=jnp.int32(-1),
    )


@partial(jax.jit, static_argnames=("N", "w"))
def topn_det_prune(values: jnp.ndarray, *, N: int, w: int = 4,
                   state: TopNDetState | None = None) -> PruneResult:
    """Deterministic threshold-ladder TOP-N (Ex. 3). values must be > 0.

    Thresholds t_i = 2^i * t0. The switch prunes v < t_{cur}; during the
    first N entries nothing is pruned. Guarantees a superset of the true
    top-N survives. ``state`` resumes a prior scan (the warmup counter
    rides in the state, so resumed micro-batches never re-warm).
    """
    v = values.astype(jnp.float32)

    def body(s, x):
        warm = s.seen < N
        # while warming: update running min over a growing window of size N
        t0 = jnp.where(warm, jnp.minimum(s.t0, x), s.t0)
        levels = t0 * (2.0 ** jnp.arange(w, dtype=jnp.float32))
        counts = s.counts + (x >= levels).astype(jnp.int32)
        # highest level with >= N entries observed at-or-above it
        qual = counts >= N
        cur = jnp.max(jnp.where(qual, jnp.arange(w), -1))
        thr = jnp.where(cur >= 0, t0 * (2.0 ** cur.astype(jnp.float32)), NEG)
        keep = warm | (x >= thr)
        return TopNDetState(t0=t0, counts=counts, seen=s.seen + 1, cur_level=cur), keep

    init = topn_det_init(w) if state is None else state
    state, keep = jax.lax.scan(body, init, v)
    return PruneResult(keep=keep, state=state)


def opt_keep_topn(values, N: int) -> jnp.ndarray:
    """OPT forwards an entry iff it is among the top-N of the prefix so far."""
    import heapq

    v = np.asarray(values, dtype=np.float64)
    out = np.zeros(v.shape[0], bool)
    heap: list = []
    for i, x in enumerate(v.tolist()):
        if len(heap) < N:
            heapq.heappush(heap, x)
            out[i] = True
        elif x > heap[0]:
            heapq.heapreplace(heap, x)
            out[i] = True
    return jnp.asarray(out)


def master_complete_topn(values: jnp.ndarray, keep: jnp.ndarray, N: int):
    """Exact top-N among forwarded entries, on the master (the host).

    The answer ``lax.top_k`` gives over the masked stream: values
    descending, ties to the lower index, and NEG in the slots past the
    forwarded count (indexed by the lowest dropped entries). Only the
    survivors and at most N dropped entries are sorted: a ``top_k`` over
    the whole stream costs the TPU compiler over a minute per N at
    TPC-H SF1.
    """
    v = np.asarray(values).astype(np.float32).reshape(-1)
    kept = np.asarray(keep).reshape(-1).astype(bool)
    cand = np.concatenate([np.flatnonzero(kept), np.flatnonzero(~kept)[:N]])
    cv = np.where(kept[cand], v[cand], NEG)
    order = np.lexsort((cand, -cv))[:N]
    return jnp.asarray(cv[order]), jnp.asarray(cand[order].astype(np.int32))
