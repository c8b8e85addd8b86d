"""SKYLINE pruning (paper §4.4 Ex. 6): w stored points + monotone projection.

The switch stores w points, each with a scalar score h(x) where h is
monotone increasing in every dimension (so y dominates x ⇒ h(y) >= h(x)).
On arrival of x the pipeline does a rolling-minimum insertion by score
(each stage: replace-if-greater, displaced point rolls on) which keeps the
stages sorted descending by h. A stage whose point dominates x marks the
packet for pruning; the drop happens at the end of the pipeline.

Because insertion preserves descending score order and any dominator of x
has h >= h(x), all potential dominators sit at stages *before* x's
insertion point — so the per-stage pipeline is exactly equivalent to the
vectorized form used here: compare x against the stored points with score
>= h(x), then sorted-insert. (Deviation from the paper, documented in
DESIGN.md: we forward a packet iff its ORIGINAL point is undominated,
rather than forwarding displaced points and draining the switch at
end-of-stream. The master receives a superset of the paper's forwarded
set — at most w extra packets — and supersets never change skyline
output, so correctness and pruning-rate plots are unaffected at stream
scale.)

Projections: SUM h_S(x)=Σx_j (biased by ranges) and APH — approximate
product via sum of piecewise-linear log2 approximations (the switch uses
TCAM lookups; the frexp identity log2(v) ≈ e + (v/2^e - 1) is exactly a
first-order lookup-table approximation). Dominance is checked with strict
inequality in at least one dim so exact duplicates are never pruned.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..constants import NEG
from .pruning import PruneResult


def _dim_sum(v: jnp.ndarray, axis: int, keepdims: bool) -> jnp.ndarray:
    """Σ over the point's dimensions as a left fold: one summation order
    on every backend and in the Pallas kernels, so scores (and hence
    which points a switch stage stores) agree bit for bit."""
    parts = [jax.lax.index_in_dim(v, j, axis, keepdims)
             for j in range(v.shape[axis])]
    out = parts[0]
    for p in parts[1:]:
        out = out + p
    return out


def score_sum(x: jnp.ndarray, axis: int = -1,
              keepdims: bool = False) -> jnp.ndarray:
    return _dim_sum(x.astype(jnp.float32), axis, keepdims)


def score_aph(x: jnp.ndarray, axis: int = -1,
              keepdims: bool = False) -> jnp.ndarray:
    """Approximate Product Heuristic: Σ log2~(x_j), where log2~ is the
    piecewise-linear log2 read off the float's bits: exponent plus
    mantissa fraction (the TCAM priority-encode). Exact on every
    backend, unlike a transcendental log2."""
    v = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(jnp.maximum(v, 1.0), jnp.int32)
    e = ((bits >> 23) - 127).astype(jnp.float32)
    frac = jax.lax.bitcast_convert_type(
        (bits & 0x7FFFFF) | 0x3F800000, jnp.float32) - 1.0
    lg = jnp.where(v >= 1.0, e + frac, -16.0)
    return _dim_sum(lg, axis, keepdims)


_SCORES = {"sum": score_sum, "aph": score_aph}


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SkylineState:
    points: jnp.ndarray  # f32[w, D] sorted descending by score
    scores: jnp.ndarray  # f32[w]    (NEG = empty slot)


def skyline_init(w: int, D: int) -> SkylineState:
    return SkylineState(points=jnp.zeros((w, D), jnp.float32),
                        scores=jnp.full((w,), NEG, jnp.float32))


@partial(jax.jit, static_argnames=("w", "score"))
def skyline_prune(points: jnp.ndarray, *, w: int, score: str = "aph",
                  state: SkylineState | None = None) -> PruneResult:
    """Stream points (f32/int[m, D], maximizing all dims) through w stages.

    ``state`` resumes a prior scan: micro-batched folds with the carried
    state match one scan over the concatenation bit for bit.
    """
    h = _SCORES[score]
    D = points.shape[-1]
    idx = jnp.arange(w)

    def body(state, x):
        x = x.astype(jnp.float32)
        hx = h(x)
        pts, scs = state.points, state.scores
        pos = jnp.sum(hx <= scs)  # stages with score >= hx sit before x
        before = idx < pos        # empty slots (NEG) always sort after
        dom = before & jnp.all(x <= pts, axis=-1) & jnp.any(x < pts, axis=-1)
        pruned = jnp.any(dom)
        # sorted insert at pos (rolling displacement == shift right)
        shift = idx[:, None] > pos
        new_pts = jnp.where(idx[:, None] == pos, x,
                            jnp.where(shift, jnp.roll(pts, 1, axis=0), pts))
        new_scs = jnp.where(idx == pos, hx,
                            jnp.where(idx > pos, jnp.roll(scs, 1), scs))
        return SkylineState(new_pts, new_scs), ~pruned

    init = skyline_init(w, D) if state is None else state
    state, keep = jax.lax.scan(body, init, points.astype(jnp.float32))
    return PruneResult(keep=keep, state=state)


def skyline_oracle(points) -> jnp.ndarray:
    """True skyline membership mask (numpy O(m^2), test scale only)."""
    import numpy as np

    p = np.asarray(points, dtype=np.float64)
    m = p.shape[0]
    out = np.ones(m, bool)
    for i in range(m):
        dom = np.all(p >= p[i], axis=1) & np.any(p > p[i], axis=1)
        if dom.any():
            out[i] = False
    return jnp.asarray(out)


def opt_keep_skyline(points) -> jnp.ndarray:
    """OPT forwards a point iff no *previous* point dominates it."""
    import numpy as np

    p = np.asarray(points, dtype=np.float64)
    out = np.ones(p.shape[0], bool)
    for i in range(1, p.shape[0]):
        prev = p[:i]
        dom = np.all(prev >= p[i], axis=1) & np.any(prev > p[i], axis=1)
        out[i] = not dom.any()
    return jnp.asarray(out)


def master_complete_skyline(points, keep) -> jnp.ndarray:
    """Exact skyline over forwarded points, mapped back to original idx."""
    import numpy as np

    p = np.asarray(points, dtype=np.float64)
    k = np.asarray(keep)
    out = np.zeros(p.shape[0], bool)
    idx = np.nonzero(k)[0]
    sub = p[idx]
    for j, i in enumerate(idx):
        dom = np.all(sub >= sub[j], axis=1) & np.any(sub > sub[j], axis=1)
        out[i] = not dom.any()
    return jnp.asarray(out)
