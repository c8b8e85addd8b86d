"""GROUP BY pruning (paper §4.2/§8, Table 2 row GROUP BY).

The switch maintains a d×w matrix of (key, aggregate) pairs. For a
commutative-monoid aggregate (SUM/COUNT/MIN/MAX) an arriving entry whose
key is cached is *folded into* the cached aggregate and pruned; on a miss
the rolling replacement evicts a (key, partial) pair which is emitted to
the master as a synthetic entry (the paper's packet-with-new-values). The
master folds forwarded entries + emitted partials + the final state —
exactly Q(D) because the aggregate is associative/commutative.

keep[i]=False means entry i's value was absorbed into switch state; the
emitted stream (same length m, masked) carries evictions.

The aggregate state takes its dtype from the value column
(``acc_dtype``): integer-valued columns (and COUNT) aggregate in int32,
exactly up to 2^31 per group, the way engines hold TPC-H DECIMAL as
scaled integers; float columns keep f32, which is exact only while
partial sums stay below 2^24.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .hashing import hash_mod
from .pruning import PruneResult

_FOLD = {
    "sum": lambda a, v: a + v,
    "count": lambda a, v: a + 1,
    "min": jnp.minimum,
    "max": jnp.maximum,
}


def acc_dtype(values_dtype, agg: str = "sum"):
    """Aggregate-state dtype: int32 for COUNT and integer/bool columns,
    f32 for float columns."""
    if agg == "count" or not jnp.issubdtype(values_dtype, jnp.floating):
        return jnp.dtype(jnp.int32)
    return jnp.dtype(jnp.float32)


def init_value(agg: str, dtype):
    """The aggregate's identity in ``dtype`` (a numpy scalar)."""
    dtype = jnp.dtype(dtype)
    if agg in ("sum", "count"):
        return dtype.type(0)
    info = (jnp.iinfo if jnp.issubdtype(dtype, jnp.integer)
            else jnp.finfo)(dtype)
    return dtype.type(info.max if agg == "min" else info.min)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GroupByState:
    keys: jnp.ndarray  # uint32[d, w]
    aggs: jnp.ndarray  # acc_dtype[d, w]
    valid: jnp.ndarray  # bool[d, w]


def groupby_init(d: int, w: int, agg: str = "sum",
                 dtype=jnp.float32) -> GroupByState:
    """Empty cache; ``dtype`` is the value column's dtype."""
    acc = acc_dtype(dtype, agg)
    return GroupByState(
        keys=jnp.zeros((d, w), jnp.uint32),
        aggs=jnp.full((d, w), init_value(agg, acc), acc),
        valid=jnp.zeros((d, w), jnp.bool_),
    )


@partial(jax.jit, static_argnames=("d", "w", "agg", "seed"))
def groupby_prune(keys: jnp.ndarray, values: jnp.ndarray,
                  valid: jnp.ndarray | None = None, *, d: int, w: int,
                  agg: str = "sum", seed: int = 0,
                  state: GroupByState | None = None) -> PruneResult:
    """Returns keep mask + emitted (evicted_key, evicted_agg, evicted_valid).

    valid: optional bool[m] entry-validity column. Entries with
    valid=False leave the switch state completely untouched (no fold, no
    insertion, no eviction) — the hook sharded execution uses to make
    tail pads inert under *every* aggregate, including COUNT, which has
    no neutral pad value (each entry folds +1 regardless of its value).

    state: resume from a prior call's final cache — partials folded in an
    earlier micro-batch keep aggregating, and evictions of carried
    partials are emitted exactly as in one scan over the concatenation.
    """
    fold = _FOLD[agg]
    acc = acc_dtype(values.dtype, agg)
    init_v = init_value(agg, acc)
    rows = hash_mod(keys, d, seed=seed)
    if valid is None:
        valid = jnp.ones(keys.shape[0], jnp.bool_)

    def body(state, krvo):
        k, r, v, ok = krvo
        krow, arow, vrow = state.keys[r], state.aggs[r], state.valid[r]
        hitvec = (krow == k) & vrow
        hit = jnp.any(hitvec)
        hitpos = jnp.argmax(hitvec)
        # fold into cached aggregate on hit
        arow_hit = arow.at[hitpos].set(fold(arow[hitpos], v))
        # miss: insert (k, fold(init, v)) at front, evict last slot
        ev_k, ev_a, ev_valid = krow[-1], arow[-1], vrow[-1] & ~hit & ok
        krow_miss = jnp.roll(krow, 1).at[0].set(k)
        arow_miss = jnp.roll(arow, 1).at[0].set(fold(init_v, v))
        vrow_miss = jnp.roll(vrow, 1).at[0].set(True)
        new_k = jnp.where(ok, jnp.where(hit, krow, krow_miss), krow)
        new_a = jnp.where(ok, jnp.where(hit, arow_hit, arow_miss), arow)
        new_vld = jnp.where(ok, jnp.where(hit, vrow, vrow_miss), vrow)
        state = GroupByState(
            keys=state.keys.at[r].set(new_k),
            aggs=state.aggs.at[r].set(new_a),
            valid=state.valid.at[r].set(new_vld),
        )
        # entry is always absorbed (pruned); evictions are the traffic
        return state, (jnp.bool_(False), ev_k, ev_a, ev_valid)

    init = groupby_init(d, w, agg, values.dtype) if state is None else state
    state, (keep, ev_k, ev_a, ev_valid) = jax.lax.scan(
        body, init, (keys, rows, values.astype(acc), valid))
    return PruneResult(keep=keep, state=state, emitted=(ev_k, ev_a, ev_valid))


def master_complete_groupby(result: PruneResult, agg: str = "sum") -> dict:
    """Fold evicted partials + final switch state into exact Q(D)."""
    import numpy as np

    fold = {"sum": lambda a, v: a + v, "count": lambda a, v: a + v,
            "min": min, "max": max}[agg]
    out: dict = {}
    ev_k, ev_a, ev_valid = result.emitted
    for k, a, ok in zip(np.asarray(ev_k).tolist(), np.asarray(ev_a).tolist(),
                        np.asarray(ev_valid).tolist()):
        if ok:
            out[k] = fold(out[k], a) if k in out else a
    st = result.state
    for k, a, ok in zip(np.asarray(st.keys).ravel().tolist(),
                        np.asarray(st.aggs).ravel().tolist(),
                        np.asarray(st.valid).ravel().tolist()):
        if ok:
            out[k] = fold(out[k], a) if k in out else a
    return out


def groupby_oracle(keys, values, agg: str = "sum") -> dict:
    import numpy as np

    fold = {"sum": lambda a, v: a + v, "count": lambda a, v: a + 1,
            "min": min, "max": max}[agg]
    init = {"sum": 0.0, "count": 0.0}.get(agg)
    out: dict = {}
    for k, v in zip(np.asarray(keys).tolist(), np.asarray(values).tolist()):
        if k in out:
            out[k] = fold(out[k], v)
        elif agg in ("min", "max"):
            out[k] = v
        else:
            out[k] = fold(init, v)
    return out
