"""Pallas TPU kernel: deterministic TOP-N pruning over RLE runs.

Prunes a run-length-compressed column without expanding the runs: the
threshold-ladder scan (core.topn.topn_det_prune) admits a per-run closed
form because every entry of a run carries the same value v, so the
ladder comparison vector ``ge[i] = v >= t0·2^i`` is constant across the
run and the per-entry level counts grow linearly in the within-run
position t.

Per run (v, L) with entering state (t0, counts[w], seen):

    t0'      = seen < N ? min(t0, v) : t0          (warmup running min)
    ge[i]    = v >= t0'·2^i                        (constant over the run)
    A        = max({i : counts[i] >= N and not ge[i]} ∪ {-1})
    C        = max({counts[i] : ge[i] and i > A} ∪ {-1})
    W        = clip(N - seen, 0, L)                (warmup prefix length)
    tstar    = A < 0 ? 1 : (C >= 0 ? N - C : BIG)

and the flat keep mask within the run is the prefix∪suffix

    keep[t] = (t < W) | (t + 1 >= tstar),  t = 0..L-1

(kernels.ops.rle_expand_mask materializes it). Correctness: at entry t
the active level is cur_t = max(A, B_t) with B_t the best qualifying
ladder rung among the ge levels; B_t is nondecreasing in t and exceeds A
exactly when t+1 >= N - C, at which point keep is certain (ge[cur]);
below that cur_t = A whose rung the run fails, so only warmup keeps.
With A = -1 every entry keeps (either cur = -1 or ge[B_t] holds) —
hence tstar = 1. Note ge need NOT be a prefix in i when t0' <= 0, which
is why A/C are computed from the full vector rather than a level index.

State across runs: counts += L·ge, seen += L, t0 = t0'. Pad runs MUST be
(v = POS, L = 0): POS never lowers t0 during warmup and L = 0 leaves
counts/seen untouched (NEG pads would corrupt t0).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import POS, compiler_params

_BIG = np.int32(1 << 30)


def _lane_scan(x: jnp.ndarray, op, identity) -> jnp.ndarray:
    """Inclusive prefix ``op`` along lanes: log2(n) roll-and-combine
    steps (Mosaic has no cumsum/cummin)."""
    n = x.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < n:
        x = op(x, jnp.where(iota >= s, pltpu.roll(x, s, 1), identity))
        s *= 2
    return x


def _pow2(i: jnp.ndarray) -> jnp.ndarray:
    """Exact 2.0**i for small non-negative int32 i (built from bits)."""
    return jax.lax.bitcast_convert_type((i + 127) << 23, jnp.float32)


def _run_math(v, L, t0, counts, seen, N, w):
    """Vectorized closed form for a block of B runs, runs along lanes.

    v: f32[1, B], L: i32[1, B], t0: f32[1, 1], counts: i32[w, 1], seen:
    i32[1, 1] — entering state. Returns (head, tstar, t0', counts',
    seen') in the same shapes.
    """
    B = v.shape[1]
    cumL = _lane_scan(L, jnp.add, 0)
    seen_start = seen + cumL - L                       # entering each run
    warm = seen_start < N
    # prefix running-min of warmup candidates (non-warm runs contribute POS)
    cand = jnp.where(warm, v, POS)
    t0_run = jnp.minimum(t0, _lane_scan(cand, jnp.minimum, POS))
    wi = jax.lax.broadcasted_iota(jnp.int32, (w, B), 0)
    ge = v >= t0_run * _pow2(wi)                       # [w, B]
    dL = L * ge.astype(jnp.int32)                      # per-run count bumps
    counts_in = counts + _lane_scan(dL, jnp.add, 0) - dL  # entering counts
    A = jnp.max(jnp.where(~ge & (counts_in >= N), wi, -1), axis=0,
                keepdims=True)
    C = jnp.max(jnp.where(ge & (wi > A), counts_in, -1), axis=0,
                keepdims=True)
    head = jnp.clip(N - seen_start, 0, L)
    tstar = jnp.where(A < 0, 1, jnp.where(C >= 0, N - C, _BIG))
    return (head, tstar, t0_run[:, B - 1:],
            counts + jnp.sum(dL, axis=1, keepdims=True),
            seen + cumL[:, B - 1:])


def _kernel(N, w, rv_ref, rl_ref, head_ref, tstar_ref,
            t0_ref, seen_ref, counts_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        t0_ref[...] = jnp.full_like(t0_ref, POS)
        seen_ref[...] = jnp.zeros_like(seen_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    head, tstar, t0, counts, seen = _run_math(
        rv_ref[...], rl_ref[...], t0_ref[...], counts_ref[...],
        seen_ref[...], N, w)
    head_ref[...] = head
    tstar_ref[...] = tstar
    t0_ref[...] = t0
    seen_ref[...] = seen
    counts_ref[...] = counts


@partial(jax.jit, static_argnames=("N", "w", "block", "interpret"))
def rle_topn_det_kernel(run_values: jnp.ndarray, run_lengths: jnp.ndarray,
                        *, N: int, w: int, block: int, interpret: bool):
    """(head i32[R], tstar i32[R]) per run; R % block == 0.

    Pad runs must be (POS, 0) — see module docstring.
    """
    R = run_values.shape[0]
    assert R % block == 0, "pad the runs to a multiple of block"
    spec = pl.BlockSpec((1, block), lambda i: (0, i))
    head, tstar = pl.pallas_call(
        partial(_kernel, N, w),
        grid=(R // block,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=(jax.ShapeDtypeStruct((1, R), jnp.int32),
                   jax.ShapeDtypeStruct((1, R), jnp.int32)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),   # t0
            pltpu.VMEM((1, 1), jnp.int32),     # seen
            pltpu.VMEM((w, 1), jnp.int32),     # ladder counts
        ],
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret,
    )(run_values.astype(jnp.float32).reshape(1, R),
      run_lengths.astype(jnp.int32).reshape(1, R))
    return head.reshape(R), tstar.reshape(R)


@partial(jax.jit, static_argnames=("N", "w"))
def rle_topn_det_ref(run_values: jnp.ndarray, run_lengths: jnp.ndarray,
                     *, N: int, w: int = 4):
    """Pure-jnp oracle: one lax.scan step per run, same closed form."""
    def body(carry, vL):
        t0, counts, seen = carry
        v, L = vL
        head, tstar, t0n, countsn, seenn = _run_math(
            v.reshape(1, 1), L.reshape(1, 1), t0, counts, seen, N, w)
        return (t0n, countsn, seenn), (head[0, 0], tstar[0, 0])

    init = (jnp.full((1, 1), POS, jnp.float32), jnp.zeros((w, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32))
    _, (head, tstar) = jax.lax.scan(
        body, init, (run_values.astype(jnp.float32),
                     run_lengths.astype(jnp.int32)))
    return head, tstar
