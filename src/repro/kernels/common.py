"""Shared in-kernel helpers for the Cheetah pruning kernels.

Layout (DESIGN.md §5, TPU adaptation of the switch pipeline):

* An entry stream enters a kernel as one lane row of shape ``(1, m)``
  (``ops`` reshapes ``(m,)`` for free) and each grid step reads a
  ``(1, block)`` row, ``block`` a multiple of 128 on the chip. A 1-D
  block would have to match XLA's 1024-entry tiling of 1-D arrays; the
  row satisfies Mosaic's (8, 128) block rule because its sublane
  extent equals the array's.
* Per-row switch state is a ``(rows, k)`` array, one hash row per
  sublane. The per-packet "hash to a row, read the row registers"
  becomes a ``(rows, block)`` one-hot mask: a gather is a masked max
  over sublanes and a per-row reduction is a masked reduce over lanes.
  Both are exact for every dtype, unlike an MXU matmul of f32 values.
* State taller than ``CHUNK`` rows is padded to a multiple of it and
  walked in ``CHUNK``-row slices (``over_rows``), which bounds the
  one-hot working set at ``CHUNK × block``. A row belongs to exactly
  one slice and only its own entries read it, so gathering from and
  updating one slice at a time is exact.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..constants import NEG, POS, SENTINEL  # noqa: F401  (shared sentinels)

# numpy scalars → jaxpr literals (jnp constants would be captured consts,
# which pallas_call rejects)
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_C3 = np.uint32(0x9E3779B9)

CHUNK = 512
F32_LOW = np.float32(-np.inf)   # gather fill for f32 state
I32_LOW = np.int32(np.iinfo(np.int32).min)


def compiler_params(dimension_semantics: tuple) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def mix32(x: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """murmur3 fmix32 — identical math to repro.core.hashing.mix32."""
    h = x ^ np.uint32(seed)
    h = h ^ (h >> 16)
    h = h * _C1
    h = h ^ (h >> 13)
    h = h * _C2
    h = h ^ (h >> 16)
    return h


def hash_mod(x: jnp.ndarray, mod: int, seed: int = 0) -> jnp.ndarray:
    """Range-reduce to {0..mod-1}; multiply-shift (mod < 2^16), else %."""
    h = mix32(x, seed)
    if mod < (1 << 16):
        lo = h & np.uint32(0xFFFF)
        hi = h >> 16
        m = np.uint32(mod)
        t = (hi * m) + ((lo * m) >> 16)
        return lax.bitcast_convert_type(t >> 16, jnp.int32)
    return lax.bitcast_convert_type(h % np.uint32(mod), jnp.int32)


def as_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Non-negative int32 → uint32, as a bitcast (the same bits)."""
    return lax.bitcast_convert_type(x, jnp.uint32)


def lane_iota(n: int) -> jnp.ndarray:
    """int32 (1, n) lane positions."""
    return lax.broadcasted_iota(jnp.int32, (1, n), 1)


def padded_rows(d: int) -> int:
    """State height: ``d``, or a multiple of CHUNK above it."""
    return d if d <= CHUNK else -(-d // CHUNK) * CHUNK


def over_rows(dp: int, body, carry):
    """Fold ``body(r0, C, carry)`` over the CHUNK-row slices of ``dp``
    state rows (one static slice when ``dp <= CHUNK``)."""
    if dp <= CHUNK:
        return body(0, dp, carry)
    return lax.fori_loop(
        0, dp // CHUNK,
        lambda c, acc: body(pl.multiple_of(c * CHUNK, CHUNK), CHUNK, acc),
        carry)


def onehot(rows: jnp.ndarray, r0, C: int) -> jnp.ndarray:
    """(1, B) hash rows → (C, B) mask of the entries owned by rows
    [r0, r0 + C)."""
    return lax.broadcasted_iota(jnp.int32, (C, rows.shape[1]), 0) + r0 == rows


def gather(oh: jnp.ndarray, col: jnp.ndarray, fill) -> jnp.ndarray:
    """(C, 1) per-row values → (1, B) value of each entry's row; ``fill``
    for entries whose row is outside the slice."""
    return jnp.max(jnp.where(oh, col, fill), axis=0, keepdims=True)


def row_max(oh: jnp.ndarray, x: jnp.ndarray, fill) -> jnp.ndarray:
    """(1, B) entry values → (C, 1) max over each row's entries."""
    return jnp.max(jnp.where(oh, x, fill), axis=1, keepdims=True)


def any_rows(mask: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Boolean any along ``axis`` (kept), through int32."""
    return jnp.max(mask.astype(jnp.int32), axis=axis, keepdims=True) > 0


def shift_down(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """``a`` moved one slot along ``axis``, slot 0 duplicated (the
    rolling displacement of a sorted insert)."""
    if a.shape[axis] == 1:
        return a
    head = lax.slice_in_dim(a, 0, 1, axis=axis)
    body = lax.slice_in_dim(a, 0, a.shape[axis] - 1, axis=axis)
    return jnp.concatenate([head, body], axis=axis)


def dense_onehots(idx: jnp.ndarray, R: int):
    """(1, B) indices into a lane-dense (128, R) table (index i at lane
    i % 128 of column i // 128) → f32 one-hots: lanes (128, B), columns
    (R, B)."""
    B = idx.shape[1]
    lanes = lax.broadcasted_iota(jnp.int32, (128, B), 0) == (idx & 127)
    cols = lax.broadcasted_iota(jnp.int32, (R, B), 0) == (idx >> 7)
    return lanes.astype(jnp.float32), cols.astype(jnp.float32)


def lane_pick(lanes: jnp.ndarray, col: jnp.ndarray, fill) -> jnp.ndarray:
    """(128, B) fetched table columns → (1, B) value at each entry's lane."""
    return jnp.max(jnp.where(lanes > 0, col, fill), axis=0, keepdims=True)
