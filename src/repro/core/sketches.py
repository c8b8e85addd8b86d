"""Shared sketch substrate: Bloom filter and Count-Min (paper Ex. 4/5).

Bloom: no false negatives → JOIN never prunes a matching key.
Count-Min: one-sided overestimate → HAVING f(x) > c never loses a key.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .hashing import multi_hash


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BloomFilter:
    bits: jnp.ndarray  # bool[nbits]  (kernel variant packs into uint32 words)
    num_hashes: int = dataclasses.field(metadata=dict(static=True), default=3)
    seed: int = dataclasses.field(metadata=dict(static=True), default=0)


def bloom_build(keys: jnp.ndarray, nbits: int, num_hashes: int = 3, seed: int = 0,
                mask: jnp.ndarray | None = None) -> BloomFilter:
    """Vectorized build: scatter-True is race-free and idempotent."""
    idx = multi_hash(keys, nbits, num_hashes, seed=seed)  # [m, H]
    if mask is not None:
        # inactive entries all target a dedicated dummy slot? No — drop them
        # by scattering to their own position only when active.
        idx = jnp.where(mask[:, None], idx, -1)
        bits = jnp.zeros(nbits + 1, jnp.bool_).at[idx.reshape(-1)].set(True)[:nbits]
    else:
        bits = jnp.zeros(nbits, jnp.bool_).at[idx.reshape(-1)].set(True)
    return BloomFilter(bits=bits, num_hashes=num_hashes, seed=seed)


def bloom_query(f: BloomFilter, keys: jnp.ndarray) -> jnp.ndarray:
    idx = multi_hash(keys, f.bits.shape[0], f.num_hashes, seed=f.seed)
    # one 1-D gather per hash: the TPU compiler takes minutes over a
    # single [m, H]-index gather at m in the millions, seconds over these
    ok = f.bits[idx[..., 0]]
    for h in range(1, f.num_hashes):
        ok = ok & f.bits[idx[..., h]]
    return ok


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CountMin:
    table: jnp.ndarray  # int32/f32 [rows, width]
    seed: int = dataclasses.field(metadata=dict(static=True), default=0)


def cms_build(keys: jnp.ndarray, weights: jnp.ndarray | None, rows: int, width: int,
              seed: int = 0) -> CountMin:
    """COUNT (weights=None) or SUM sketch; scatter-add per row."""
    if weights is None:
        weights = jnp.ones(keys.shape[0], jnp.int32)
    idx = multi_hash(keys, width, rows, seed=seed)  # [m, rows]
    table = jnp.zeros((rows, width), weights.dtype)
    for r in range(rows):  # rows is small (2-4); unrolled scatter-adds
        table = table.at[r].add(
            jnp.zeros(width, weights.dtype).at[idx[:, r]].add(weights))
    return CountMin(table=table, seed=seed)


def cms_query(s: CountMin, keys: jnp.ndarray) -> jnp.ndarray:
    rows, width = s.table.shape
    idx = multi_hash(keys, width, rows, seed=s.seed)  # [m, rows]
    est = s.table[jnp.arange(rows)[None, :], idx]     # [m, rows]
    return jnp.min(est, axis=-1)  # >= true value (one-sided)
