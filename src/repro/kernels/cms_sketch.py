"""Pallas TPU kernels: Count-Min sketch build + query (paper Ex. 5, HAVING).

Each sketch row's counters are laid out lane-dense as (128, R), counter
c at lane c % 128 of column c // 128, as the Bloom bits are
(bloom_filter.py). Build: sequential grid over key blocks, the table in
VMEM scratch, emitted on the final step; per sketch row the scatter-add
is a (128, B) @ (B, R) matmul of the weighted lane one-hot with the
column one-hot. Query: embarrassingly parallel gather-min, a
(128, R) @ (R, B) matmul fetching each key's column and a masked max
picking its lane. Weights and counters are arbitrary f32, so both
matmuls ask for full f32 precision.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (F32_LOW, compiler_params, dense_onehots, hash_mod,
                     lane_pick)

_NT = (((1,), (1,)), ((), ()))   # contract the entry (lane) axes
_F32 = jax.lax.Precision.HIGHEST


def _build_kernel(rows, width, seed, nblocks, k_ref, w_ref, out_ref, t_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    keys = k_ref[...]
    wts = w_ref[...]
    for r in range(rows):  # rows is 2-4: unrolled stages, as on the switch
        lanes, cols = dense_onehots(hash_mod(keys, width, seed + r * 101),
                                    t_ref.shape[2])
        t_ref[r] = t_ref[r] + jax.lax.dot_general(
            jnp.where(lanes > 0, wts, 0.0), cols, _NT, precision=_F32,
            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) == nblocks - 1)
    def _emit():
        out_ref[...] = t_ref[...]


@partial(jax.jit, static_argnames=("rows", "width", "block", "seed",
                                   "interpret"))
def cms_build_kernel(keys: jnp.ndarray, weights: jnp.ndarray, *, rows: int,
                     width: int, block: int, seed: int,
                     interpret: bool) -> jnp.ndarray:
    m = keys.shape[0]
    assert m % block == 0
    assert width < (1 << 16)
    nb = m // block
    R = -(-width // 128)
    table = pl.pallas_call(
        partial(_build_kernel, rows, width, seed, nb),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (0, i)),
                  pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows, 128, R), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 128, R), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, 128, R), jnp.float32)],
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret,
    )(keys.reshape(1, m), weights.astype(jnp.float32).reshape(1, m))
    return table.transpose(0, 2, 1).reshape(rows, -1)[:, :width]


def _query_kernel(rows, width, seed, t_ref, k_ref, est_ref):
    keys = k_ref[...]
    est = jnp.full(keys.shape, np.float32(3.4e38))
    for r in range(rows):
        lanes, cols = dense_onehots(hash_mod(keys, width, seed + r * 101),
                                    t_ref.shape[2])
        col = jnp.dot(t_ref[r], cols, precision=_F32,
                      preferred_element_type=jnp.float32)
        est = jnp.minimum(est, lane_pick(lanes, col, F32_LOW))
    est_ref[...] = est


@partial(jax.jit, static_argnames=("block", "seed", "interpret"))
def cms_query_kernel(table: jnp.ndarray, keys: jnp.ndarray, *, block: int,
                     seed: int, interpret: bool) -> jnp.ndarray:
    m = keys.shape[0]
    rows, width = table.shape
    assert m % block == 0
    R = -(-width // 128)
    dense = jnp.zeros((rows, R * 128), jnp.float32).at[:, :width].set(table)
    dense = dense.reshape(rows, R, 128).transpose(0, 2, 1)
    return pl.pallas_call(
        partial(_query_kernel, rows, width, seed),
        grid=(m // block,),
        in_specs=[pl.BlockSpec((rows, 128, R), lambda i: (0, 0, 0)),
                  pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(dense, keys.reshape(1, m)).reshape(m)
