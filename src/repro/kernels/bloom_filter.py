"""Pallas TPU kernels: Bloom filter build + probe (paper Ex. 4, JOIN).

The bits are an f32 0/1 table laid out lane-dense as (128, R): bit b
sits at lane b % 128 of column b // 128 (the packed-word uint32 variant
trades 32x memory for in-kernel shifts — noted in DESIGN.md as a
deliberate TPU adaptation). A key's bit index splits into a one-hot
over the R columns and one over the 128 lanes. Build: sequential grid;
per block the (128, B) @ (B, R) matmul of the two one-hots counts the
hits of every bit. Probe: parallel grid; the (128, R) @ (R, B) matmul
fetches each key's column, and a masked max picks its lane. Every
operand is 0/1, so the MXU is exact at any precision.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, dense_onehots, hash_mod, lane_pick

_NT = (((1,), (1,)), ((), ()))   # contract the entry (lane) axes


def _build_kernel(nbits, H, seed, nblocks, k_ref, out_ref, b_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        b_ref[...] = jnp.zeros_like(b_ref)

    keys = k_ref[...]
    bits = b_ref[...]
    for h in range(H):
        lanes, cols = dense_onehots(hash_mod(keys, nbits, seed + h * 101),
                                    bits.shape[1])
        hits = jax.lax.dot_general(lanes, cols, _NT,
                                   preferred_element_type=jnp.float32)
        bits = jnp.maximum(bits, jnp.minimum(hits, 1.0))
    b_ref[...] = bits

    @pl.when(pl.program_id(0) == nblocks - 1)
    def _emit():
        out_ref[...] = b_ref[...]


@partial(jax.jit, static_argnames=("nbits", "num_hashes", "block", "seed",
                                   "interpret"))
def bloom_build_kernel(keys: jnp.ndarray, *, nbits: int, num_hashes: int,
                       block: int, seed: int,
                       interpret: bool) -> jnp.ndarray:
    m = keys.shape[0]
    assert m % block == 0
    assert nbits < (1 << 16)
    nb = m // block
    R = -(-nbits // 128)
    bits = pl.pallas_call(
        partial(_build_kernel, nbits, num_hashes, seed, nb),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((128, R), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((128, R), jnp.float32),
        scratch_shapes=[pltpu.VMEM((128, R), jnp.float32)],
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret,
    )(keys.reshape(1, m))
    return bits.T.reshape(-1)[:nbits]


def _query_kernel(nbits, H, seed, b_ref, k_ref, ok_ref):
    keys = k_ref[...]
    bits = b_ref[...]
    ok = jnp.ones(keys.shape, jnp.float32)
    for h in range(H):
        idx = hash_mod(keys, nbits, seed + h * 101)
        lanes, cols = dense_onehots(idx, bits.shape[1])
        col = jnp.dot(bits, cols, preferred_element_type=jnp.float32)
        ok = jnp.minimum(ok, lane_pick(lanes, col, 0.0))
    ok_ref[...] = (ok > 0.5).astype(jnp.int32)


@partial(jax.jit, static_argnames=("num_hashes", "block", "seed",
                                   "interpret"))
def bloom_query_kernel(bits: jnp.ndarray, keys: jnp.ndarray, *,
                       num_hashes: int, block: int, seed: int,
                       interpret: bool) -> jnp.ndarray:
    m = keys.shape[0]
    nbits = bits.shape[0]
    assert m % block == 0
    R = -(-nbits // 128)
    table = jnp.zeros(R * 128, jnp.float32).at[:nbits].set(bits)
    return pl.pallas_call(
        partial(_query_kernel, nbits, num_hashes, seed),
        grid=(m // block,),
        in_specs=[pl.BlockSpec((128, R), lambda i: (0, 0)),
                  pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(table.reshape(R, 128).T, keys.reshape(1, m)).reshape(m)
