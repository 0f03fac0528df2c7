"""Pallas TPU kernel: per-destination histogram (redistribute planning).

The scatter side of the k:1 pattern needs, per shard, the count of records
bound for each destination (paper Alg. 8's packet bookkeeping; also the MoE
router's expert-load statistics).  TPUs have no scatter-atomics, so the
kernel computes the histogram as a *compare-and-reduce*: each grid step
loads one (BLOCK_ROWS, 128) tile of destination ids, compares it against
each destination id in turn (k is static and small: the shard count), and
accumulates the per-destination sums into a VMEM accumulator that persists
across grid steps (output block index_map is constant; initialized at step
0, read back after the last step).  The tile keeps its (8, 128) layout: the
TPU compiler refuses to reshape it into a (1024, 1) column.  Sequential
access only — the same random->sequential conversion the paper applies to
CSR.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
BLOCK_ROWS = 8
TILE = LANE * BLOCK_ROWS


def _bucket_kernel(dest_ref, o_ref, *, k: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    dest = dest_ref[...]  # [BLOCK_ROWS, LANE] int32
    ids = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)  # [1, k]
    counts = jnp.zeros((1, k), jnp.int32)
    for j in range(k):  # static unroll over destinations
        hits = (dest == j).astype(jnp.int32)
        c = jnp.sum(jnp.sum(hits, axis=0, keepdims=True), axis=1, keepdims=True)  # [1, 1]
        counts = counts + jnp.where(ids == j, c, 0)
    o_ref[...] += counts


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def bucket_hist_pallas(dest: jnp.ndarray, k: int, *, interpret: bool) -> jnp.ndarray:
    """Histogram of `dest` (int32 in [0, k)) -> counts [k] int32.

    |dest| must be a multiple of TILE (ops.py pads with k, an out-of-range
    sentinel that never matches the iota).
    """
    n = dest.shape[0]
    assert n % TILE == 0, f"n={n} must be a multiple of {TILE}"
    grid = n // TILE
    counts = pl.pallas_call(
        functools.partial(_bucket_kernel, k=k),
        grid=(grid,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, k), lambda i: (0, 0)),  # accumulator
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.int32),
        interpret=interpret,
    )(dest.reshape(-1, LANE))
    return counts[0]
