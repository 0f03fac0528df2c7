"""Pallas TPU kernel: the merge-join gather at the heart of relabel (Alg. 6).

One ring round of the relabel phase is: given edges sorted by endpoint and
the current pv chunk resident locally, replace every endpoint that falls in
the chunk's range.  The paper does this as a sort-merge-join with cursor
advancement; on TPU the chunk sits in VMEM and the join is a *masked gather*
whose indices are monotone (the edges are sorted), i.e. sequential access —
the exact property the paper's chunk-sort buys.

BlockSpec tiling = the paper's mmc chunking: each grid step processes one
(BLOCK_ROWS, 128) tile of endpoint ids against the full pv chunk (the chunk
is the paper's bounded buffer; its block index_map is constant so it is
loaded into VMEM once and reused across all edge tiles).  `base` arrives via
scalar prefetch (SMEM) so one compiled kernel serves all nb ring rounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
BLOCK_ROWS = 8
TILE = LANE * BLOCK_ROWS


def _relabel_kernel(base_ref, keys_ref, pv_ref, o_ref):
    base = base_ref[0]
    keys = keys_ref[...]                    # [BLOCK_ROWS, LANE] int32
    pv = pv_ref[...]                        # [1, B] pv chunk, resident
    B = pv.shape[1]
    local = keys - base
    in_range = (local >= 0) & (local < B)
    idx = jnp.clip(local, 0, B - 1)
    gathered = jnp.take(pv[0], idx.reshape(-1), axis=0).reshape(keys.shape)
    o_ref[...] = jnp.where(in_range, gathered, keys)


@functools.partial(jax.jit, static_argnames=("interpret",))
def relabel_gather_pallas(
    keys: jnp.ndarray, pv_chunk: jnp.ndarray, base: jnp.ndarray, *, interpret: bool
) -> jnp.ndarray:
    """Relabel keys in [base, base+B) through pv_chunk; others pass through.

    |keys| must be a multiple of TILE (ops.py pads with -1, never in range).
    """
    n = keys.shape[0]
    assert n % TILE == 0, f"n={n} must be a multiple of {TILE}"
    B = pv_chunk.shape[0]
    out = pl.pallas_call(
        _relabel_kernel,
        grid=(n // TILE,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # base scalar
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((1, B), lambda i: (0, 0)),         # chunk resident in VMEM
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // LANE, LANE), jnp.int32),
        interpret=interpret,
    )(base.reshape(1).astype(jnp.int32), keys.reshape(-1, LANE), pv_chunk.reshape(1, B))
    return out.reshape(-1)
