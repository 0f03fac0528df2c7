"""Pallas TPU kernel: R-MAT edge generation (paper Alg. 5 hot loop).

The edge generator is the pipeline's compute hot spot: `scale` levels of
(2 hashes + 2 compares + 2 shifted adds) per edge, fully data-parallel.  On
the paper's CPUs this was the per-core pthread loop; on TPU it is a VPU
kernel: edges are laid out as (rows, 128) tiles, each grid step produces one
(BLOCK_ROWS, 128) tile of src and dst in VMEM, the level walk is unrolled
`scale` times (static), and the counter-based RNG (core.rmat.mix32) needs no
state — every tile derives its randomness from the global edge index, so
tiles are generated independently and identically at any grid decomposition
(bit-exact vs the jnp oracle, tested).

LANE=128 matches the VPU lane count; BLOCK_ROWS=8 gives 8x128 int32 tiles =
4 KiB per ref, a comfortable VMEM working set (3 live tiles + temporaries).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.hostgen import FEISTEL_ROUNDS, feistel_round_key_np
from ..core.types import GraphConfig, quadrant_thresholds

LANE = 128
BLOCK_ROWS = 8
TILE = LANE * BLOCK_ROWS


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _uniform(seed: int, idx, stream: int):
    s = jnp.uint32((seed ^ (stream * 0x9E3779B9)) & 0xFFFFFFFF)
    return _mix32(_mix32(idx + s) ^ s)


def _rmat_kernel(o_src_ref, o_dst_ref, *, seed: int, scale: int, thresholds, start: int):
    t_src, t_dst0, t_dst1 = thresholds
    i = pl.program_id(0)
    # global edge index of each slot in this tile
    row = jax.lax.broadcasted_iota(jnp.uint32, (BLOCK_ROWS, LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, (BLOCK_ROWS, LANE), 1)
    idx = jnp.uint32(start) + i.astype(jnp.uint32) * jnp.uint32(TILE) + row * jnp.uint32(LANE) + lane
    src = jnp.zeros((BLOCK_ROWS, LANE), jnp.uint32)
    dst = jnp.zeros((BLOCK_ROWS, LANE), jnp.uint32)
    for level in range(scale):  # static unroll of the quadtree walk
        r1 = _uniform(seed, idx, 2 * level)
        r2 = _uniform(seed, idx, 2 * level + 1)
        src_bit = r1 < jnp.uint32(t_src)
        t_d = jnp.where(src_bit, jnp.uint32(t_dst1), jnp.uint32(t_dst0))
        dst_bit = r2 < t_d
        src = (src << 1) | src_bit.astype(jnp.uint32)
        dst = (dst << 1) | dst_bit.astype(jnp.uint32)
    o_src_ref[...] = src.astype(jnp.int32)
    o_dst_ref[...] = dst.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg", "start", "count", "interpret"))
def rmat_edges_pallas(cfg: GraphConfig, start: int, count: int, *, interpret: bool):
    """Generate `count` edges with global ids [start, start+count).

    count must be a multiple of TILE (ops.py pads otherwise).
    """
    assert count % TILE == 0, f"count={count} must be a multiple of {TILE}"
    rows = count // LANE
    grid = rows // BLOCK_ROWS
    kernel = functools.partial(
        _rmat_kernel,
        seed=cfg.seed,
        scale=cfg.scale,
        thresholds=quadrant_thresholds(cfg),
        start=start,
    )
    out_shape = jax.ShapeDtypeStruct((rows, LANE), jnp.int32)
    src, dst = pl.pallas_call(
        kernel,
        grid=(grid,),
        out_specs=(
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        ),
        out_shape=(out_shape, out_shape),
        interpret=interpret,
    )()
    return src.reshape(-1), dst.reshape(-1)


def _feistel_kernel(x_ref, o_ref, *, key: int, nbits: int, rounds: int):
    """Keyed Feistel permutation tile (twin of hostgen.feistel_perm_np).

    Round keys are Python-int constants folded at trace time (the SAME
    numpy derivation the host family uses, so the three implementations
    share one key schedule by construction); the round loop is a static
    unroll like the R-MAT level walk — per element it is `rounds` mix32
    evaluations plus shifts/xors, pure VPU work."""
    lo_bits = nbits // 2
    x = x_ref[...].astype(jnp.uint32)
    L = x >> lo_bits
    R = x & jnp.uint32((1 << lo_bits) - 1)
    wL, wR = nbits - lo_bits, lo_bits
    for i in range(rounds):  # static unroll
        rk = jnp.uint32(int(feistel_round_key_np(key, i)))
        F = _mix32(R ^ rk)
        L, R, wL, wR = R, (L ^ F) & jnp.uint32((1 << wL) - 1), wR, wL
    o_ref[...] = ((L << lo_bits) | R).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("key", "nbits", "rounds", "interpret"))
def feistel_perm_pallas(x: jnp.ndarray, key: int, nbits: int,
                        rounds: int = FEISTEL_ROUNDS, *,
                        interpret: bool) -> jnp.ndarray:
    """Permute int32 ids through the keyed Feistel bijection on
    [0, 2**nbits), as (BLOCK_ROWS, 128) VMEM tiles.

    Power-of-two domains only (the pipeline's n = 2**scale case — cycle
    walking is data-dependent control flow and stays on the host/jnp
    paths); nbits <= 31 so outputs fit int32.  x.size must be a multiple
    of TILE (callers pad, as with rmat_edges_pallas).  Bit-exact vs
    shuffle.feistel_perm and hostgen.feistel_perm_np (tested).
    """
    assert x.size % TILE == 0, f"size={x.size} must be a multiple of {TILE}"
    assert 1 <= nbits <= 31, f"int32 lanes need 1 <= nbits <= 31, got {nbits}"
    rows = x.size // LANE
    grid = rows // BLOCK_ROWS
    kernel = functools.partial(_feistel_kernel, key=key, nbits=nbits,
                               rounds=rounds)
    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=(pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),),
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        interpret=interpret,
    )(x.reshape(rows, LANE))
    return out.reshape(-1)
