"""Vectorized R-MAT edge generation (paper Alg. 5 / gen_rmat_edge).

The sequential kernel draws one edge at a time by descending `scale` levels of
the adjacency-matrix quadtree.  Our adaptation vectorizes the level walk over
a whole block of edges (the paper's per-core bin of b*f edges) and replaces
the stateful RNG with a *counter-based* hash RNG so that

  * every edge is generated independently from (seed, edge_index, level,
    field) — no sequential RNG state, perfectly parallel across shards,
    cores, and Pallas grid steps;
  * the Pallas TPU kernel (kernels/rmat.py) and this jnp reference produce
    bit-identical streams (tests assert exact equality);
  * regeneration is deterministic: edge i can be re-derived at any time,
    which is what makes checkpoint-free restart of the *generation* phase
    possible (fault tolerance for the data pipeline).

All arithmetic is uint32: thresholds are integer cut points on the 2**32
lattice (core/types.quadrant_thresholds).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import GraphConfig, quadrant_thresholds

# splitmix32-style avalanche constants (Stafford / murmur3 finalizer family).
# numpy scalars, not jnp: a jnp constant here would start a JAX backend at
# import, and the disk tier's worker processes import this package without
# doing any JAX work (one process per chip).
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_GOLDEN = 0x9E3779B9


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """Full-avalanche 32-bit mixer (murmur3 finalizer variant).

    Bijective on uint32, so distinct counters never collide.
    """
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def counter_uniform_u32(seed: int, index: jnp.ndarray, stream: int) -> jnp.ndarray:
    """One uint32 uniform per counter: h(seed, stream, index).

    `stream` enumerates (level, field) pairs; `index` is the global edge id.
    """
    s = jnp.uint32((seed ^ (stream * _GOLDEN)) & 0xFFFFFFFF)
    return mix32(mix32(jnp.asarray(index, jnp.uint32) + s) ^ s)


@partial(jax.jit, static_argnames=("cfg", "count"))
def rmat_edge_block(cfg: GraphConfig, start: jnp.ndarray, count: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Generate `count` R-MAT edges with global ids [start, start+count).

    Returns (src, dst), each int32 of shape (count,).  This is the pure-jnp
    reference; kernels/rmat.py is the Pallas TPU version of the same math.
    """
    t_src, t_dst0, t_dst1 = quadrant_thresholds(cfg)
    idx = jnp.asarray(start, jnp.uint32) + jnp.arange(count, dtype=jnp.uint32)
    src = jnp.zeros((count,), jnp.uint32)
    dst = jnp.zeros((count,), jnp.uint32)
    for level in range(cfg.scale):
        r1 = counter_uniform_u32(cfg.seed, idx, 2 * level)
        r2 = counter_uniform_u32(cfg.seed, idx, 2 * level + 1)
        src_bit = r1 < jnp.uint32(t_src)          # P = c + d  (t < 2**32 since c+d < 1)
        # dst threshold depends on the src bit (conditional quadrant probs)
        t_d = jnp.where(src_bit, jnp.uint32(t_dst1), jnp.uint32(t_dst0))
        dst_bit = r2 < t_d
        src = (src << 1) | src_bit.astype(jnp.uint32)
        dst = (dst << 1) | dst_bit.astype(jnp.uint32)
    return src.astype(cfg.vertex_dtype), dst.astype(cfg.vertex_dtype)


def degree_bias_stat(src: jnp.ndarray, dst: jnp.ndarray, n: int) -> float:
    """Fraction of edge endpoints landing in the lowest n/16 vertex ids.

    R-MAT with (a,b,c,d)=(.57,.19,.19,.05) concentrates mass on small ids —
    the 'bias' the paper de-biases via shuffling (§I).  Used by tests to
    verify (i) raw R-MAT output IS biased and (ii) relabeled output is NOT.
    """
    lo = n // 16
    cnt = jnp.sum(src < lo) + jnp.sum(dst < lo)
    return float(cnt) / float(2 * src.shape[0])
