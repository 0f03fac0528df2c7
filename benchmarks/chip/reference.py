"""Plain reference of what the timed path produces, and the comparison.

Written from the generator's specification, not from its code: nothing here
imports the program or takes an array it made.  Every function is plain
`jax.numpy` over whole arrays, with the seeds as traced operands, so one
compiled reference serves every seed of a cell.

The specification it follows (`GraphSpec` fixes the sizes):

* R-MAT (Graph500 Kronecker, quadrants a/b/c/d): edge i, level l draws two
  uint32 counters u(seed, i, 2l) and u(seed, i, 2l+1) with
  u(seed, i, s) = mix32(mix32(i + k) ^ k), k = seed ^ (s * GOLDEN) mod 2^32,
  and descends one quadrant per level by integer cut points on the 2^32
  lattice.  Edge i is the i-th edge of the graph at any shard count.
* Paper shuffle (Alg. 2-4): shard b starts with [b*B, (b+1)*B); each of
  `rounds` rounds sorts every shard's buffer by mix32(value ^ salt_r),
  salt_r = mix32(seed + r * GOLDEN), then shard j receives slice j of every
  shard, in shard order.  pv is the buffers read in shard order.
* Recompute shuffle: pv[x] is a 4-round unbalanced Feistel bijection over
  mix32 keyed by seed ^ 0xFE157E11 (n a power of two: no cycle walk).
* The graph is {(pv[src_i], pv[dst_i])}, duplicates and self-loops kept.
  CSR rows hold their edges in edge-index order.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

GOLDEN = 0x9E3779B9
_M1 = 0x7FEB352D
_M2 = 0x846CA68B
_MASK = 0xFFFFFFFF
_FEISTEL_STREAM = 0xFE157E11


@dataclasses.dataclass(frozen=True)
class GraphSpec:
    """Sizes and variant of one generated graph, as the configuration states."""

    scale: int
    edge_factor: int
    a: float
    b: float
    c: float
    d: float
    nb: int
    shuffle: str = "paper"          # "paper" | "recompute"
    feistel_rounds: int = 4

    @property
    def n(self) -> int:
        return 1 << self.scale

    @property
    def m(self) -> int:
        return self.n * self.edge_factor

    @property
    def bucket(self) -> int:
        return self.n // self.nb

    @property
    def rounds(self) -> int:
        """log_nb(n) shuffle rounds (paper Alg. 4 line 8); one at nb=1."""
        if self.nb <= 1:
            return 1
        return max(1, int(math.ceil(math.log(self.n) / math.log(self.nb))))


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def mix32(x):
    x = _u32(x)
    x = x ^ (x >> 16)
    x = x * _u32(_M1)
    x = x ^ (x >> 15)
    x = x * _u32(_M2)
    return x ^ (x >> 16)


def _cut(p: float):
    return _u32(int(p * float(1 << 32)))


def rmat_edges(spec: GraphSpec, seed):
    """(src, dst) int32 of all m edges, in edge-index order."""
    idx = jnp.arange(spec.m, dtype=jnp.uint32)
    t_src = _cut(spec.c + spec.d)
    t_dst0 = _cut(spec.b / (spec.a + spec.b))
    t_dst1 = _cut(spec.d / (spec.c + spec.d))
    src = jnp.zeros((spec.m,), jnp.uint32)
    dst = jnp.zeros((spec.m,), jnp.uint32)
    for level in range(spec.scale):
        k1 = seed ^ _u32((2 * level * GOLDEN) & _MASK)
        k2 = seed ^ _u32(((2 * level + 1) * GOLDEN) & _MASK)
        r1 = mix32(mix32(idx + k1) ^ k1)
        r2 = mix32(mix32(idx + k2) ^ k2)
        s_bit = r1 < t_src
        d_bit = r2 < jnp.where(s_bit, t_dst1, t_dst0)
        src = (src << 1) | s_bit.astype(jnp.uint32)
        dst = (dst << 1) | d_bit.astype(jnp.uint32)
    return src.astype(jnp.int32), dst.astype(jnp.int32)


def paper_permutation(spec: GraphSpec, seed):
    nb, B = spec.nb, spec.bucket
    buf = jnp.arange(spec.n, dtype=jnp.int32).reshape(nb, B)
    for r in range(spec.rounds):
        salt = mix32(seed + _u32(r) * _u32(GOLDEN))
        order = jnp.argsort(mix32(buf.astype(jnp.uint32) ^ salt), axis=1)
        buf = jnp.take_along_axis(buf, order, axis=1)
        if nb > 1:
            buf = buf.reshape(nb, nb, B // nb).transpose(1, 0, 2).reshape(nb, B)
    return buf.reshape(spec.n)


def feistel_permutation(spec: GraphSpec, seed):
    nbits = max(1, (spec.n - 1).bit_length())
    if nbits > 32:
        raise ValueError(f"Feistel reference holds ids of up to 32 bits, not {nbits}")
    key = seed ^ _u32(_FEISTEL_STREAM)
    lo = nbits // 2
    x = jnp.arange(spec.n, dtype=jnp.uint32)
    left, right = x >> lo, x & _u32((1 << lo) - 1)
    w_left, w_right = nbits - lo, lo
    for i in range(spec.feistel_rounds):
        rk = mix32(key + _u32(((i + 1) * GOLDEN) & _MASK))
        f = mix32(right ^ rk)
        left, right = right, (left ^ f) & _u32((1 << w_left) - 1)
        w_left, w_right = w_right, w_left
    return ((left << lo) | right).astype(jnp.int32)


def permutation(spec: GraphSpec, seed):
    if spec.shuffle == "paper":
        return paper_permutation(spec, seed)
    if spec.shuffle == "recompute":
        return feistel_permutation(spec, seed)
    raise ValueError(f"no reference for shuffle {spec.shuffle!r}")


def graph(spec: GraphSpec, seed):
    """pv and the relabeled edges (src, dst), in edge-index order."""
    pv = permutation(spec, seed)
    src, dst = rmat_edges(spec, seed)
    return pv, pv[src], pv[dst]


def _count(x):
    return jnp.sum(x.astype(jnp.int32))


# ---------------------------------------------------------------------------
# generation: pv and every CSR row as a multiset
# ---------------------------------------------------------------------------


def csr_pairs(spec: GraphSpec, offv, adjv, num_edges):
    """The program's CSR as (degrees [n], rows [nb*cap], cols [nb*cap],
    edge count); each shard s holds rows [s*B, (s+1)*B) with local offsets
    and a valid prefix of num_edges[s] entries.  Unused slots hold n."""
    nb, B, n = spec.nb, spec.bucket, spec.n
    offv = offv.reshape(nb, B + 1)
    adjv = adjv.reshape(nb, -1)
    cap = adjv.shape[1]
    pos = jnp.arange(cap, dtype=jnp.int32)

    def rows_of(o):
        # row of slot j = how many rows end at or before j
        ends = jnp.zeros((cap + 1,), jnp.int32).at[jnp.clip(o[1:], 0, cap)].add(1)
        return jnp.cumsum(ends)[:cap]

    local = jax.vmap(rows_of)(offv)
    base = (jnp.arange(nb, dtype=jnp.int32) * B)[:, None]
    used = pos[None, :] < num_edges.reshape(nb, 1)
    rows = jnp.where(used, base + local, n).reshape(-1)
    cols = jnp.where(used, adjv, n).reshape(-1)
    degrees = jnp.diff(offv, axis=1).reshape(-1)
    return degrees, rows, cols, jnp.sum(num_edges)


@partial(jax.jit, static_argnames=("spec",))
def compare_graph(spec: GraphSpec, seed, pv_prog, offv, adjv, num_edges):
    """Counts of disagreement between the program's graph and the reference.

    pv: entries that differ.  degree: rows whose degree differs.
    adjacency: positions at which the sorted (row, neighbour) lists differ,
    plus every edge beyond m, so each row is compared as a multiset."""
    pv, src, dst = graph(spec, seed)
    ref_rows, ref_cols = lax.sort((src, dst), num_keys=2)
    ref_deg = jnp.zeros((spec.n,), jnp.int32).at[src].add(1)
    deg, rows, cols, total = csr_pairs(spec, offv, adjv, num_edges)
    rows, cols = lax.sort((rows, cols), num_keys=2)
    m = spec.m
    if rows.shape[0] < m:  # fewer slots than edges: the missing ones differ
        short = m - rows.shape[0]
        rows = jnp.concatenate([rows, jnp.full((short,), spec.n, rows.dtype)])
        cols = jnp.concatenate([cols, jnp.full((short,), spec.n, cols.dtype)])
    differ = (rows[:m] != ref_rows) | (cols[:m] != ref_cols)
    return {
        "pv_mismatch": _count(pv_prog != pv),
        "degree_mismatch": _count(deg != ref_deg),
        "adjacency_mismatch": _count(differ) + jnp.maximum(total - m, 0),
    }

