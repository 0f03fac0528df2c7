"""Plain reference of the walk corpus, and the comparison.

Written from the walk contract, not from the program's code: nothing here
imports the program or takes an array it made.  The graph is
`reference.graph`'s, laid out as a CSR by a stable sort on the relabeled
source, so each row holds its edges in edge-index order.  Seeds are traced
operands, so one compiled reference serves every seed of a cell.

The contract (`WalkSpec` fixes the sizes), with
r(s, w, t) = mix32(mix32(w ^ s) + t * GOLDEN) over uint32:

* walker w belongs to shard b = w // walkers_per_shard and starts at
  b * B + r(ws ^ 0xA5A5, w, 0) % B;
* at hop t = 1 .. length a walker at vertex v of degree d > 0 moves to
  entry r(ws, w, t) % d of row v, and a walker at a sink to r(ws, w, t) % n;
* its history is the start followed by the vertex after each hop.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

import reference as ref

START_SALT = 0xA5A5


@dataclasses.dataclass(frozen=True)
class WalkSpec:
    """Sizes of one walk call, as the configuration and traffic state them."""

    graph: ref.GraphSpec
    walkers_per_shard: int
    length: int

    @property
    def walkers(self) -> int:
        return self.graph.nb * self.walkers_per_shard

    @property
    def hops(self) -> int:
        return self.walkers * self.length


def walk_rand(seed, walker, step):
    """r(seed, walker, step) as uint32."""
    return ref.mix32(ref.mix32(walker.astype(jnp.uint32) ^ seed)
                     + jnp.uint32(step) * jnp.uint32(ref.GOLDEN))


def csr(spec: ref.GraphSpec, seed):
    """(offsets [n+1], adjacency [m]) of the reference graph, each row in
    edge-index order."""
    _, src, dst = ref.graph(spec, seed)
    rows, adj = lax.sort((src, dst), num_keys=1, is_stable=True)
    counts = jnp.zeros((spec.n,), jnp.int32).at[rows].add(1)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]), adj


def walks(spec: WalkSpec, offsets, adj, walk_seed):
    """Histories [walkers, length + 1] int32, row w the walk of walker w."""
    g = spec.graph
    w = jnp.arange(spec.walkers, dtype=jnp.uint32)
    shard = (w // jnp.uint32(spec.walkers_per_shard)).astype(jnp.int32)
    start = shard * g.bucket + (walk_rand(walk_seed ^ jnp.uint32(START_SALT), w, 0)
                                % jnp.uint32(g.bucket)).astype(jnp.int32)

    def hop(pos, t):
        r = walk_rand(walk_seed, w, t)
        lo = offsets[pos]
        deg = offsets[pos + 1] - lo
        entry = lo + (r % jnp.maximum(deg, 1).astype(jnp.uint32)).astype(jnp.int32)
        nxt = jnp.where(deg > 0, adj[jnp.minimum(entry, adj.shape[0] - 1)],
                        (r % jnp.uint32(g.n)).astype(jnp.int32))
        return nxt, nxt

    _, steps = lax.scan(hop, start, jnp.arange(1, spec.length + 1, dtype=jnp.uint32))
    return jnp.concatenate([start[:, None], steps.T], axis=1)


def _count(x):
    return jnp.sum(x.astype(jnp.int32))


@partial(jax.jit, static_argnames=("spec",))
def compare_walks(spec: WalkSpec, graph_seed, walk_seed, hist, valid, wid):
    """Counts of disagreement between the program's walks and the reference.

    The program returns rows of (history, valid, walker id) in any order.
    walk_mismatch: reference walkers with a valid row whose history differs
    anywhere, or with more than one valid row, plus valid rows whose id no
    reference walker has.  walkers_missing: reference walkers with no valid
    row."""
    want = walks(spec, *csr(spec.graph, graph_seed), walk_seed)
    n_walkers = spec.walkers
    wid = wid.astype(jnp.int32)
    known = valid & (wid >= 0) & (wid < n_walkers)
    at = jnp.where(known, wid, 0)
    differs = known & jnp.any(hist != want[at], axis=1)
    hits = jnp.zeros((n_walkers,), jnp.int32).at[at].add(known.astype(jnp.int32))
    bad = jnp.zeros((n_walkers,), jnp.int32).at[at].add(differs.astype(jnp.int32))
    return {
        "walk_mismatch": _count((bad > 0) | (hits > 1)) + _count(valid & ~known),
        "walkers_missing": _count(hits == 0),
    }
