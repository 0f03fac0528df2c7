"""Build the CSR representation (paper Alg. 1, 10, 11 and §III-B7).

Each shard owns vertices [bid*B, (bid+1)*B) and all edges whose relabeled
source is in that range (post-redistribute).  CSR per shard:

  offv: [B+1] offsets into adjv  (global vertex `v` -> local row `v - bid*B`)
  adjv: [cap_m] destination ids, valid prefix per row given by offv

Two variants, matching the paper:

  build_csr_scatter   adapts Alg. 10/11.  The paper increments an in-memory
      associative map and flushes with atomic CAS.  TPUs have no useful
      scatter-atomics, so the *insight-faithful* adaptation is: degree via
      scatter-add (XLA serializes deterministically), offsets via exclusive
      scan, and adjacency placement via offv[src] + within-source rank.  The
      rank needs a sort anyway — which is precisely the paper's observation
      that unordered CSR construction is the scaling bottleneck (Fig. 2's
      super-linear CSR curve).  The *measured* random-I/O blowup is
      reproduced on the host/external path (external.py + benchmarks), where
      scatter really does hit memmap pages randomly.

  build_csr_sorted    Alg. 1 on §III-B7 output: edges arrive sorted by src,
      so offsets are a searchsorted and adjv is the dst column verbatim —
      O(m) sequential access, the paper's predicted fix.  This is the
      default (csr_variant="sorted").
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .redistribute import OwnedEdges
from .types import GraphConfig


class CSRShards(NamedTuple):
    """Distributed CSR: shard i owns rows [i*B, (i+1)*B)."""

    offv: jnp.ndarray    # global [nb*(B+1)]  (per-shard [B+1])
    adjv: jnp.ndarray    # global [nb*cap_m]  (per-shard [cap_m], valid prefix)
    num_edges: jnp.ndarray  # global [nb] edges owned per shard


def _degrees(src_local: jnp.ndarray, valid: jnp.ndarray, base: jnp.ndarray, B: int) -> jnp.ndarray:
    """Alg. 10 adapted: masked scatter-add into the local degree vector."""
    rows = jnp.clip(src_local - base, 0, B - 1)
    return jnp.zeros((B,), jnp.int32).at[rows].add(valid.astype(jnp.int32))


def _offsets(degv: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(degv, dtype=jnp.int32)])


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
def build_csr_scatter(cfg: GraphConfig, mesh: Mesh, owned: OwnedEdges, axis: str = "shards") -> CSRShards:
    """Unordered-input CSR (paper Alg. 10/11 adapted to sort-rank placement)."""
    B = cfg.bucket_size

    def per_shard(src, dst, valid):
        with jax.named_scope("place"):
            bid = lax.axis_index(axis)
            base = bid * B
            s, d, v = src.reshape(-1), dst.reshape(-1), valid.reshape(-1)
            degv = _degrees(s, v, base, B)
            offv = _offsets(degv)
        # adjacency: position = offv[row] + within-row rank.  After a stable
        # sort by row key (invalid -> B, sinks to the end) the sorted order
        # IS that placement: edge i of the sorted stream lands at adjv[i].
        # This sort is exactly the cost the paper's Fig. 2 charges to the
        # unordered CSR variant; §III-B7 (build_csr_sorted) avoids it.
        with jax.named_scope("sort"):
            rows = jnp.where(v, jnp.clip(s - base, 0, B - 1), B)
            order = jnp.argsort(rows, stable=True)          # the hidden sort
        with jax.named_scope("permute"):
            cnt = jnp.sum(v.astype(jnp.int32))
            adjv = jnp.where(jnp.arange(order.shape[0]) < cnt, d[order], 0)
        return offv, adjv, cnt[None]

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)),
    )
    with jax.named_scope("csr"):
        offv, adjv, cnt = fn(owned.src, owned.dst, owned.valid)
    return CSRShards(offv, adjv, cnt)


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
def build_csr_sorted(cfg: GraphConfig, mesh: Mesh, owned: OwnedEdges, axis: str = "shards") -> CSRShards:
    """Sorted-input CSR (paper Alg. 1 / §III-B7 fast path): offsets by
    searchsorted, adjacency verbatim.  Input must be redistribute_sorted
    output (flattened per-shard arrays sorted by src)."""
    B = cfg.bucket_size

    def per_shard(src, dst, valid):
        with jax.named_scope("search"):
            bid = lax.axis_index(axis)
            base = bid * B
        s, d, v = src.reshape(-1), dst.reshape(-1), valid.reshape(-1)
        with jax.named_scope("place"):
            cnt = jnp.sum(v.astype(jnp.int32))
        with jax.named_scope("search"):
            # rows sorted ascending over the valid prefix (invalid sorted to
            # end by redistribute_sorted's sentinel keys).
            keyed = jnp.where(v, s - base, B)
            offv_full = jnp.searchsorted(keyed, jnp.arange(B + 1, dtype=keyed.dtype), side="left")
            offv = offv_full.astype(jnp.int32)
        with jax.named_scope("place"):
            adjv = jnp.where(jnp.arange(d.shape[0]) < cnt, d, 0)
        return offv, adjv, cnt[None]

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)),
    )
    with jax.named_scope("csr"):
        offv, adjv, cnt = fn(owned.src, owned.dst, owned.valid)
    return CSRShards(offv, adjv, cnt)


def csr_to_host(csr: CSRShards, cfg: GraphConfig):
    """Assemble the distributed CSR into one host (offv [n+1], adjv [m]) pair.

    Per-shard offsets are local; rebase and concatenate the valid prefixes.
    Used by the host random-walk sampler (data/) and validation.
    """
    import numpy as np

    B = cfg.bucket_size
    nb = cfg.nb
    offv_s = np.asarray(csr.offv).reshape(nb, B + 1)
    cap_m = csr.adjv.shape[0] // nb
    adjv_s = np.asarray(csr.adjv).reshape(nb, cap_m)
    cnt = np.asarray(csr.num_edges)
    parts = [adjv_s[i, : cnt[i]] for i in range(nb)]
    base = np.concatenate([[0], np.cumsum(cnt)]).astype(np.int64)
    offv = np.concatenate(
        [offv_s[i, :-1].astype(np.int64) + base[i] for i in range(nb)]
        + [[base[-1]]]
    )
    return offv, np.concatenate(parts) if parts else np.zeros((0,), np.int32)


def csr_neighbors(csr: CSRShards, cfg: GraphConfig, v: int):
    """Host-side convenience: adjacency list of global vertex v (for tests
    and the random-walk sampler)."""
    B = cfg.bucket_size
    shard = v // B
    row = v - shard * B
    offv = csr.offv.reshape(cfg.nb, B + 1)[shard]
    cap_m = csr.adjv.shape[0] // cfg.nb
    adjv = csr.adjv.reshape(cfg.nb, cap_m)[shard]
    return adjv[offv[row]:offv[row + 1]]
