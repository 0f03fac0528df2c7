"""Redistribute relabeled edges to their owners (paper Alg. 8-9, §III-B5).

An edge is owned by the shard whose range partition contains its (relabeled)
source.  The paper's implementation is the 1:1 scatter-gather: bucket edges
into per-destination packets, ship packets when full, collector appends.
Here that is exactly one `capacity_all_to_all` call.

Because the sources have been relabeled through a *uniform* permutation, the
per-destination counts concentrate tightly around m_local/nb (this is why the
paper relabels *before* redistributing!) — a modest capacity factor absorbs
the binomial fluctuation plus residual high-degree-vertex skew (the paper's
§IV-C weak-scaling observation).  Overflow is counted and surfaced.

Two variants, mirroring the paper:
  redistribute            unordered (paper's implemented version, §III-B5)
  redistribute_sorted     §III-B7: senders pre-sort by new source, so each
                          packet is one contiguous run of sorted records;
                          the receiver merges the nb sorted runs by one
                          stable sort of their concatenation => its edges
                          arrive globally sorted by source and the
                          CSR build degenerates to the trivial Alg. 1.
                          (The paper proposes but does NOT implement this
                          variant; we implement both and benchmark the gap.)

Disk-tier twin's I/O overlap (cfg.io_overlap): the external redistribute
(phases.redistribute_bucket, external.StreamingGenerator.redistribute)
streams its partition scan through a prefetch thread and ships owner runs
write-behind through the Transport (blockstore.PrefetchReader /
WriteBehindWriter); this module's all_to_all is device-side and has no
disk I/O to overlap.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..distributed.collectives import ExchangeResult, capacity_all_to_all, merge_sorted_runs
from .types import GraphConfig


class OwnedEdges(NamedTuple):
    """Per-shard owned edge set, fixed capacity with validity mask.

    src/dst: [nb_shards, capacity] on each shard (global: [nb*nb, cap]);
    rows are per-sender packets.  Whether the flattened per-shard view is
    globally sorted by src is a property of which redistribute variant
    produced it (§III-B7 => sorted), not a runtime flag — jit traces bools.
    peak_load is the exchange's most records from one sender to one owner:
    the exchange was lossless iff it is at most the capacity.
    """

    src: jnp.ndarray
    dst: jnp.ndarray
    valid: jnp.ndarray
    dropped: jnp.ndarray
    peak_load: jnp.ndarray


def _default_capacity(cfg: GraphConfig, nb: int) -> int:
    # Rounded up to whole (8, 128) int32 tiles: an unaligned capacity costs
    # the TPU compile of the exchange time and temporary memory.
    cap = int(cfg.capacity_factor * cfg.edges_per_shard / max(nb, 1)) + 8
    return -(-cap // 1024) * 1024


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis", "capacity"))
def redistribute(
    cfg: GraphConfig,
    mesh: Mesh,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    axis: str = "shards",
    capacity: int = 0,
) -> OwnedEdges:
    """Unordered redistribute (paper Alg. 8-9)."""
    nb = mesh.shape[axis]
    B = cfg.bucket_size
    cap = capacity or _default_capacity(cfg, nb)

    def per_shard(src_l, dst_l):
        with jax.named_scope("place"):
            pair = jnp.stack([src_l, dst_l], axis=-1)      # [N, 2]
            owner = src_l // B
        ex = capacity_all_to_all(pair, owner, axis=axis, capacity=cap)
        with jax.named_scope("place"):
            return ex.data[..., 0], ex.data[..., 1], ex.valid, ex.dropped, ex.peak_load

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(), P()),
    )
    with jax.named_scope("redistribute"):
        return OwnedEdges(*fn(src, dst))


def merge_received(ex: ExchangeResult, n: int):
    """Receive side of redistribute_sorted: merge of the nb sorted packets
    that arrived, empty slots keyed past every vertex (`n`) so they sink to
    the end.  Returns (src, dst, valid, dropped) in the [nb, cap] layout,
    whose flattened view is sorted by src."""
    nb, cap = ex.valid.shape
    with jax.named_scope("merge"):
        rs, rd = ex.data[..., 0], ex.data[..., 1]
        keys = jnp.where(ex.valid, rs, jnp.asarray(n, rs.dtype))
        mkeys, mdst = merge_sorted_runs(keys, rd)
        mvalid = mkeys < n                                 # every valid source is below n
        msrc = jnp.where(mvalid, mkeys, 0)
        return (
            msrc.reshape(nb, cap),
            mdst.reshape(nb, cap),
            mvalid.reshape(nb, cap),
            ex.dropped,
        )


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis", "capacity"))
def redistribute_sorted(
    cfg: GraphConfig,
    mesh: Mesh,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    axis: str = "shards",
    capacity: int = 0,
) -> OwnedEdges:
    """Sorted-merge redistribute (paper §III-B7, proposed-not-implemented).

    Sort locally by (new) src; each packet is then one contiguous run of
    the sorted records, so it is sorted too; receiver merges its nb sorted
    runs (invalid slots are key-maxed so they sink to the end).  Output
    flattened arrays are globally sorted by src.
    """
    nb = mesh.shape[axis]
    B = cfg.bucket_size
    cap = capacity or _default_capacity(cfg, nb)

    def per_shard(src_l, dst_l):
        with jax.named_scope("sort"):
            order = jnp.argsort(src_l)                     # send-side sort
        with jax.named_scope("permute"):
            src_s, dst_s = src_l[order], dst_l[order]
            pair = jnp.stack([src_s, dst_s], axis=-1)
        with jax.named_scope("place"):
            owner = src_s // B
        # sorted by src, so the owners are too: each bucket is one run
        ex = capacity_all_to_all(pair, owner, axis=axis, capacity=cap, dest_sorted=True)
        return (*merge_received(ex, cfg.n), ex.peak_load)

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(), P()),
    )
    with jax.named_scope("redistribute"):
        return OwnedEdges(*fn(src, dst))
