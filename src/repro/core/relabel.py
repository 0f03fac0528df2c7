"""Relabel edges through the permutation vector (paper Alg. 6-7).

This is the step that captures the paper's central idea: a *hash-style*
relabel touches pv at random positions (random I/O); the paper instead
chunk-sorts the edges by endpoint and streams the permutation ranges past
them one at a time, doing a sort-merge-join — every access sequential.

TPU adaptation (ring variant, paper-faithful):
  * edges sorted locally by the field being relabeled (the chunk sort);
  * the pv ranges do not sit on disk on a remote node — they sit in the HBM
    of remote shards.  The paper's `permute_server` pull becomes a static
    ring schedule: in round r, shard `bid` holds the pv chunk of shard
    `(bid + r) mod nb` (one `ppermute` per round).  nb rounds stream the
    whole vector past every shard with O(B) resident memory — the exact
    analogue of the paper's bounded-buffer streaming;
  * the merge-join inside a round is a masked monotone gather: edges are
    sorted, the pv chunk is contiguous, so `pv_chunk[field - base]` is a
    sequential-access gather (kernels/relabel.py tiles it through VMEM).

Communication-free variant (`relabel_recompute`, Funke et al.): when the
permutation is the keyed Feistel family (cfg.perm_family="feistel"), pv[u]
is a pure hash of u — so the relabel is an ELEMENTWISE map u -> perm(u)
with no pv operand, no sorting, and no collectives at all.  The exchange
bytes of both ring and all_to_all variants become per-element hash
evaluations; this is the device twin of the disk tier's
shuffle_variant="recompute" fast path.

Disk-tier twin's I/O overlap (cfg.io_overlap): the external relabel kernels
(phases.relabel_*_bucket, external.StreamingGenerator.relabel) prefetch
their merge-cursor refills and complete their emitted runs write-behind
(blockstore.PrefetchReader / WriteBehindWriter), hiding the sort-merge-join
pass's disk time behind the lookup compute — this module is pure device
compute with no disk I/O, so the flag has nothing to overlap here.

Optimized variant (`relabel_alltoall`): ship each endpoint to its owner
(capacity_all_to_all), gather, ship back.  One round trip instead of nb
rounds — but the destinations are *raw R-MAT ids*, whose ownership is
heavily skewed toward shard 0 (P(top bits all zero) ~ (a+b)^log2(nb)), so the
fixed capacity must be ~nb^0.4x uniform.  DESIGN.md quantifies why the
paper's ring is the robust choice under skew; the all_to_all variant is the
fast path at small nb / high capacity.  (Post-relabel, ids are uniform and
the same primitive is cheap — that's redistribute.py.)
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..distributed.collectives import capacity_all_to_all, return_all_to_all, ring_shift
from .types import GraphConfig


def _relabel_field_ring(field: jnp.ndarray, pv_local: jnp.ndarray, *, bid, nb: int, B: int, axis: str):
    """Relabel one endpoint field via the ring-streamed merge-join.

    field: [N] local endpoint values (any order; sorting is an optimization
           handled by the caller/kernel, correctness does not require it).
    pv_local: [B] this shard's pv chunk.
    """
    with jax.named_scope("sort"):
        sort_idx = jnp.argsort(field)        # paper: chunk-sort by endpoint
    with jax.named_scope("permute"):
        sorted_field = field[sort_idx]
    with jax.named_scope("lookup"):
        out_sorted = jnp.zeros_like(sorted_field)

    def round_body(r, carry):
        pv_chunk, out = carry
        with jax.named_scope("lookup"):
            chunk_owner = (bid + r) % nb
            base = chunk_owner * B
            local = sorted_field - base
            in_range = (local >= 0) & (local < B)
            idx = jnp.clip(local, 0, B - 1)
            gathered = pv_chunk[idx]          # monotone gather (edges sorted)
            out = jnp.where(in_range, gathered, out)
        pv_chunk = ring_shift(pv_chunk, axis) if nb > 1 else pv_chunk
        return pv_chunk, out

    _, out_sorted = lax.fori_loop(0, nb, round_body, (pv_local, out_sorted))
    with jax.named_scope("permute"):
        # scatter back to generation order
        return jnp.zeros_like(field).at[sort_idx].set(out_sorted)


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"), donate_argnames=("src", "dst"))
def relabel_ring(
    cfg: GraphConfig,
    mesh: Mesh,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    pv: jnp.ndarray,
    axis: str = "shards",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paper-faithful relabel: dst pass then src pass (paper relabels the
    destination field first, then the source field — Alg. 7 runs twice).

    Consumes the raw edges: `src` and `dst` are donated, and the relabelled
    fields are written into their buffers.  The host dispatches every phase
    before the first has run, so edges still held would sit beside the
    redistribute's and the CSR's outputs.  A caller that needs the raw edges
    again passes copies."""
    nb = mesh.shape[axis]
    B = cfg.bucket_size

    def per_shard(src_l, dst_l, pv_l):
        with jax.named_scope("lookup"):
            bid = lax.axis_index(axis)
        new_dst = _relabel_field_ring(dst_l, pv_l, bid=bid, nb=nb, B=B, axis=axis)
        new_src = _relabel_field_ring(src_l, pv_l, bid=bid, nb=nb, B=B, axis=axis)
        return new_src, new_dst

    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
    )
    with jax.named_scope("relabel"):
        return fn(src, dst, pv)


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
def relabel_recompute(
    cfg: GraphConfig,
    mesh: Mesh,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    axis: str = "shards",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Communication-free relabel: (perm(src), perm(dst)) elementwise.

    Takes NO pv operand — the permutation is recomputed from cfg.seed via
    the keyed Feistel family (shuffle.graph_perm), so there is nothing to
    stream, ring-shift, or exchange.  `mesh`/`axis` are accepted for
    signature symmetry with the other variants and unused: the map is
    embarrassingly shard-local.  Bit-identical to relabel_ring against
    pv = shuffle_recompute(cfg, ...) (tested)."""
    from .shuffle import graph_perm

    del mesh, axis  # no collectives: the whole point
    with jax.named_scope("relabel"), jax.named_scope("lookup"):
        return (graph_perm(cfg.seed, src, cfg.n, rounds=cfg.feistel_rounds),
                graph_perm(cfg.seed, dst, cfg.n, rounds=cfg.feistel_rounds))


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis", "capacity"))
def relabel_alltoall(
    cfg: GraphConfig,
    mesh: Mesh,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    pv: jnp.ndarray,
    axis: str = "shards",
    capacity: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Optimized relabel: one bucketed round trip per *both* fields at once.

    Returns (new_src, new_dst, dropped).  dropped > 0 means the capacity
    factor was too small for the R-MAT ownership skew — callers must treat
    that as a hard error (a mislabeled edge is corruption, not load shedding).
    """
    nb = mesh.shape[axis]
    B = cfg.bucket_size
    if capacity == 0:
        per_shard_q = 2 * (cfg.edges_per_shard)
        capacity = int(cfg.capacity_factor * per_shard_q / max(nb, 1)) + 8

    def per_shard(src_l, dst_l, pv_l):
        with jax.named_scope("place"):
            q = jnp.concatenate([src_l, dst_l])        # both fields, one trip
            owner = q // B
        ex = capacity_all_to_all(q, owner, axis=axis, capacity=capacity)
        with jax.named_scope("lookup"):
            base = lax.axis_index(axis) * B
            local = jnp.clip(ex.data - base, 0, B - 1)
            answered = jnp.where(ex.valid, pv_l[local], 0)
        back = return_all_to_all(answered, ex.position, axis=axis)
        with jax.named_scope("place"):
            new_src, new_dst = jnp.split(back, 2)
        return new_src, new_dst, ex.dropped

    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P()),
    )
    with jax.named_scope("relabel"):
        return fn(src, dst, pv)
