"""End-to-end distributed graph generation (the paper's driver routine).

generate(cfg) wires the phases in the paper's order:

    shuffle -> generate edges -> relabel -> redistribute -> build CSR

Each phase is independently jitted so benchmarks can time them separately
(the paper's Fig. 2/4 are per-phase measurements).  The whole pipeline runs
under shard_map on a 1-D mesh whose shards play the paper's "compute nodes".

Device-memory variant here; the true out-of-core variant (host memmap
streaming, the paper's SSD tier) is core/external.py's StreamingGenerator.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..distributed.collectives import flat_mesh
from .csr import CSRShards, build_csr_scatter, build_csr_sorted
from .redistribute import OwnedEdges, redistribute, redistribute_sorted
from .relabel import relabel_alltoall, relabel_recompute, relabel_ring
from .rmat import rmat_edge_block
from .shuffle import distributed_shuffle, shuffle_argsort, shuffle_recompute
from .types import GraphConfig


class GraphResult(NamedTuple):
    pv: jnp.ndarray
    src: jnp.ndarray          # relabeled, pre-redistribute (generation order)
    dst: jnp.ndarray
    owned: OwnedEdges
    csr: CSRShards
    dropped_relabel: jnp.ndarray
    dropped_redistribute: jnp.ndarray


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
def generate_edges(cfg: GraphConfig, mesh: Mesh, axis: str = "shards"):
    """Paper Alg. 5: each shard generates its bin of B*f edges.  The
    counter-based RNG makes every shard's stream independent of nb — the
    same graph is produced at any shard count (tested), which is also what
    makes regeneration-instead-of-checkpoint possible for this phase."""
    eps = cfg.edges_per_shard

    def per_shard(_):
        bid = jax.lax.axis_index(axis)
        start = (bid * eps).astype(jnp.uint32)
        return rmat_edge_block(cfg, start, eps)

    fn = jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P(axis),), out_specs=(P(axis), P(axis))
    )
    return fn(jnp.zeros((mesh.shape[axis],), jnp.int32))


def generate(
    cfg: GraphConfig,
    mesh: Optional[Mesh] = None,
    axis: str = "shards",
    shuffle_variant: str = "paper",        # "paper" | "argsort" | "recompute"
) -> GraphResult:
    """Run the full pipeline.  Returns device arrays (sharded over `axis`)."""
    mesh = mesh if mesh is not None else flat_mesh(cfg.nb, axis)
    assert mesh.shape[axis] == cfg.nb

    # 1. permutation phase
    if shuffle_variant == "paper":
        pv = distributed_shuffle(cfg, mesh, axis)
    elif shuffle_variant == "argsort":
        pv = shuffle_argsort(cfg, mesh, axis)
    elif shuffle_variant == "recompute":
        # Communication-free: the permutation is the keyed Feistel family.
        # pv is materialized only because GraphResult exposes it — the
        # relabel below recomputes labels directly and never reads it.
        pv = shuffle_recompute(cfg, mesh, axis)
    else:
        raise ValueError(shuffle_variant)

    # 2. edge generation phase
    src, dst = generate_edges(cfg, mesh, axis)

    # 3. relabeling phase
    dropped_rel = jnp.zeros((), jnp.int32)
    if shuffle_variant == "recompute":
        # Zero collectives: both endpoints relabel as hash evaluations.
        new_src, new_dst = relabel_recompute(cfg, mesh, src, dst, axis)
    elif cfg.relabel_variant == "ring":
        new_src, new_dst = relabel_ring(cfg, mesh, src, dst, pv, axis)
    elif cfg.relabel_variant == "alltoall":
        new_src, new_dst, dropped_rel = relabel_alltoall(cfg, mesh, src, dst, pv, axis)
    else:
        raise ValueError(cfg.relabel_variant)
    # The raw edges are not part of the result: free them before the
    # redistribute, the phase with the largest footprint.
    del src, dst

    # 4+5. redistribute + CSR
    if cfg.csr_variant == "sorted":
        owned = redistribute_sorted(cfg, mesh, new_src, new_dst, axis)
        csr = build_csr_sorted(cfg, mesh, owned, axis)
    elif cfg.csr_variant == "scatter":
        owned = redistribute(cfg, mesh, new_src, new_dst, axis)
        csr = build_csr_scatter(cfg, mesh, owned, axis)
    else:
        raise ValueError(cfg.csr_variant)

    return GraphResult(pv, new_src, new_dst, owned, csr, dropped_rel, owned.dropped)


# ---------------------------------------------------------------------------
# The memory-resident hash baseline (what the paper is replacing)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",))
def generate_baseline_hash(cfg: GraphConfig):
    """Graph500 'hashing based' kernel: generate, hash-relabel in place, sort,
    CSR — all memory-resident, no permutation vector, no communication.
    The single-node reference for benchmarks/bench_hash_vs_sort.py."""
    from .hashing import hash_relabel

    src, dst = rmat_edge_block(cfg, jnp.uint32(0), cfg.m)
    src, dst = hash_relabel(cfg, src, dst)
    order = jnp.argsort(src)
    src_s, dst_s = src[order], dst[order]
    offv = jnp.searchsorted(src_s, jnp.arange(cfg.n + 1, dtype=src_s.dtype), side="left").astype(jnp.int32)
    return offv, dst_s
