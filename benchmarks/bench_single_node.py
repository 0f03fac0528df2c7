"""Paper Fig. 2: per-phase time on a single compute node, normalized by
2^(s-16), across scales.  Flat curves = linear scaling in problem size; the
paper's scatter-CSR curve grows super-linearly — ours shows the same on the
scatter variant and stays flat on the sorted variant (§III-B7, which the
paper proposed but did not implement)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.csr import build_csr_scatter, build_csr_sorted
from repro.core.pipeline import generate_edges
from repro.core.redistribute import redistribute, redistribute_sorted
from repro.core.relabel import relabel_ring
from repro.core.shuffle import distributed_shuffle
from repro.core.types import GraphConfig
from repro.distributed.collectives import flat_mesh

from .common import normalized, print_table, save_json, time_fn


def run(scales=(10, 12, 14, 16), base=16):
    mesh = flat_mesh(1)
    rows = []
    for s in scales:
        cfg = GraphConfig(scale=s, nb=1, capacity_factor=3.0)
        t_shuffle = time_fn(lambda: distributed_shuffle(cfg, mesh))
        pv = distributed_shuffle(cfg, mesh)
        t_gen = time_fn(lambda: generate_edges(cfg, mesh))
        src, dst = generate_edges(cfg, mesh)
        t_rel = time_fn(lambda: relabel_ring(cfg, mesh, jnp.copy(src), jnp.copy(dst), pv))
        nsrc, ndst = relabel_ring(cfg, mesh, src, dst, pv)
        t_red_s = time_fn(lambda: redistribute_sorted(cfg, mesh, nsrc, ndst))
        owned_s = redistribute_sorted(cfg, mesh, nsrc, ndst)
        owned_u = redistribute(cfg, mesh, nsrc, ndst)
        t_csr_sorted = time_fn(lambda: build_csr_sorted(cfg, mesh, owned_s))
        t_csr_scatter = time_fn(lambda: build_csr_scatter(cfg, mesh, owned_u))
        rows.append({
            "scale": s,
            "shuffle": normalized(t_shuffle, s, base),
            "edge_gen": normalized(t_gen, s, base),
            "relabel": normalized(t_rel, s, base),
            "redistribute": normalized(t_red_s, s, base),
            "csr_sorted": normalized(t_csr_sorted, s, base),
            "csr_scatter": normalized(t_csr_scatter, s, base),
        })
    print_table("Fig.2: single-node per-phase time / 2^(s-16) [s]",
                rows, ["scale", "shuffle", "edge_gen", "relabel",
                       "redistribute", "csr_sorted", "csr_scatter"])
    save_json("single_node", rows)
    return rows


if __name__ == "__main__":
    run()
