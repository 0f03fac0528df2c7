"""Graph-generation core: single-device pipeline invariants + the
out-of-core (external memory) path vs the device path."""

import numpy as np
import pytest

from repro.core import validate as V
from repro.core.csr import csr_to_host
from repro.core.external import StreamingGenerator
from repro.core.pipeline import generate, generate_baseline_hash
from repro.core.types import GraphConfig

CFG = GraphConfig(scale=10, nb=1, capacity_factor=4.0)


@pytest.fixture(scope="module")
def result():
    return generate(CFG)


def test_permutation_is_bijection(result):
    assert V.check_permutation(result.pv)


def test_no_drops(result):
    assert int(result.dropped_redistribute) == 0
    assert int(result.dropped_relabel) == 0


def test_relabel_multiset(result):
    from repro.core.rmat import rmat_edge_block
    import jax.numpy as jnp

    src, dst = rmat_edge_block(CFG, jnp.uint32(0), CFG.m)
    assert V.check_relabel(src, dst, result.src, result.dst, result.pv)


def test_ownership(result):
    assert V.check_ownership(result.owned.src, result.owned.valid, CFG)


def test_csr_invariants(result):
    checks = V.check_csr(result.csr, result.owned, CFG)
    assert all(checks.values()), checks


def test_debiasing(result):
    """The point of the shuffle (paper §I): raw R-MAT endpoints concentrate
    on small ids; relabeled endpoints are near-uniform."""
    from repro.core.rmat import rmat_edge_block
    import jax.numpy as jnp

    src_raw, dst_raw = rmat_edge_block(CFG, jnp.uint32(0), CFG.m)
    raw = V.endpoint_skew(src_raw, dst_raw, CFG.n)
    rel = V.endpoint_skew(result.src, result.dst, CFG.n)
    assert raw > 0.3            # heavily biased to the low 1/16 of ids
    assert abs(rel - 1 / 16) < 0.02


def test_degree_distribution_heavy_tail(result):
    stats = V.degree_stats(result.csr, CFG)
    assert stats["max_degree"] > 10 * stats["mean_degree"]


def test_variants_agree():
    """sorted-merge CSR (paper §III-B7) == scatter CSR (Alg. 10/11) output."""
    r_sorted = generate(CFG.with_(csr_variant="sorted"))
    r_scatter = generate(CFG.with_(csr_variant="scatter"))
    o1, a1 = csr_to_host(r_sorted.csr, CFG)
    o2, a2 = csr_to_host(r_scatter.csr, CFG)
    np.testing.assert_array_equal(o1, o2)
    # adjacency rows may be permuted within a row; compare per-row multisets
    for r in range(CFG.n):
        np.testing.assert_array_equal(
            np.sort(a1[o1[r]:o1[r + 1]]), np.sort(a2[o2[r]:o2[r + 1]]))


def test_relabel_variants_agree():
    r_ring = generate(CFG.with_(relabel_variant="ring"))
    r_a2a = generate(CFG.with_(relabel_variant="alltoall"))
    np.testing.assert_array_equal(
        V.edge_multiset(r_ring.src, r_ring.dst),
        V.edge_multiset(r_a2a.src, r_a2a.dst))


def test_the_ring_relabel_writes_into_the_raw_edges():
    """relabel_ring takes the raw edges' buffers for its output (they are
    donated), and maps each endpoint v to pv[v] in place."""
    from repro.core.pipeline import generate_edges
    from repro.core.relabel import relabel_ring
    from repro.core.shuffle import distributed_shuffle
    from repro.distributed.collectives import flat_mesh

    mesh = flat_mesh(1)
    pv = distributed_shuffle(CFG, mesh)
    src, dst = generate_edges(CFG, mesh)
    want = [np.asarray(pv)[np.asarray(e)] for e in (src, dst)]
    got = relabel_ring(CFG, mesh, src, dst, pv)
    assert src.is_deleted() and dst.is_deleted()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_baseline_hash_kernel():
    """The memory-resident Graph500 baseline produces a valid CSR with the
    same edge count and de-biased endpoints."""
    offv, adjv = generate_baseline_hash(CFG)
    offv = np.asarray(offv)
    assert offv[-1] == CFG.m
    assert (np.diff(offv) >= 0).all()


def test_external_memory_path_matches_device(tmp_path):
    """The literal out-of-core generator (memmap runs, bounded memory) must
    produce the exact same graph as the device pipeline: same counter RNG,
    same (nb=1) shuffle => same permutation => identical degree vectors."""
    cfg = GraphConfig(scale=9, nb=2, chunk_edges=1 << 10, capacity_factor=4.0)
    pv, csr, ledger = StreamingGenerator(cfg, str(tmp_path)).run()
    dev = generate(cfg.with_(nb=1))

    np.testing.assert_array_equal(np.asarray(pv), np.asarray(dev.pv))
    deg_ext = np.concatenate([np.diff(np.asarray(o)) for o, _ in csr])
    o_dev, a_dev = csr_to_host(dev.csr, cfg.with_(nb=1))
    np.testing.assert_array_equal(deg_ext, np.diff(o_dev))
    # per-row adjacency multisets agree
    a_ext = np.concatenate([a for _, a in csr])
    off = np.concatenate([[0], np.cumsum(deg_ext)])
    for r in range(cfg.n):
        np.testing.assert_array_equal(
            np.sort(a_ext[off[r]:off[r + 1]]),
            np.sort(a_dev[o_dev[r]:o_dev[r + 1]]))
    # and the I/O ledger must show the sorted path doing NO random I/O
    assert ledger.rand_reads == 0
    assert ledger.rand_writes == 0


def test_external_csr_scatter_does_random_io(tmp_path):
    """Alg. 10/11 (scatter CSR) hits random I/O — the measured reason the
    paper's Fig. 2 CSR curve blows up; §III-B7 (sorted) avoids it."""
    cfg = GraphConfig(scale=9, nb=2, chunk_edges=1 << 10, capacity_factor=4.0)
    _, _, ledger = StreamingGenerator(cfg, str(tmp_path)).run(csr_variant="scatter")
    assert ledger.rand_writes > 0
