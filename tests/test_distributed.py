"""Multi-shard behaviour on 8 fake CPU devices.

XLA locks the device count at first jax init, so these run in SUBPROCESSES
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the conftest/pytest
process itself must keep seeing 1 device per the assignment).
"""

import os
import subprocess
import sys

import pytest

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH="src")


def run_py(body: str, timeout=600):
    r = subprocess.run([sys.executable, "-c", body], env=ENV, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_pipeline_8_shards_full_validation():
    out = run_py("""
import numpy as np, jax.numpy as jnp
from repro.core.types import GraphConfig
from repro.core.pipeline import generate
from repro.core import validate as V
from repro.core.rmat import rmat_edge_block

cfg = GraphConfig(scale=12, nb=8, capacity_factor=4.0)
res = generate(cfg)
assert int(res.dropped_redistribute) == 0
assert V.check_permutation(res.pv)
src, dst = rmat_edge_block(cfg, jnp.uint32(0), cfg.m)
assert V.check_relabel(src, dst, res.src, res.dst, res.pv)
assert V.check_ownership(res.owned.src, res.owned.valid, cfg)
checks = V.check_csr(res.csr, res.owned, cfg)
assert all(checks.values()), checks
print("OK8")
""")
    assert "OK8" in out


def test_shard_count_invariance():
    """The SAME graph comes out at nb=1, 2, 8 (counter RNG + deterministic
    shuffle make the pipeline topology-independent) — the property that lets
    an elastic restart regenerate data on a different cluster size."""
    out = run_py("""
import numpy as np
from repro.core.types import GraphConfig
from repro.core.pipeline import generate
from repro.core.csr import csr_to_host
from repro.core import validate as V

degs = []
for nb in (1, 2, 8):
    cfg = GraphConfig(scale=10, nb=nb, capacity_factor=6.0)
    res = generate(cfg)
    assert int(res.dropped_redistribute) == 0, nb
    # relabeled edge multiset is the invariant (pv depends on nb rounds)
    degs.append(np.sort(np.asarray(V.edge_multiset(res.src, res.dst))))
# pv differs per nb (different shuffle round structure) but every variant
# must be a valid de-biased graph with identical degree STATISTICS profile;
# exact-multiset equality holds between runs with the same nb:
res2 = generate(GraphConfig(scale=10, nb=8, capacity_factor=6.0))
np.testing.assert_array_equal(
    degs[2], np.sort(np.asarray(V.edge_multiset(res2.src, res2.dst))))
print("OKINV")
""")
    assert "OKINV" in out


@pytest.mark.parametrize("nb", [1, 2, 4])
def test_sorted_redistribute_matches_the_general_bucketing(nb):
    """redistribute_sorted buckets its sorted records by their runs; the
    general bucketing of the same sorted records, under the same shard_map
    and merge, gives the same OwnedEdges bit for bit, drops included."""
    out = run_py(f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.types import GraphConfig
from repro.core.pipeline import generate
from repro.core.redistribute import _default_capacity, merge_received, redistribute_sorted
from repro.distributed.collectives import capacity_all_to_all, flat_mesh

nb = {nb}
cfg = GraphConfig(scale=10, nb=nb, capacity_factor=4.0)
mesh = flat_mesh(nb)
res = generate(cfg, mesh)

def general(cap):
    def per_shard(src_l, dst_l):
        order = jnp.argsort(src_l)
        src_s, dst_s = src_l[order], dst_l[order]
        pair = jnp.stack([src_s, dst_s], axis=-1)
        ex = capacity_all_to_all(pair, src_s // cfg.bucket_size, axis="shards",
                                 capacity=cap, dest_sorted=False)
        return (*merge_received(ex, cfg.n), ex.peak_load)
    return jax.jit(jax.shard_map(per_shard, mesh=mesh, in_specs=(P("shards"), P("shards")),
                                 out_specs=(P("shards"), P("shards"), P("shards"), P(), P())))

# the phase's own capacity (lossless), then one that drops
for cap in (_default_capacity(cfg, nb), cfg.edges_per_shard // nb // 2):
    got = redistribute_sorted(cfg, mesh, res.src, res.dst, capacity=cap)
    want = general(cap)(res.src, res.dst)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (cap, g.shape, w.shape)
        np.testing.assert_array_equal(g, w)
    print(cap, int(got.dropped))
assert int(got.dropped) > 0
print("OKRUNS")
""")
    assert "OKRUNS" in out


def test_the_four_way_merge_is_a_stable_sort_of_the_received_runs():
    """At nb = 4, merge_received's four arrays equal, bit for bit, a NumPy
    stable sort of the runs each shard received, empty slots keyed n: at the
    phase's capacity and at one that drops, so empty slots are covered."""
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.types import GraphConfig
from repro.core.pipeline import generate
from repro.core.redistribute import _default_capacity, merge_received
from repro.distributed.collectives import capacity_all_to_all, flat_mesh

nb = 4
cfg = GraphConfig(scale=10, nb=nb, capacity_factor=4.0)
mesh = flat_mesh(nb)
res = generate(cfg, mesh)

def received_and_merged(cap):
    def per_shard(src_l, dst_l):
        order = jnp.argsort(src_l)
        src_s, dst_s = src_l[order], dst_l[order]
        pair = jnp.stack([src_s, dst_s], axis=-1)
        ex = capacity_all_to_all(pair, src_s // cfg.bucket_size, axis="shards",
                                 capacity=cap, dest_sorted=True)
        return (ex.data, ex.valid, *merge_received(ex, cfg.n))
    spec = P("shards")
    return jax.jit(jax.shard_map(per_shard, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=(spec, spec, spec, spec, spec, P())))

for cap in (_default_capacity(cfg, nb), cfg.edges_per_shard // nb // 2):
    data, valid, msrc, mdst, mvalid, dropped = received_and_merged(cap)(res.src, res.dst)
    data, valid = np.asarray(data).reshape(nb, -1, 2), np.asarray(valid).reshape(nb, -1)
    got = [np.asarray(a).reshape(nb, -1) for a in (msrc, mdst, mvalid)]
    for s in range(nb):
        keys = np.where(valid[s], data[s, :, 0], cfg.n)
        order = np.argsort(keys, kind="stable")
        want = (np.where(keys < cfg.n, keys, 0)[order], data[s, :, 1][order], valid[s][order])
        for g, w in zip(got, want):
            assert g[s].dtype == w.dtype, (g.dtype, w.dtype)
            np.testing.assert_array_equal(g[s], w)
    owner = np.asarray(res.src).reshape(nb, -1) // cfg.bucket_size
    lost = sum(max(int((owner[s] == d).sum()) - cap, 0) for s in range(nb) for d in range(nb))
    assert int(dropped) == lost, (cap, int(dropped), lost)
    print(cap, int(dropped), int((~valid).sum()))
    assert int(dropped) > 0 or not valid.all()  # lossless: empty slots to check
assert int(dropped) > 0
print("OKMERGE")
""")
    assert "OKMERGE" in out


@pytest.mark.parametrize("nb", [1, 4])
def test_the_exchange_counts_its_fullest_pair(nb):
    """capacity_all_to_all's peak_load is the most records one shard sent to
    one destination, counted in numpy, for both bucketings, whether the
    capacity holds them all or drops some."""
    out = run_py(f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import capacity_all_to_all, flat_mesh

nb, N = {nb}, 512
mesh = flat_mesh(nb)
rng = np.random.default_rng(7)
# skewed: each shard sends most of its records to the next one
dest = np.where(rng.random((nb, N)) < 0.6, (np.arange(nb)[:, None] + 1) % nb,
                rng.integers(0, nb, (nb, N))).astype(np.int32)
data = rng.integers(0, 1 << 30, (nb, N)).astype(np.int32)
pairs = np.array([[(dest[s] == d).sum() for d in range(nb)] for s in range(nb)])
want = int(pairs.max())

def exchange(cap, dest_sorted):
    def per_shard(x, d):
        if dest_sorted:
            order = jnp.argsort(d, stable=True)
            x, d = x[order], d[order]
        ex = capacity_all_to_all(x, d, axis="shards", capacity=cap, dest_sorted=dest_sorted)
        return ex.dropped, ex.peak_load
    return jax.jit(jax.shard_map(per_shard, mesh=mesh, in_specs=(P("shards"), P("shards")),
                                 out_specs=(P(), P())))

for dest_sorted in (False, True):
    for cap in (want + 8, want, want - 1, N // (2 * nb)):
        dropped, peak = exchange(cap, dest_sorted)(data.reshape(-1), dest.reshape(-1))
        lost = int(np.maximum(pairs - cap, 0).sum())
        assert int(peak) == want, (dest_sorted, cap, int(peak), want)
        assert int(dropped) == lost and (lost > 0) == (want > cap), (dest_sorted, cap, lost)
print("OKPEAK", want)
""")
    assert "OKPEAK" in out


def test_the_graph_reports_its_redistribute_load():
    """GraphResult.redistribute_peak_load is the most relabeled edges one
    shard sends to one owner, at most the capacity wherever nothing was
    dropped, and above it where the exchange drops."""
    out = run_py("""
import numpy as np
from repro.core.types import GraphConfig
from repro.core.pipeline import generate
from repro.core.redistribute import _default_capacity, redistribute_sorted
from repro.distributed.collectives import flat_mesh

for nb, factor in ((1, 1.0), (4, 1.1), (4, 4.0)):
    cfg = GraphConfig(scale=10, nb=nb, capacity_factor=factor)
    mesh = flat_mesh(nb)
    res = generate(cfg, mesh)
    load, cap = int(res.redistribute_peak_load), res.redistribute_capacity
    assert cap == _default_capacity(cfg, nb)
    owner = np.asarray(res.src).reshape(nb, -1) // cfg.bucket_size
    want = max(int((owner[s] == d).sum()) for s in range(nb) for d in range(nb))
    assert load == want, (nb, factor, load, want)
    if int(res.dropped_redistribute) == 0:
        assert load <= cap, (nb, factor, load, cap)
    print(nb, factor, load, cap)
small = redistribute_sorted(cfg, mesh, res.src, res.dst, capacity=want // 2)
assert int(small.dropped) > 0 and int(small.peak_load) == want
print("OKLOAD")
""")
    assert "OKLOAD" in out


def test_distributed_walks_match_host_oracle():
    out = run_py("""
import numpy as np
from repro.core.types import GraphConfig
from repro.core.pipeline import generate
from repro.core.csr import csr_to_host
from repro.data.walks import distributed_walks, host_walks, start_vertex
from repro.distributed.collectives import flat_mesh

cfg = GraphConfig(scale=10, nb=8, capacity_factor=4.0)
mesh = flat_mesh(8)
res = generate(cfg, mesh)
offv, adjv = csr_to_host(res.csr, cfg)
W = 16
hist, valid, wid, dropped = distributed_walks(
    cfg, mesh, res.csr.offv, res.csr.adjv,
    length=12, seed=7, walkers_per_shard=W, capacity_factor=8.0)
hist, valid, wid = map(np.asarray, (hist, valid, wid))
assert int(dropped) == 0, int(dropped)
live = valid & (wid >= 0)
assert live.sum() == 8 * W
starts = start_vertex(7, wid[live].astype(np.uint32), cfg.bucket_size,
                      (wid[live] // W) * cfg.bucket_size)
ref = host_walks(offv, adjv, starts, 12, 7, n=cfg.n, walker_ids=wid[live])
np.testing.assert_array_equal(hist[live], ref)
print("OKWALK")
""")
    assert "OKWALK" in out


def test_moe_alltoall_matches_dense_dispatch():
    """EP all_to_all dispatch == dense dispatch (same routing, same experts)
    on a (2 data x 4 model) mesh."""
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.configs.base import get_smoke_config
from repro.models.registry import init_all, get_model
from repro.models.nn import DistContext
from repro.distributed.sharding import make_dist

cfg = get_smoke_config('qwen3-moe-235b-a22b').with_(num_layers=2)
api = get_model(cfg)
params, f = init_all(cfg)
mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ('data', 'model'))
B, S = 4, 8
tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (B, S)), jnp.int32)
batch = {'tokens': tokens}

logits_dense, aux_d = api.forward(cfg, params, batch, None)
dist = make_dist(cfg, mesh, None, fsdp=False, moe_dispatch='alltoall')
logits_a2a, aux_a = api.forward(cfg, params, batch, dist)
assert float(aux_a['dropped']) == 0.0, float(aux_a['dropped'])
np.testing.assert_allclose(np.asarray(logits_dense, np.float32),
                           np.asarray(logits_a2a, np.float32), atol=3e-2, rtol=3e-2)
print("OKMOE")
""")
    assert "OKMOE" in out


def test_external_shuffle_parity_8_shards():
    """The disk-resident external shuffle (paper Alg. 2-4 on disk) is
    bit-identical to the device shuffle on an 8-shard mesh, and the full
    external pipeline reproduces the device pipeline's graph."""
    out = run_py("""
import tempfile
import numpy as np
from repro.core.types import GraphConfig
from repro.core.external import StreamingGenerator
from repro.core.pipeline import generate
from repro.core.shuffle import distributed_shuffle
from repro.distributed.collectives import flat_mesh

cfg = GraphConfig(scale=10, nb=8, chunk_edges=128, edge_factor=4,
                  capacity_factor=6.0, shuffle_variant="external")
with tempfile.TemporaryDirectory() as d:
    gen = StreamingGenerator(cfg, d)
    pv_ext, csr_ext, ledger = gen.run()
    pv_ext = np.asarray(pv_ext).copy()
    deg_ext = np.concatenate([np.diff(o) for o, _ in csr_ext])
    adj_rows = [np.sort(np.asarray(a[o[r]:o[r+1]]))
                for o, a in csr_ext for r in range(len(o) - 1)]
pv_dev = np.asarray(distributed_shuffle(cfg, flat_mesh(8)))
np.testing.assert_array_equal(pv_ext, pv_dev)
res = generate(cfg)
from repro.core.csr import csr_to_host
o_dev, a_dev = csr_to_host(res.csr, cfg)
np.testing.assert_array_equal(deg_ext, np.diff(o_dev))
for r in range(cfg.n):
    np.testing.assert_array_equal(adj_rows[r], np.sort(a_dev[o_dev[r]:o_dev[r+1]]))
assert ledger.rand_reads == 0 == ledger.rand_writes
print("OKEXT")
""")
    assert "OKEXT" in out


def test_podwise_int8_psum():
    """Cross-pod compressed gradient reduction ~= exact mean."""
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.train.compression import podwise_psum_int8

mesh = Mesh(np.asarray(jax.devices()).reshape(8), ('pod',))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)

def per_pod(gl):
    return podwise_psum_int8({'w': gl[0]}, 'pod')['w']

out = shard_map(per_pod, mesh=mesh, in_specs=P('pod'), out_specs=P('pod'))(g)
got = np.asarray(out).reshape(8, -1)
want = np.asarray(g).mean(0)
for i in range(8):
    np.testing.assert_allclose(got[i], want, atol=2e-2)
print("OKPSUM")
""")
    assert "OKPSUM" in out


def test_shard_map_axis_size_pcast_compose():
    """jax.shard_map, lax.axis_size and lax.pcast used directly (no compat
    layer) compose inside a one-device shard_map: axis_size folds to the
    mesh axis length, and pcast marks an axis-invariant value as varying
    (what distributed_walks does to its scan carry)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("i",))

    def body(x):
        ones = lax.pcast(jnp.ones(x.shape, x.dtype), "i", to="varying")
        return x + ones * lax.axis_size("i")

    y = jax.shard_map(body, mesh=mesh, in_specs=P("i"), out_specs=P("i"))(
        jnp.zeros(4, jnp.int32))
    np.testing.assert_array_equal(np.asarray(y), np.ones(4, np.int32))
