"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX and compiles for a described chip,
so these tests catch what CPU runs and interpret-mode kernels cannot: a
program or kernel the chip's compiler refuses, and a program too large for
one chip's HBM.  Nothing runs.  Shapes are those of chip_smoke.py's
one-chip real scale (Graph500 R-MAT, scale 24, nb=1).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.  Keep every such compile in this one file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.types import GraphConfig

SCALE = 24
HBM_BUDGET = 14 << 30  # what one 16 GiB v5e chip is allowed to hold


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh1(topo):
    return Mesh(np.asarray(topo.devices[:1]), ("shards",))


def _total_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_generate_edges_compiles_at_smoke_scale(mesh1):
    from repro.core.pipeline import generate_edges

    cfg = GraphConfig(scale=SCALE, nb=1)
    compiled = generate_edges.lower(cfg, mesh1).compile()
    assert _total_bytes(compiled) < HBM_BUDGET
    # (src, dst) int32, plus a few hundred bytes of tuple bookkeeping
    assert 0 <= compiled.memory_analysis().output_size_in_bytes - 8 * cfg.m < 4096


def test_distributed_shuffle_compiles_at_smoke_scale(mesh1):
    from repro.core.shuffle import distributed_shuffle

    cfg = GraphConfig(scale=SCALE, nb=1)
    compiled = distributed_shuffle.lower(cfg, mesh1).compile()
    assert _total_bytes(compiled) < HBM_BUDGET


def _shape(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def test_rmat_kernel_compiles(one_chip):
    from repro.kernels.rmat import rmat_edges_pallas

    cfg = GraphConfig(scale=SCALE)
    fn = jax.jit(lambda: rmat_edges_pallas(cfg, 0, cfg.m, interpret=False),
                 out_shardings=one_chip)
    assert "tpu_custom_call" in fn.lower().compile().as_text()


def test_feistel_kernel_compiles(one_chip):
    from repro.kernels.rmat import feistel_perm_pallas

    cfg = GraphConfig(scale=SCALE)
    compiled = feistel_perm_pallas.lower(
        _shape((cfg.n,), one_chip), 0x1234, SCALE, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [1, 4])
def test_bucket_hist_kernel_compiles(one_chip, k):
    from repro.kernels.bucket import bucket_hist_pallas

    cfg = GraphConfig(scale=SCALE)
    compiled = bucket_hist_pallas.lower(
        _shape((cfg.m,), one_chip), k, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="TPU compiler: 'Only 2D gather is supported' (the jnp.take "
                          "on the resident pv chunk); the kernel is not on the main path")
def test_relabel_gather_kernel_compiles(one_chip):
    from repro.kernels.relabel_gather import relabel_gather_pallas

    cfg = GraphConfig(scale=SCALE, nb=4)
    relabel_gather_pallas.lower(
        _shape((cfg.edges_per_shard,), one_chip), _shape((cfg.bucket_size,), one_chip),
        _shape((), one_chip), interpret=False).compile()


def test_sorted_redistribute_buckets_without_a_scatter_at_cell_scale(mesh1):
    """redistribute_sorted at the benchmark cell's shape (Graph500 scale 22,
    nb=1, capacity_factor 1.0): the exchange slices the sorted records' runs,
    so no scatter lies under `exchange`, and the program holds no more than
    with the general bucketing: argument + output + temp 2,483,419,648 B
    (536,870,912 + 805,319,680 + 1,141,229,056) there, read from the same
    compile of the program that bucketed by a sort and a slot scatter."""
    import re

    from repro.core.redistribute import redistribute_sorted

    cfg = GraphConfig(scale=22, nb=1, capacity_factor=1.0)
    edges = jax.ShapeDtypeStruct((cfg.m,), jnp.int32, sharding=NamedSharding(mesh1, P("shards")))
    compiled = redistribute_sorted.lower(cfg, mesh1, edges, edges).compile()
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) <= 2_483_419_648
    scatters = [line for line in compiled.as_text().splitlines()
                if re.search(r" scatter\(", line) and "/exchange/" in line]
    assert scatters == []
