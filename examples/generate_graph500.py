"""Graph500 kernel-1 style run: distributed generation across every local
device, both pipeline variants, plus the literal out-of-core path.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/generate_graph500.py --scale 14
"""

import argparse
import tempfile
import time

import jax

from repro.core import validate as V
from repro.core.external import StreamingGenerator
from repro.core.pipeline import generate, generate_baseline_hash
from repro.core.types import GraphConfig
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=13)
    ap.add_argument("--edge-factor", type=int, default=16)
    args = ap.parse_args()

    use_compile_cache()
    devices = jax.devices()
    print(f"device: platform={devices[0].platform} kind={devices[0].device_kind} "
          f"count={len(devices)}")
    nb = len(devices)
    cfg = GraphConfig(scale=args.scale, edge_factor=args.edge_factor,
                      nb=nb, capacity_factor=4.0)
    print(f"scale={args.scale} -> {cfg.n} vertices, {cfg.m} edges, "
          f"{nb} shards ('compute nodes')")

    # paper pipeline (sorted-merge CSR, the §III-B7 fast path)
    t0 = time.time()
    res = generate(cfg)
    jax.block_until_ready(res.csr.offv)
    t_paper = time.time() - t0
    assert int(res.dropped_redistribute) == 0
    assert V.check_permutation(res.pv)
    print(f"[paper pipeline]   {t_paper:.2f}s  "
          f"(TEPS ~ {cfg.m / t_paper:,.0f})")

    # memory-resident hash baseline (what the paper replaces)
    t0 = time.time()
    offv, adjv = generate_baseline_hash(cfg)
    jax.block_until_ready(offv)
    print(f"[hash baseline]    {time.time() - t0:.2f}s (single shard, "
          f"all-in-memory)")

    # literal out-of-core run (bounded host memory, I/O ledger)
    ext_cfg = cfg.with_(nb=min(nb, 2), scale=min(args.scale, 12))
    with tempfile.TemporaryDirectory() as d:
        t0 = time.time()
        _, _, ledger = StreamingGenerator(ext_cfg, d).run()
        print(f"[out-of-core]      {time.time() - t0:.2f}s at scale "
              f"{ext_cfg.scale}; I/O ledger: {ledger.as_dict()}")
        assert ledger.rand_reads == 0 and ledger.rand_writes == 0, \
            "sorted path must be sequential-only"

    # fully external run: pv itself lives in disk bucket files (Alg. 2-4 on
    # disk); peak resident rows stay O(chunk_edges) regardless of scale
    xcfg = ext_cfg.with_(shuffle_variant="external")
    with tempfile.TemporaryDirectory() as d:
        t0 = time.time()
        gen = StreamingGenerator(xcfg, d)
        gen.run()
        print(f"[external shuffle] {time.time() - t0:.2f}s; peak resident "
              f"rows {gen.gauge.peak_rows} (n = {xcfg.n}); per-phase:")
        for rec in gen.orchestrator.report():
            print(f"    {rec['phase']:>14s}: {rec['seconds']:7.2f}s  "
                  f"seq r/w {rec['seq_reads']}/{rec['seq_writes']}  "
                  f"rand r/w {rec['rand_reads']}/{rec['rand_writes']}")


if __name__ == "__main__":
    main()
