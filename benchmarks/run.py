"""Benchmark harness entry point: `PYTHONPATH=src python -m benchmarks.run`.

One module per paper table/figure (DESIGN.md §6):

  bench_single_node     Fig. 2   per-phase, normalized, single node
  bench_strong_scaling  Fig. 3/4 fixed size, growing shard count
  bench_weak_scaling    Fig. 5   size and shards grow together
  bench_hash_vs_sort    §I       hashing vs chunk-sort microbench
  bench_csr_variants    Fig. 2 CSR + §III-B7  scatter vs sorted (+ I/O ledger)
  bench_external_shuffle §IV-A  external vs device-spill shuffle: peak RSS,
                        per-phase ledger, partitioned-mode wall time
  bench_external_walks  out-of-core walk sampler vs host oracle: hops/s,
                        sequential fraction, peak resident rows
  bench_merge_fanin     cascaded external merge fan-in sweep: pass-count x
                        bytes trade-off, bit-identity asserted per point
  bench_overlap         overlapped (prefetch + write-behind) vs serial I/O
                        on a throttled I/O-bound merge cascade — strict
                        wall-time win gated, sha parity, overlap fraction
  bench_transport       bucket-exchange transport: filesystem {sender}_{seq}
                        runs vs framed TCP (loopback), wall time + wire
                        bytes, bit-identity asserted per point
  bench_jobqueue        multi-tenant job queue: serial vs work-stealing
                        drain of the same job batch on a 2-host cluster —
                        makespan, utilization, overlap factor, parity
  bench_skew            skew-aware shard map: static contiguous ownership
                        vs barrier-time rebalancing of a skewed RMAT on a
                        2-host cluster — hot-host byte share, migrations,
                        per-bucket ledger bytes, parity
  bench_lm              substrate sanity: train/serve throughput
  bench_roofline        deliverable (g): render the dry-run roofline table
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BENCH_DIR = "experiments/bench"


def _bench_json(name: str, payload) -> str:
    """Machine-readable per-bench summary (BENCH_{name}.json) so the perf
    trajectory is diffable PR-over-PR instead of buried in stdout tables."""
    os.makedirs(BENCH_DIR, exist_ok=True)
    path = os.path.join(BENCH_DIR, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    print(f"[saved {path}]")
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-list of bench names")
    ap.add_argument("--fast", action="store_true",
                    help="smaller scales (CI mode)")
    args = ap.parse_args()

    from repro.core.trace import GLOBAL as METRICS, run_metadata
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from . import (bench_csr_variants, bench_external_shuffle,
                   bench_external_walks, bench_hash_vs_sort, bench_jobqueue,
                   bench_lm, bench_merge_fanin, bench_overlap,
                   bench_roofline, bench_single_node, bench_skew,
                   bench_strong_scaling, bench_transport,
                   bench_weak_scaling)

    benches = {
        "single_node": lambda: bench_single_node.run(
            scales=(10, 12) if args.fast else (10, 12, 14, 16)),
        "strong_scaling": lambda: bench_strong_scaling.run(
            scales=(12,) if args.fast else (12, 14),
            shard_counts=(1, 2, 4) if args.fast else (1, 2, 4, 8)),
        "weak_scaling": lambda: bench_weak_scaling.run(
            steps=3 if args.fast else 4),
        "hash_vs_sort": lambda: bench_hash_vs_sort.run(
            log_n=20 if args.fast else 22),
        "csr_variants": lambda: bench_csr_variants.run(
            scales=(10, 12) if args.fast else (10, 12, 14)),
        "external_shuffle": lambda: bench_external_shuffle.run(
            scales=(10, 12) if args.fast else (10, 12, 14),
            worker_counts=(0, 2) if args.fast else (0, 2, 4)),
        "merge_fanin": lambda: bench_merge_fanin.run(
            nruns=128 if args.fast else 512,
            run_rows=512 if args.fast else 2048,
            fanins=(0, 4, 16) if args.fast else (0, 4, 8, 16, 64, 256)),
        # no reduced fast variant: the throttled I/O toll already keeps the
        # point to a few seconds, and shrinking it further would let thread
        # handoff noise into the strict serial-vs-overlap wall-time gate.
        "overlap": lambda: bench_overlap.run(
            nruns=8, run_rows=16384, max_fanin=4),
        "transport": lambda: bench_transport.run(
            scales=(9, 10) if args.fast else (10, 12),
            walkers=32 if args.fast else 64,
            length=6 if args.fast else 8),
        # no reduced fast variant: below this batch size the per-job work
        # is so small that cross-job barrier interleaving costs more than
        # the idle time it fills and the overlap factor dips under 1.0 —
        # a fast point would benchmark the scheduler's floor, not its win.
        "jobqueue": lambda: bench_jobqueue.run(
            scale=9, walkers=32, length=6),
        # one point, no fast variant: the byte-balance gate needs enough
        # skewed bytes for a strict-improvement migration to exist, and
        # scale 10 already runs in CI time.
        "skew": lambda: bench_skew.run(
            scale=10, walkers=64, length=6),
        "external_walks": lambda: bench_external_walks.run(
            scales=(9, 10) if args.fast else (10, 12, 14),
            walkers=64 if args.fast else 256,
            length=8 if args.fast else 16),
        "lm": bench_lm.run,
        "roofline": bench_roofline.run,
    }
    chosen = [s for s in args.only.split(",") if s] or list(benches)

    failed, summary = [], []
    for name in chosen:
        print(f"\n######## {name} ########")
        t0 = time.time()
        # Per-bench metrics isolation: the process-wide registry accumulates
        # whatever drivers ran; clearing here scopes `combined()` to THIS
        # bench's phases.  The snapshot (trace.unified_snapshot schema) rides
        # in every BENCH json under "metrics"; diff.py ignores the subtree.
        METRICS.clear()
        try:
            result = benches[name]()
            secs = time.time() - t0
            print(f"[{name} done in {secs:.1f}s]")
            entry = {"bench": name, "ok": True,
                     "wall_seconds": round(secs, 3), "fast": args.fast,
                     "metrics": METRICS.combined()}
            try:
                json.dumps(result, default=str)
                entry["result"] = result
            except TypeError:
                entry["result"] = None
            _bench_json(name, entry)
            summary.append({k: entry[k] for k in
                            ("bench", "ok", "wall_seconds", "fast")})
        except Exception:
            traceback.print_exc()
            secs = time.time() - t0
            _bench_json(name, {"bench": name, "ok": False,
                               "wall_seconds": round(secs, 3),
                               "fast": args.fast,
                               "metrics": METRICS.combined()})
            summary.append({"bench": name, "ok": False,
                            "wall_seconds": round(secs, 3), "fast": args.fast})
            failed.append(name)
    _bench_json("summary", {"benches": summary, "failed": failed,
                            "meta": run_metadata()})
    if failed:
        print(f"\nFAILED: {failed}")
        sys.exit(1)
    print("\nAll benchmarks completed.")


if __name__ == "__main__":
    main()
