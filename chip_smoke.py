"""Smoke run of the device generation pipeline on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # nb=4 over four chips, cross-chip path only

Default mode (one chip, nb=1):
  1. names the device and stops with an error unless it is a TPU;
  2. checks the device pipeline against independent host code at
     VALIDATION_SCALE: the paper shuffle's pv, degrees and every CSR row
     against the disk tier's StreamingGenerator (external shuffle), the
     recompute shuffle's pv against hostgen.graph_perm_np, and
     distributed_walks against host_walks;
  3. runs `generate()` at REAL_SCALE, the largest Graph500 scale one v5e
     chip holds, and checks on the device that nothing was dropped, that pv
     is a permutation and that the CSR holds all m edges; then walks
     WALKERS walkers of WALK_LENGTH hops over that CSR.

--four-chip runs only what exists across chips (all_to_all, ppermute,
capacity_all_to_all): the full validate.* checks at VALIDATION_SCALE on
nb=4, recompute's nb=4 graph against its nb=1 graph, and Graph500 "toy"
(scale 26, paper shuffle) once, with the same device-side checks and walks.
Per-phase device times are the benchmark's (benchmarks/chip/).

Every check raises on failure.  The seconds, rates and bytes printed are
smoke readings of one run, not benchmark numbers.  The last line of
standard output is {"ok": true, "device": {...}}, printed only when every
phase passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.core import validate as V  # noqa: E402
from repro.core.csr import csr_to_host  # noqa: E402
from repro.core.external import StreamingGenerator  # noqa: E402
from repro.core.hostgen import graph_perm_np  # noqa: E402
from repro.core.pipeline import generate, generate_edges  # noqa: E402
from repro.core.types import GraphConfig  # noqa: E402
from repro.data.walks import distributed_walks, host_walks, start_vertex  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

# The host oracle (StreamingGenerator) takes about a minute at scale 18.
VALIDATION_SCALE = 18
# Largest scale whose every phase, compiled for one v5e chip, stays under
# 14 GiB with what generate() holds across phases (redistribute_sorted:
# 9.25 GiB at capacity_factor 1.0).  Graph500 "toy" (26) does not fit.
REAL_SCALE = 24
# Graph500 "toy" over four chips (redistribute_sorted: 12.28 GiB per chip at
# capacity_factor 1.1).
FOUR_CHIP_SCALE = 26
WALKERS = 1 << 16
WALK_LENGTH = 80  # node2vec's walk length
WALK_SEED = 7

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s: dict = {}


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        name = kw.get("fun_name", "?")
        _compile_s[name] = _compile_s.get(name, 0.0) + duration


def say(*parts) -> None:
    print(*parts, flush=True)


def require(ok, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")
    say(f"  ok: {what}")


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def report_compiles(label: str) -> None:
    for name, secs in sorted(_compile_s.items()):
        if secs >= 0.1:
            say(f"  compile_s[{label}] {name}: {secs!r}")
    _compile_s.clear()


def mesh_of(devices) -> Mesh:
    return Mesh(np.asarray(devices), ("shards",))


def rows_multiset(offv: np.ndarray, adjv: np.ndarray) -> np.ndarray:
    """(row, neighbour) pairs, sorted: equal iff every row is the same
    multiset of neighbours."""
    rows = np.repeat(np.arange(offv.shape[0] - 1), np.diff(offv))
    return V.edge_multiset(rows, np.asarray(adjv)[: offv[-1]])


def check_on_device(res, cfg: GraphConfig) -> None:
    """Step-3 invariants, reduced on the device; only scalars come back."""
    require(int(res.dropped_redistribute) == 0, "dropped_redistribute == 0")
    pv_sorted = jnp.sort(res.pv)
    require(bool(jnp.array_equal(pv_sorted, jnp.arange(cfg.n, dtype=pv_sorted.dtype))),
            "pv is a permutation (sorted pv == arange, on device)")
    require(int(jnp.sum(res.csr.num_edges)) == cfg.m, f"sum(num_edges) == m == {cfg.m}")
    offv = res.csr.offv.reshape(cfg.nb, cfg.bucket_size + 1)
    monotone = jnp.all(jnp.diff(offv, axis=1) >= 0)
    ends = jnp.array_equal(offv[:, -1], res.csr.num_edges)
    require(bool(monotone & ends), "offsets monotone, last offset == num_edges")


def run_walks(cfg: GraphConfig, mesh: Mesh, csr, walkers: int, capacity_factor: float):
    out, secs = timed(distributed_walks, cfg, mesh, csr.offv, csr.adjv,
                      length=WALK_LENGTH, seed=WALK_SEED,
                      walkers_per_shard=walkers // cfg.nb,
                      capacity_factor=capacity_factor)
    hist, valid, wid, dropped = out
    require(int(dropped) == 0, "walks: dropped == 0")
    live = int(jnp.sum(valid & (wid >= 0)))
    require(live == walkers, f"walks: {walkers} walkers alive")
    return out, secs


def check_walks_against_host(cfg: GraphConfig, hist, valid, wid, csr, walkers_per_shard: int) -> None:
    offv, adjv = csr_to_host(csr, cfg)
    hist, valid, wid = map(np.asarray, (hist, valid, wid))
    live = valid & (wid >= 0)
    starts = start_vertex(WALK_SEED, wid[live].astype(np.uint32), cfg.bucket_size,
                          (wid[live] // walkers_per_shard) * cfg.bucket_size)
    ref = host_walks(offv, adjv, starts, WALK_LENGTH, WALK_SEED, n=cfg.n,
                     walker_ids=wid[live])
    require(np.array_equal(hist[live], ref), "distributed_walks == host_walks")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def validate_one_chip(mesh: Mesh) -> None:
    cfg = GraphConfig(scale=VALIDATION_SCALE, nb=1, capacity_factor=1.0)
    say(f"[validate] scale {cfg.scale}: n={cfg.n} m={cfg.m}, nb=1")
    res = generate(cfg, mesh)
    require(int(res.dropped_redistribute) == 0, "paper: dropped_redistribute == 0")
    pv_dev = np.asarray(res.pv)
    o_dev, a_dev = csr_to_host(res.csr, cfg)

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as d:
        pv_host, csr_host, _ = StreamingGenerator(cfg.with_(shuffle_variant="external"), d).run()
        pv_host = np.asarray(pv_host).copy()
        o_host, a_host = (np.asarray(x).copy() for x in csr_host[0])
    say(f"  host StreamingGenerator seconds: {time.perf_counter() - t0!r}")
    require(np.array_equal(pv_dev, pv_host), "paper pv == StreamingGenerator(external) pv")
    require(np.array_equal(np.diff(o_dev), np.diff(o_host)), "degree vectors equal")
    require(np.array_equal(rows_multiset(o_dev, a_dev), rows_multiset(o_host, a_host)),
            "every CSR row equal as a sorted multiset")

    rc = generate(cfg, mesh, shuffle_variant="recompute")
    want = graph_perm_np(cfg.seed, np.arange(cfg.n), cfg.n, rounds=cfg.feistel_rounds)
    require(np.array_equal(np.asarray(rc.pv, np.int64), want), "recompute pv == hostgen.graph_perm_np")
    del rc

    walkers = 4096
    (hist, valid, wid, _), _ = run_walks(cfg, mesh, res.csr, walkers, capacity_factor=1.0)
    check_walks_against_host(cfg, hist, valid, wid, res.csr, walkers)
    report_compiles("validate")


def real_scale(cfg: GraphConfig, mesh: Mesh, walk_capacity: float,
               variants=("paper", "recompute")) -> None:
    say(f"[real] scale {cfg.scale}: n={cfg.n} m={cfg.m}, nb={cfg.nb}, "
        f"capacity_factor={cfg.capacity_factor}")
    for variant in variants:
        res, cold = timed(generate, cfg, mesh, shuffle_variant=variant)
        say(f"  {variant}: first generate() seconds (compile included): {cold!r}")
        report_compiles(variant)
        check_on_device(res, cfg)
        if variant == "paper":
            cold_w = run_walks(cfg, mesh, res.csr, WALKERS, walk_capacity)[1]
            report_compiles("walks")
            steady_w = run_walks(cfg, mesh, res.csr, WALKERS, walk_capacity)[1]
            say(f"  walks: {WALKERS} walkers x {WALK_LENGTH} hops: first seconds {cold_w!r}, "
                f"steady seconds {steady_w!r}, hops/s {WALKERS * WALK_LENGTH / steady_w!r}")
        # Drop the result before the next generate(): two graphs of this
        # size do not fit the chip together.
        del res


def report_peak(mesh: Mesh) -> None:
    for dev in mesh.devices.flat:
        stats = dev.memory_stats() or {}
        say(f"  peak_bytes_in_use (device {dev.id}): "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def validate_four_chip(mesh4: Mesh, mesh1: Mesh) -> None:
    cfg = GraphConfig(scale=VALIDATION_SCALE, nb=4)
    say(f"[validate x4] scale {cfg.scale}: n={cfg.n} m={cfg.m}, nb=4")
    res = generate(cfg, mesh4)
    require(int(res.dropped_redistribute) == 0, "paper: dropped_redistribute == 0")
    src, dst = generate_edges(cfg, mesh4)
    require(V.check_permutation(res.pv), "validate.check_permutation")
    require(V.check_relabel(src, dst, res.src, res.dst, res.pv), "validate.check_relabel")
    require(V.check_ownership(res.owned.src, res.owned.valid, cfg), "validate.check_ownership")
    checks = V.check_csr(res.csr, res.owned, cfg)
    require(all(checks.values()), f"validate.check_csr {checks}")
    del src, dst

    walkers = 4096
    (hist, valid, wid, _), _ = run_walks(cfg, mesh4, res.csr, walkers, capacity_factor=4.0)
    check_walks_against_host(cfg, hist, valid, wid, res.csr, walkers // cfg.nb)
    del res

    r4 = generate(cfg, mesh4, shuffle_variant="recompute")
    cfg1 = cfg.with_(nb=1)
    r1 = generate(cfg1, mesh1, shuffle_variant="recompute")
    require(int(r4.dropped_redistribute) == 0 == int(r1.dropped_redistribute),
            "recompute: no drops at nb=4 or nb=1")
    require(np.array_equal(np.asarray(r4.pv), np.asarray(r1.pv)), "recompute pv: nb=4 == nb=1")
    require(np.array_equal(rows_multiset(*csr_to_host(r4.csr, cfg)),
                           rows_multiset(*csr_to_host(r1.csr, cfg1))),
            "recompute edge multiset: nb=4 == nb=1 (on device 0)")
    report_compiles("validate x4")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="nb=4 over four chips: the cross-chip path only")
    args = ap.parse_args(argv)

    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    say(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    if dev.platform != "tpu":
        say("no TPU: this smoke run measures the chip and has no CPU fallback")
        return 2

    if args.four_chip:
        if len(devices) < 4:
            say(f"--four-chip needs 4 devices, found {len(devices)}")
            return 2
        mesh = mesh_of(devices[:4])
        validate_four_chip(mesh, mesh_of(devices[:1]))
        # recompute's cross-chip graph is checked at VALIDATION_SCALE; at
        # full size only the paper path: one scale-26 generate() takes
        # about ten minutes on four v5e chips.
        real_scale(GraphConfig(scale=FOUR_CHIP_SCALE, nb=4, capacity_factor=1.1),
                   mesh, walk_capacity=4.0, variants=("paper",))
    else:
        mesh = mesh_of(devices[:1])
        validate_one_chip(mesh)
        # nb=1: the capacity _default_capacity gives at factor 1.0 is >= m,
        # so the exchange is lossless; 2.0 does not fit the chip at scale 24.
        cfg = GraphConfig(scale=REAL_SCALE, nb=1, capacity_factor=1.0)
        real_scale(cfg, mesh, walk_capacity=1.0)
    report_peak(mesh)

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
