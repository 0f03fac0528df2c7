"""The control of each traffic kind: the cell run with one guarantee of its
configuration broken, which the comparison has to fail.

    gen     lossy-exchange: the program's own exchange sized 10 % under
            lossless (capacity_factor 0.9): edges are dropped, where the
            configuration states that every edge is kept.
            one-shot-shuffle: the program's one-shot shuffle
            (shuffle_argsort) in place of the paper's shuffle-exchange,
            which the configuration states pv to be.
    walks   neighbour-order-rows: the walks run over the program's CSR
            with each row sorted by neighbour, where the configuration
            states rows in edge-index order.
            lossy-walk-exchange: the walkers' exchange sized 10 % under
            lossless: each bucket of every hop's exchange keeps its first
            90 % of slots and counts the rest as dropped, as an exchange of
            that capacity would, where the configuration states that every
            walker comes back.  (The program itself cannot be sized so at
            one shard: a walk capacity under the walker count fails.)

    python3 benchmarks/chip/controls.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2] [--controls one-shot-shuffle]

runs the cell on each seed, and each control of its kind (or those named)
on each control seed (all seeds by default), in one process, one unit each,
and prints one JSON line per run with what the comparison reads.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
from functools import partial
from typing import Iterator, Optional

import spec


@contextlib.contextmanager
def _patched(module, name: str, value) -> Iterator[None]:
    orig = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, orig)


def _lossy_exchange(cell):
    return contextlib.nullcontext(
        dataclasses.replace(cell, config=dict(cell.config, capacity_factor=0.9)))


@contextlib.contextmanager
def _one_shot_shuffle(cell):
    from repro.core import pipeline

    with _patched(pipeline, "distributed_shuffle", pipeline.shuffle_argsort):
        yield cell


def _exchange_kept(exchange, share: float):
    """capacity_all_to_all whose buckets keep the first `share` of their
    slots: a received row's slot is its rank in its sender's bucket."""
    import jax.numpy as jnp
    from jax import lax

    def lossy(data, dest, *, axis, capacity, **kwargs):
        ex = exchange(data, dest, axis=axis, capacity=capacity, **kwargs)
        kept = jnp.arange(capacity) < int(share * capacity)
        lost = jnp.sum((ex.valid & ~kept).astype(jnp.int32))
        return ex._replace(valid=ex.valid & kept, dropped=ex.dropped + lax.psum(lost, axis))
    return lossy


@contextlib.contextmanager
def _lossy_walk_exchange(cell):
    import jax
    from repro.data import walks

    # distributed_walks reads the exchange while it is traced: drop the
    # programs traced before the patch, and those traced under it after
    jax.clear_caches()
    try:
        with _patched(walks, "capacity_all_to_all",
                      _exchange_kept(walks.capacity_all_to_all, 0.9)):
            yield cell
    finally:
        jax.clear_caches()


def _rows_by_neighbour(build_csr):
    """build_csr, with each shard's rows then sorted by neighbour."""
    import jax
    from jax import lax

    import reference

    @partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
    def built(cfg, mesh, owned, axis="shards"):
        csr = build_csr(cfg, mesh, owned, axis)
        g = reference.GraphSpec(scale=cfg.scale, edge_factor=cfg.edge_factor, a=cfg.a,
                                b=cfg.b, c=cfg.c, d=cfg.d, nb=cfg.nb)
        _, rows, cols, _ = reference.csr_pairs(g, csr.offv, csr.adjv, csr.num_edges)
        rows, cols = rows.reshape(cfg.nb, -1), cols.reshape(cfg.nb, -1)
        _, cols = jax.vmap(lambda r, c: lax.sort((r, c), num_keys=2))(rows, cols)
        # slots past a shard's edges hold n, which sorts last in the shard
        return csr._replace(adjv=cols.reshape(csr.adjv.shape).astype(csr.adjv.dtype))
    return built


@contextlib.contextmanager
def _neighbour_order_rows(cell):
    from repro.core import pipeline

    with _patched(pipeline, "build_csr_sorted", _rows_by_neighbour(pipeline.build_csr_sorted)):
        yield cell


CONTROLS = {
    "gen": {"lossy-exchange": _lossy_exchange, "one-shot-shuffle": _one_shot_shuffle},
    "walks": {"neighbour-order-rows": _neighbour_order_rows,
              "lossy-walk-exchange": _lossy_walk_exchange},
}


def control(cell: spec.Cell, name: Optional[str] = None):
    """The cell to run as the control `name` (by default the first of its
    traffic kind), with the program patched while the block lasts where the
    control needs it."""
    kind = cell.traffic["kind"]
    if kind not in CONTROLS:
        raise ValueError(f"no control for traffic kind {kind!r}")
    name = name or next(iter(CONTROLS[kind]))
    return CONTROLS[kind][name](cell)


def _line(seed: int, run: str, result: dict) -> str:
    return json.dumps({"seed": seed, "run": run, "correct": result["correct"],
                       "failed": result["failed"], "attempted": result["attempted"],
                       "checks": {k: c["value"] for k, c in result["checks"].items()}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run a cell and its control on several seeds.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", help="comma-separated; default: --seeds")
    ap.add_argument("--controls", help="comma-separated; default: every control of the kind")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = (seeds if args.control_seeds is None
                     else [int(s) for s in args.control_seeds.split(",")])

    import bench

    cell = spec.resolve(spec.load_benchmark(), args.workload)
    bench.cache_every_program()
    names = (list(CONTROLS[cell.traffic["kind"]]) if args.controls is None
             else args.controls.split(","))
    for seed in seeds:
        print(_line(seed, "program", bench.run(cell, seed, args.seconds, False)), flush=True)
        if seed in control_seeds:
            for name in names:
                with control(cell, name) as ctl:
                    print(_line(seed, name, bench.run(ctl, seed, args.seconds, False)),
                          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
