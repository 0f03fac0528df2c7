"""Random-walk corpus generation over generated graphs.

Three samplers over the same CSR:

  host_walks            numpy, sequential-access host sampler (the oracle,
                        and the loader's default on one host)
  distributed_walks     shard_map sampler where walkers MIGRATE between
                        shards with the paper's k:1 scatter-gather
                        (capacity_all_to_all): at every step each walker is
                        shipped to the shard that owns its current vertex
                        (the paper's "a core owns its range's vertices"),
                        which advances it one hop from its LOCAL CSR rows.
                        This is the redistribute phase run once per walk
                        step — the generator's communication machinery
                        reused verbatim by the training-data subsystem.
  external_walks        out-of-core sampler over the disk tier's CSR bucket
                        files: walker frontiers live in per-bucket BlockStore
                        runs; every hop external-sorts the frontier by
                        current vertex, sort-merge-joins it against the owned
                        bucket's offv/adjv (MonotoneLookup + a forward adjv
                        scan), and partitions the advanced walkers to their
                        new owner bucket (core/phases.py walk kernels).  The
                        CSR never materializes in RAM — peak resident rows
                        are O(chunk_edges), independent of graph size.

Walk semantics — the shared RNG contract, bit-identical across all three
samplers:

  * counter-based RNG keyed by (seed, walker_id, step): the value drawn for
    walker w at step t is hostgen.walk_rand_np(seed, w, t) (uint32), so a
    walk depends only on its id and seed, never on which sampler, shard,
    bucket, or process advanced it;
  * start vertex = start_vertex(seed, w, n) (the same counter RNG at step 0,
    salted with 0xA5A5);
  * a walker at a sink vertex (deg 0) teleports to rand % n, otherwise it
    follows adjv[offv[pos] + rand % deg] — within-row adjacency ORDER is
    therefore part of the contract: samplers agree bit-for-bit only on the
    same CSR layout (host vs external comparisons must assemble the host
    CSR from the same bucket files, see concat_bucket_csr).

Dtype contract: walk histories are int64 on the host side — host_walks and
external_walks emit int64 end-to-end, so vertex ids past 2**31 survive
round-tripping.  distributed_walks computes in cfg.vertex_dtype on device
(int32 by default; set vertex_dtype=int64 under jax x64 for larger graphs —
it refuses configs whose n overflows the dtype).  Tokenization:
token = vertex % vocab (stable, vocabulary-bounded).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..core.blockstore import IOLedger, MemoryGauge
from ..core.hostgen import mix32_np as _mix32_np
from ..core.hostgen import walk_rand_np, walk_start_np
from ..core.corpus import ShardedWalks
from ..core.phases import (
    _KERNELS,
    PhaseOrchestrator,
    PlainCfg,
    WalkCfg,
    drive_walks,
    plain_config,
    result_config_key,
)
from ..core.transport import make_transport
from ..core.types import GraphConfig, owner_of
from ..distributed.collectives import capacity_all_to_all


def _mix32_jnp(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


# The numpy walk RNG lives in core/hostgen (jax-free, importable by worker
# processes running the external walk kernels); alias it so all samplers
# visibly share one stream.
_walk_rand_np = walk_rand_np


def _walk_rand_jnp(seed: int, walker: jnp.ndarray, step) -> jnp.ndarray:
    s = jnp.uint32(seed & 0xFFFFFFFF)
    stepc = jnp.uint32(step) * jnp.uint32(0x9E3779B9)
    return _mix32_jnp(_mix32_jnp(walker.astype(jnp.uint32) ^ s) + stepc)


def start_vertex(seed: int, walker: np.ndarray, n_or_B: int, base: int = 0,
                 dtype=None):
    """Deterministic start vertex of a walker (shared by all samplers).
    Numpy inputs follow the int64 history contract; jnp inputs take the
    device vertex dtype (`dtype`, default int32)."""
    if isinstance(walker, np.ndarray):
        return walk_start_np(seed, walker, n_or_B, base)
    dtype = jnp.int32 if dtype is None else dtype
    return (base + (_walk_rand_jnp(seed ^ 0xA5A5, walker, 0)
                    % jnp.uint32(n_or_B))).astype(dtype)


# ---------------------------------------------------------------------------
# host oracle
# ---------------------------------------------------------------------------


def host_walks(offv: np.ndarray, adjv: np.ndarray, starts: np.ndarray,
               length: int, seed: int, n: Optional[int] = None,
               walker_ids: Optional[np.ndarray] = None) -> np.ndarray:
    """[W, length+1] vertex walks.  starts [W]."""
    n = n if n is not None else offv.shape[0] - 1
    W = starts.shape[0]
    wid = (walker_ids if walker_ids is not None
           else np.arange(W)).astype(np.uint32)
    pos = starts.astype(np.int64).copy()
    hist = np.zeros((W, length + 1), np.int64)
    hist[:, 0] = pos
    for t in range(length):
        deg = (offv[pos + 1] - offv[pos]).astype(np.int64)
        r = _walk_rand_np(seed, wid, t + 1).astype(np.int64)
        sink = deg == 0
        idx = offv[pos] + np.where(sink, 0, r % np.maximum(deg, 1))
        nxt = np.where(sink, r % n, adjv[np.minimum(idx, adjv.shape[0] - 1)])
        pos = nxt.astype(np.int64)
        hist[:, t + 1] = pos
    return hist


# ---------------------------------------------------------------------------
# distributed sampler (walker redistribution = paper's scatter-gather)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg", "mesh", "length", "seed", "axis",
                                   "walkers_per_shard", "capacity_factor"))
def distributed_walks(
    cfg: GraphConfig,
    mesh: Mesh,
    offv: jnp.ndarray,       # [nb*(B+1)] sharded per-shard local offsets
    adjv: jnp.ndarray,       # [nb*cap_m] sharded adjacency
    *,
    length: int,
    seed: int = 0,
    walkers_per_shard: int = 64,
    capacity_factor: float = 4.0,
    axis: str = "shards",
):
    """Walk histories [nb*cap, length+1], validity [nb*cap], walker ids
    [nb*cap], global dropped count.

    Walkers start at deterministic vertices of the launching shard and hop;
    before every hop all walkers are redistributed to the owner shard of
    their current vertex via capacity_all_to_all, so each hop reads only
    LOCAL CSR rows (the external-memory discipline: every shard touches its
    own bucket, never random remote rows).  Hub-vertex skew can overflow the
    fixed per-pair capacity — overflowed walkers are counted, their rows
    marked invalid (tests assert zero drops at the configured factor).
    """
    B = cfg.bucket_size
    n = cfg.n
    W = walkers_per_shard
    k = mesh.shape[axis]
    # Device histories are computed in the configured vertex dtype; a config
    # whose ids overflow it would corrupt walks silently — refuse instead.
    # Guard against the CANONICALIZED dtype: with x64 disabled, a requested
    # int64 actually runs as int32, which is exactly the silent wrap this
    # refuses (int64-safe runs need vertex_dtype=int64 AND jax x64).
    vdt = cfg.vertex_dtype
    if cfg.n - 1 > jnp.iinfo(jax.dtypes.canonicalize_dtype(vdt)).max:
        raise ValueError(
            f"n={cfg.n} overflows vertex_dtype={np.dtype(vdt).name} "
            f"(canonicalized {np.dtype(jax.dtypes.canonicalize_dtype(vdt)).name}); "
            "use vertex_dtype=int64 with jax x64 enabled for graphs past 2**31")
    # per-(src,dst)-pair exchange capacity; every shard holds cap = cp*k rows
    cp = max(1, int(np.ceil(W * capacity_factor / k)))
    cap = cp * k

    def per_shard(offv_l, adjv_l):
        bid = lax.axis_index(axis)
        base = (bid * B).astype(vdt)
        wid = (bid * W + jnp.arange(W, dtype=jnp.int32)).astype(vdt)
        pos = start_vertex(seed, wid.astype(jnp.uint32), B, base, dtype=vdt)
        alive = jnp.ones((W,), vdt)

        def pad_to(x, fill=0):
            extra = cap - x.shape[0]
            return jnp.concatenate(
                [x, jnp.full((extra,) + x.shape[1:], fill, x.dtype)])

        pos, wid = pad_to(pos), pad_to(wid, -1)
        # alive starts axis-invariant but becomes axis-varying through the
        # exchange; mark it varying so the scan carry types match
        alive = lax.pcast(pad_to(alive), axis, to="varying")
        hist = jnp.zeros((cap, length + 1), vdt).at[:, 0].set(pos)

        def step(carry, t):
            pos, hist, alive, wid = carry
            payload = jnp.concatenate(
                [pos[:, None], wid[:, None], alive[:, None], hist], axis=1)
            ex = capacity_all_to_all(payload, owner_of(pos, B), axis=axis,
                                     capacity=cp, valid=alive == 1)
            rp = ex.data.reshape(-1, payload.shape[1])            # [cap, 3+L+1]
            rvalid = ex.valid.reshape(-1)
            rpos, rwid, ralive = rp[:, 0], rp[:, 1], rp[:, 2]
            rhist = rp[:, 3:]
            alive_now = (rvalid & (ralive == 1)).astype(vdt)
            # advance one hop from local CSR rows
            row = jnp.clip(rpos - bid * B, 0, B - 1)
            start, end = offv_l[row], offv_l[row + 1]
            deg = end - start
            r = _walk_rand_jnp(seed, rwid.astype(jnp.uint32), t + 1)
            sink = deg <= 0
            idx = start + jnp.where(
                sink, 0,
                (r % jnp.maximum(deg, 1).astype(jnp.uint32)).astype(vdt))
            nxt = jnp.where(sink, (r % jnp.uint32(n)).astype(vdt),
                            adjv_l[jnp.clip(idx, 0, adjv_l.shape[0] - 1)])
            nxt = jnp.where(alive_now == 1, nxt, 0)
            rhist = jax.vmap(
                lambda h, v: h.at[t + 1].set(v))(rhist, nxt)
            return (nxt, rhist, alive_now, rwid), ex.dropped

        (pos, hist, alive, wid), dropped = lax.scan(
            step, (pos, hist, alive, wid), jnp.arange(length, dtype=jnp.int32))
        # ex.dropped is already psum'd -> every shard holds the same global
        # per-step totals; sum over steps, report one copy per shard.
        return hist, alive, wid, jnp.sum(dropped)[None]

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
    )
    hist, alive, wid, dropped = fn(offv, adjv)
    return hist, alive == 1, wid, dropped[0]


def walks_to_tokens(walks: np.ndarray, vocab: int) -> Tuple[np.ndarray, np.ndarray]:
    """Vertex walks [W, L+1] -> (tokens [W, L], labels [W, L]) next-token LM
    pairs; token = vertex % vocab."""
    toks = (walks % vocab).astype(np.int32)
    return toks[:, :-1], toks[:, 1:].copy()


# ---------------------------------------------------------------------------
# external sampler (the redistribute phase re-run once per hop, on disk)
# ---------------------------------------------------------------------------


class ExternalWalkResult(NamedTuple):
    """external_walks output: the sharded corpus plus the accounting objects
    tests and benchmarks assert against."""

    walks: "ShardedWalks"        # [W, length+1] int64 array-like (disk-backed
                                 # per-bucket shards + manifest, core/corpus.py)
    ledger: IOLedger
    gauge: MemoryGauge
    orchestrator: PhaseOrchestrator


def external_walks(cfg, workdir: str, *, num_walkers: int, length: int,
                   seed: int = 0, ledger: Optional[IOLedger] = None,
                   gauge: Optional[MemoryGauge] = None,
                   checkpoint: bool = False,
                   out_name: str = "walks.npy") -> ExternalWalkResult:
    """Out-of-core walk corpus [num_walkers, length+1] over the CSR bucket
    files in `workdir` (written by StreamingGenerator / PartitionedGenerator's
    CSR phase) — the graph never materializes in RAM, and neither does the
    corpus: the collect phase is SHARDED (one `{out}_b{j}.npy` per bucket +
    a manifest, core/corpus.py), and `result.walks` is an array-like view
    over the shards.

    Each hop is the paper's redistribute phase applied to walkers: sort the
    per-bucket frontier by current vertex, sort-merge-join it against the
    owned offv/adjv runs, partition advanced walkers to their new owner
    (core/phases.py walk kernels).  Bit-identical to host_walks on the
    assembled bucket CSR (concat_bucket_csr) with walker_ids arange(W) and
    the standard start_vertex starts.  With checkpoint=True each hop is a
    resumable phase (state in <workdir>/walk_phases.json, independent of the
    generator's checkpoint); phase-level ledger deltas and peak resident
    rows come back in the result.

    Runs the bucket kernels in-process; for real process parallelism use
    PartitionedGenerator.walk_corpus, which drives the same kernels through
    its worker pool.

    Every per-hop frontier sort and the history gather merge through
    cfg.merge_fanin-bounded cascades (blockstore.merge_runs via PlainCfg),
    so walking a store with millions of frontier runs never exceeds the
    open-file budget — identical corpus at any fan-in.
    """
    pcfg = cfg if isinstance(cfg, PlainCfg) else plain_config(cfg)
    ledger = IOLedger() if ledger is None else ledger
    gauge = MemoryGauge(budget_rows=pcfg.chunk_edges) if gauge is None else gauge
    wcfg = WalkCfg(num_walkers=num_walkers, length=length, seed=seed,
                   out_name=out_name)
    orch = PhaseOrchestrator(workdir, ledger, checkpoint=checkpoint,
                             state_name="walk_phases.json",
                             config_key=repr((result_config_key(pcfg), wcfg)),
                             keep_all=bool(getattr(cfg, "keep_phase_stores",
                                                   False)))

    # One transport for the whole corpus: the kernels' exchange AND the
    # drivers' pre-senders inbox sweeps go through it (fs by default;
    # a socket config with live peer_addrs works too — the partitioned
    # driver is the usual owner of that mode).
    with make_transport(pcfg, workdir, ledger, gauge) as tr:

        def inline_map(kernel: str, argss):
            # Outputs matter: the pooled-cascade hop plans its merge levels
            # from the counts the sort kernels return.
            return [_KERNELS[kernel](pcfg, workdir, *args, ledger=ledger,
                                     gauge=gauge, transport=tr)
                    for args in argss]

        path = drive_walks(pcfg, workdir, wcfg, inline_map, orch, transport=tr)
    return ExternalWalkResult(ShardedWalks(path), ledger, gauge, orch)


def concat_bucket_csr(csr) -> Tuple[np.ndarray, np.ndarray]:
    """Assemble per-bucket CSR [(offv_i, adjv_i)] into one host (offv, adjv).

    Oracle-side helper: within-row adjacency order is part of the walk
    contract, so host_walks must read the SAME layout external_walks joins
    against.  Materializes the CSR — tests and small graphs only.
    """
    parts = [np.zeros(1, np.int64)]
    total = 0
    for offv, _ in csr:
        offv = np.asarray(offv, np.int64)
        parts.append(offv[1:] + total)
        total += int(offv[-1])
    adjv = np.concatenate([np.asarray(a, np.int64) for _, a in csr])
    return np.concatenate(parts), adjv
