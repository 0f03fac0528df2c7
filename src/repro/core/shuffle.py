"""Distributed random shuffle -> permutation vector pv (paper Alg. 2-4).

The paper's shuffle: each node holds one range-partition of [0:n) in `sbuf`;
for log_nb(n) rounds it (i) shuffles sbuf locally, (ii) 1:1 scatter-gathers
equal slices to every other node, (iii) swaps buffers.  The result, read in
shard order, is a permutation vector pv with pv[i] = new label of vertex i.

TPU adaptation:
  * local shuffle  = argsort of counter-hash keys (Fisher-Yates equivalent:
    sorting by i.i.d. keys is a uniform permutation of the buffer);
  * 1:1 slice exchange = `lax.all_to_all` over the shard axis (the paper's
    Alg. 2/3 send/recv loops are literally the definition of all_to_all);
  * the round loop is a `lax.fori_loop`, so the whole shuffle is one compiled
    program regardless of n.

Three variants:
  distributed_shuffle       paper-faithful multi-round shuffle-exchange
  shuffle_argsort           beyond-paper exact one-shot shuffle (global sort
                            by random keys) — what you'd do when the whole
                            key vector fits aggregate HBM.
  shuffle_recompute         the communication-free family (Funke et al.):
                            pv[i] = keyed_perm(i), a Feistel bijection over
                            mix32 — ZERO collectives, every shard evaluates
                            its own slice, and any host can recompute any
                            entry (the disk tier never materializes pv at
                            all).  jnp twin of hostgen.keyed_perm_np,
                            bit-exact (tested).

All return pv as a global array of shape (n,) sharded over the mesh axis.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .hostgen import (
    FEISTEL_ROUNDS,
    feistel_round_key_np,
    graph_perm_key,
    perm_domain_bits,
)
from .rmat import mix32
from .types import GraphConfig


def _local_shuffle(buf: jnp.ndarray, salt: jnp.ndarray) -> jnp.ndarray:
    """Uniform local permutation: sort by i.i.d. counter-hash keys.

    Keys depend on the *values* (unique across the machine — buf always holds
    a subset of a permutation of [0:n)) and a per-round salt, so the schedule
    is deterministic, reproducible, and needs no RNG state.
    """
    with jax.named_scope("rng"):
        keys = mix32(buf.astype(jnp.uint32) ^ salt)
    with jax.named_scope("sort"):
        order = jnp.argsort(keys)
    with jax.named_scope("permute"):
        return buf[order]


def _shuffle_rounds_body(nb: int, axis: str, seed: int):
    def body(r, sbuf):
        with jax.named_scope("rng"):
            salt = mix32(jnp.uint32(seed) + jnp.uint32(r) * jnp.uint32(0x9E3779B9))
        sbuf = _local_shuffle(sbuf, salt)
        if nb > 1:
            blk = sbuf.shape[0] // nb
            pieces = sbuf.reshape(nb, blk)
            # Alg. 2/3: slice j of my buffer -> node j; my slice stays (line 6).
            with jax.named_scope("collective"):
                pieces = lax.all_to_all(pieces, axis, split_axis=0, concat_axis=0, tiled=False)
            sbuf = pieces.reshape(-1)
        return sbuf

    return body


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
def distributed_shuffle(cfg: GraphConfig, mesh: Mesh, axis: str = "shards") -> jnp.ndarray:
    """Paper-faithful shuffle (Alg. 4).  Returns pv of shape (n,), sharded."""
    nb = mesh.shape[axis]
    assert nb == cfg.nb, f"mesh axis size {nb} != cfg.nb {cfg.nb}"
    B = cfg.bucket_size
    assert B % max(nb, 1) == 0, "bucket size must split into nb exchange slices"
    rounds = cfg.rounds

    def per_shard(_):
        with jax.named_scope("rng"):
            bid = lax.axis_index(axis)
            # sbuf initialized to this shard's range partition of [0:n)  (RP(n, nb))
            sbuf = bid * B + jnp.arange(B, dtype=cfg.vertex_dtype)
        sbuf = lax.fori_loop(0, rounds, _shuffle_rounds_body(nb, axis, cfg.seed), sbuf)
        return sbuf

    shard_fn = jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis)
    )
    with jax.named_scope("shuffle"):
        dummy = jnp.zeros((nb,), jnp.int32)  # carries the axis, no data
        return shard_fn(dummy)


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
def shuffle_argsort(cfg: GraphConfig, mesh: Mesh, axis: str = "shards") -> jnp.ndarray:
    """Beyond-paper exact shuffle: pv = argsort(counter-hash keys of [0:n)).

    One global (distributed) sort instead of log_nb(n) shuffle-exchange
    rounds.  XLA partitions the sort across the mesh; this is the fast path
    when aggregate HBM holds the key vector — i.e. the regime where the
    paper's memory wall doesn't bind.
    """
    n = cfg.n
    sharding = NamedSharding(mesh, P(axis))
    with jax.named_scope("shuffle"):
        with jax.named_scope("rng"):
            ids = jnp.arange(n, dtype=cfg.vertex_dtype)
            ids = lax.with_sharding_constraint(ids, sharding)
            keys = mix32(ids.astype(jnp.uint32) + jnp.uint32(cfg.seed))
        # sort (keys, ids) pairs by key: ids land in uniformly-random order.
        # mix32 is bijective => no duplicate keys => exact uniform permutation.
        with jax.named_scope("sort"):
            _, pv = lax.sort([keys, ids], dimension=0, num_keys=1)
            return lax.with_sharding_constraint(pv, sharding)


# ---------------------------------------------------------------------------
# Keyed invertible permutation family — jnp twin of hostgen's Feistel.
# Container is uint32 (jax x64 stays disabled), so nbits <= 32; the numpy
# source of truth covers nbits <= 62 with its uint64 container.  For the
# overlap the two agree bit for bit (tested), as does the Pallas kernel
# (kernels/rmat.feistel_perm_pallas).
# ---------------------------------------------------------------------------


def feistel_perm(x: jnp.ndarray, key: int, nbits: int,
                 rounds: int = FEISTEL_ROUNDS) -> jnp.ndarray:
    """Keyed bijection on [0, 2**nbits), nbits <= 32.  Returns uint32.

    Identical round structure to hostgen.feistel_perm_np: F = mix32(R ^
    rk_i) with rk_i = mix32(key + (i+1)*GOLDEN) folded in Python ints, the
    halves swap, and the new R is masked to the old L's width.  The round
    loop is a static unroll (rounds is a compile-time constant)."""
    if rounds < 2 or rounds % 2:
        raise ValueError(f"feistel rounds must be even and >= 2, got {rounds}")
    if not 1 <= nbits <= 32:
        raise ValueError(
            f"jnp feistel container is uint32: need 1 <= nbits <= 32, got "
            f"{nbits} (use hostgen.feistel_perm_np for wider domains)")
    lo_bits = nbits // 2
    x = jnp.asarray(x).astype(jnp.uint32)
    L = x >> lo_bits
    R = x & jnp.uint32((1 << lo_bits) - 1)
    wL, wR = nbits - lo_bits, lo_bits
    for i in range(rounds):
        rk = jnp.uint32(int(feistel_round_key_np(key, i)))
        F = mix32(R ^ rk)
        L, R, wL, wR = R, (L ^ F) & jnp.uint32((1 << wL) - 1), wR, wL
    return (L << lo_bits) | R


def keyed_perm(x: jnp.ndarray, key: int, n: int,
               rounds: int = FEISTEL_ROUNDS) -> jnp.ndarray:
    """Keyed bijection on [0, n) via cycle-walking (twin of
    hostgen.keyed_perm_np).  For power-of-two n the while_loop body never
    runs; otherwise out-of-range lanes are re-permuted until in range
    (termination: the Feistel orbit of any x < n returns to x).  Returns
    the input's dtype."""
    nbits = perm_domain_bits(n)
    dtype = jnp.asarray(x).dtype
    y = feistel_perm(x, key, nbits, rounds)
    bound = jnp.uint32(n)

    def walk(y):
        return jnp.where(y >= bound, feistel_perm(y, key, nbits, rounds), y)

    if n != (1 << nbits):  # non-power-of-two domain: cycle-walk
        y = lax.while_loop(lambda y: jnp.any(y >= bound), walk, y)
    return y.astype(dtype)


def graph_perm(seed: int, x: jnp.ndarray, n: int,
               rounds: int = FEISTEL_ROUNDS) -> jnp.ndarray:
    """Device twin of hostgen.graph_perm_np (same key derivation)."""
    return keyed_perm(x, graph_perm_key(seed), n, rounds)


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
def shuffle_recompute(cfg: GraphConfig, mesh: Mesh, axis: str = "shards") -> jnp.ndarray:
    """Communication-free pv: every shard evaluates keyed_perm over its own
    range partition — no shuffle rounds, no all_to_all, no materialized
    state beyond the output itself.  Requires cfg.scale <= 31 (vertex ids
    must fit the uint32 Feistel container)."""
    sharding = NamedSharding(mesh, P(axis))
    with jax.named_scope("shuffle"), jax.named_scope("rng"):
        ids = lax.with_sharding_constraint(
            jnp.arange(cfg.n, dtype=cfg.vertex_dtype), sharding)
        pv = graph_perm(cfg.seed, ids, cfg.n, rounds=cfg.feistel_rounds)
        return lax.with_sharding_constraint(pv, sharding)


def pv_is_permutation(pv: jnp.ndarray) -> jnp.ndarray:
    """Check pv is a bijection on [0:n) (validation hook)."""
    n = pv.shape[0]
    hits = jnp.zeros((n,), jnp.int32).at[pv].add(1)
    return jnp.all(hits == 1)
