"""Collective building blocks.

The paper's communication machinery is a *k:1 scatter-gather* pattern
(§III-A): every node buckets outgoing records per destination, ships packets
when full, and one collector thread per node appends arriving packets.  On a
TPU mesh the same pattern is a **fixed-capacity bucketed all_to_all**:

    bucket-by-destination  ->  all_to_all  ->  concatenate-what-arrived

Because XLA requires static shapes, "send packet when full" becomes a
per-destination buffer of `capacity` records plus a validity mask; overflow
is *counted and reported*, never silently dropped (tests assert zero drops at
the configured capacity factor).  This one primitive serves three masters:

  * core/redistribute.py  — the paper's redistribute step,
  * core/relabel.py       — the optimized (non-ring) relabel variant,
  * models/moe.py         — MoE expert dispatch (tokens -> expert owners),

which is the concrete sense in which the paper's scatter-gather pattern is a
first-class framework primitive.

Everything here runs *inside* shard_map over a single named axis.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Mesh helpers
# ---------------------------------------------------------------------------


def flat_mesh(n_shards: Optional[int] = None, axis: str = "shards") -> jax.sharding.Mesh:
    """A 1-D mesh over the first `n_shards` devices (default: all).

    The graph pipeline treats every chip as one of the paper's "compute
    nodes" (nb = number of shards); model code uses the 2-D/3-D production
    mesh from launch/mesh.py instead.
    """
    devs = jax.devices()
    if n_shards is None:
        n_shards = len(devs)
    import numpy as np

    return jax.sharding.Mesh(np.asarray(devs[:n_shards]), (axis,))


# ---------------------------------------------------------------------------
# Bucketing (the scatter side)
# ---------------------------------------------------------------------------


class Buckets(NamedTuple):
    """Result of bucketing N records into k fixed-capacity destination rows."""

    data: jnp.ndarray      # [k, capacity, ...]  bucketed payload
    valid: jnp.ndarray     # [k, capacity] bool  slot occupied?
    position: jnp.ndarray  # [N] int32  (dest, slot) flattened index each record went to
                           #            (= dest*capacity + slot; capacity*k if dropped)
    dropped: jnp.ndarray   # [] int32   records that exceeded capacity (counted, not lost silently)


def bucket_by_destination(data: jnp.ndarray, dest: jnp.ndarray, k: int, capacity: int,
                          valid: Optional[jnp.ndarray] = None) -> Buckets:
    """Stable bucket of `data` rows by `dest` in [0, k) with fixed capacity.

    Paper Alg. 8 lines 2-7 ("append to elp_d; if full, send") under static
    shapes.  Stability (records to the same destination keep their relative
    order) is what lets the sorted-merge redistribute variant (§III-B7) ship
    pre-sorted runs.  Rows with valid=False are discarded silently (they
    consume no capacity and are not counted as drops) — used by callers that
    carry fixed-size buffers with dead slots (data/walks.py).
    """
    n = dest.shape[0]
    dest = dest.astype(jnp.int32)
    if valid is not None:
        dest = jnp.where(valid, dest, k)                          # sentinel group
    # Rank of each record within its destination group, via stable sort:
    order = jnp.argsort(dest, stable=True)                       # [N]
    sorted_dest = dest[order]
    # start offset of each destination group among the sorted records
    group_start = jnp.searchsorted(sorted_dest, jnp.arange(k, dtype=jnp.int32), side="left")
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - group_start[jnp.minimum(sorted_dest, k - 1)]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)  # rank within dest group
    keep = (rank < capacity) & (dest < k)
    slot = jnp.where(keep, dest * capacity + rank, k * capacity)  # overflow -> scratch slot
    flat_shape = (k * capacity + 1,) + data.shape[1:]
    flat = jnp.zeros(flat_shape, data.dtype).at[slot].set(data, mode="drop")
    # Occupancy is marked in int32, not bool: a large bool scatter (and the
    # bool all_to_all after it) takes the TPU compiler minutes and several
    # times the host memory of the int32 one.
    occupied = jnp.zeros((k * capacity + 1,), jnp.int32).at[slot].set(1, mode="drop")
    dropped = jnp.sum((rank >= capacity) & (dest < k)).astype(jnp.int32)
    return Buckets(
        data=flat[:-1].reshape((k, capacity) + data.shape[1:]),
        valid=occupied[:-1].reshape(k, capacity) == 1,
        position=slot,
        dropped=dropped,
    )


def unbucket(buckets_data: jnp.ndarray, position: jnp.ndarray, fill=0) -> jnp.ndarray:
    """Inverse of bucket_by_destination for the *return trip*: gather each
    record's (possibly transformed) payload back to its original position.

    Dropped records receive `fill`.
    """
    k, capacity = buckets_data.shape[:2]
    flat = buckets_data.reshape((k * capacity,) + buckets_data.shape[2:])
    pad = jnp.full((1,) + flat.shape[1:], fill, flat.dtype)
    flat = jnp.concatenate([flat, pad], axis=0)
    return flat[position]


# ---------------------------------------------------------------------------
# The k:1 scatter-gather collective
# ---------------------------------------------------------------------------


class ExchangeResult(NamedTuple):
    data: jnp.ndarray      # [k, capacity, ...] row j = records sent to me by shard j
    valid: jnp.ndarray     # [k, capacity] bool
    position: jnp.ndarray  # [N] local bucketing positions (for the return trip)
    dropped: jnp.ndarray   # [] int32  GLOBAL dropped count (psum'd)


def capacity_all_to_all(
    data: jnp.ndarray,
    dest: jnp.ndarray,
    *,
    axis: str,
    capacity: int,
    valid: Optional[jnp.ndarray] = None,
) -> ExchangeResult:
    """Bucket records by destination shard and exchange them (k:1 pattern).

    Must be called inside shard_map over `axis`.  `data` is [N, ...] local
    records, `dest` [N] destination shard ids in [0, k).  Rows with
    valid=False are discarded without consuming capacity.
    """
    k = lax.axis_size(axis)
    b = bucket_by_destination(data, dest, k, capacity, valid=valid)
    recv = lax.all_to_all(b.data, axis, split_axis=0, concat_axis=0, tiled=False)
    # the mask crosses as int32 for the reason bucket_by_destination marks it so
    recv_valid = lax.all_to_all(b.valid.astype(jnp.int32), axis, split_axis=0,
                                concat_axis=0, tiled=False) == 1
    dropped = lax.psum(b.dropped, axis)
    return ExchangeResult(recv, recv_valid, b.position, dropped)


def return_all_to_all(
    results: jnp.ndarray,
    position: jnp.ndarray,
    *,
    axis: str,
    fill=0,
) -> jnp.ndarray:
    """Return trip of capacity_all_to_all: send per-record results back to the
    shard that asked, and scatter them to the original record order.

    `results` is [k, capacity, ...] aligned with ExchangeResult.data.
    """
    back = lax.all_to_all(results, axis, split_axis=0, concat_axis=0, tiled=False)
    return unbucket(back, position, fill=fill)


# ---------------------------------------------------------------------------
# Ring streaming (the paper's permute_server, as a collective schedule)
# ---------------------------------------------------------------------------


def ring_shift(x: jnp.ndarray, axis: str, shift: int = 1) -> jnp.ndarray:
    """Rotate shard-local blocks around the ring: shard i receives the block
    of shard (i + shift) mod k.

    This is the paper's `get_permute_range` remote fetch turned into a
    static collective schedule: instead of every shard *pulling* chunk s from
    its owner (random access across the interconnect), the chunks *stream*
    past every shard in nb rounds — sequential access on the ICI, the exact
    analogue of the paper turning random disk I/O into sequential scans.
    """
    k = lax.axis_size(axis)
    perm = [(i, (i - shift) % k) for i in range(k)]  # (source, destination)
    return lax.ppermute(x, axis, perm)


# ---------------------------------------------------------------------------
# Sorted-merge helpers (paper §III-B7)
# ---------------------------------------------------------------------------


def merge_two_sorted(a: jnp.ndarray, b: jnp.ndarray, a_payload=None, b_payload=None):
    """Merge two sorted arrays in O(n) sequential-access style using
    searchsorted ranks (no comparison sort).

    Returns merged keys (and merged payloads if given).  This is the TPU
    analogue of the paper's streaming sorted-merge: every element's final
    position is computed by a binary search + add, all memory access patterns
    are sequential scans or monotone gathers.
    """
    na, nb_ = a.shape[0], b.shape[0]
    pos_a = jnp.arange(na, dtype=jnp.int32) + jnp.searchsorted(b, a, side="left").astype(jnp.int32)
    pos_b = jnp.arange(nb_, dtype=jnp.int32) + jnp.searchsorted(a, b, side="right").astype(jnp.int32)
    # pos_a and pos_b are each strictly increasing (a sorted index plus a
    # monotone rank).  Saying so lets the TPU compiler emit a plain scatter,
    # which compiles faster and needs less temporary memory.
    put = dict(indices_are_sorted=True, unique_indices=True)
    out = jnp.zeros((na + nb_,), a.dtype)
    out = out.at[pos_a].set(a, **put).at[pos_b].set(b, **put)
    if a_payload is None:
        return out
    pay = jnp.zeros((na + nb_,) + a_payload.shape[1:], a_payload.dtype)
    pay = pay.at[pos_a].set(a_payload, **put).at[pos_b].set(b_payload, **put)
    return out, pay


def merge_sorted_runs(keys: jnp.ndarray, payload: Optional[jnp.ndarray] = None):
    """K-way merge of k sorted runs [k, run_len] via log2(k) pairwise rounds.

    O(m log k) work with sequential access — cheaper than re-sorting
    (O(m log m)) and faithful to the paper's sorted-merge redistribute.
    k must be a power of two (mesh axis sizes are).

    Runs stay separate arrays between rounds: stacking a round's outputs
    and slicing the next round's inputs back out of the stack makes the
    TPU compile of a 4-way merge of 2^24-long runs take minutes.
    """
    k = keys.shape[0]
    assert (k & (k - 1)) == 0, f"k={k} must be a power of two"
    runs = [keys[i] for i in range(k)]
    pays = [None if payload is None else payload[i] for i in range(k)]
    while len(runs) > 1:
        if payload is None:
            runs = [merge_two_sorted(runs[i], runs[i + 1])
                    for i in range(0, len(runs), 2)]
        else:
            merged = [merge_two_sorted(runs[i], runs[i + 1], pays[i], pays[i + 1])
                      for i in range(0, len(runs), 2)]
            runs, pays = [m[0] for m in merged], [m[1] for m in merged]
    if payload is None:
        return runs[0]
    return runs[0], pays[0]
