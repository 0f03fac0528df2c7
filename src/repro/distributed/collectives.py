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
    peak: jnp.ndarray      # [] int32   records bound for the fullest destination, kept or not


def bucket_by_destination(data: jnp.ndarray, dest: jnp.ndarray, k: int, capacity: int,
                          valid: Optional[jnp.ndarray] = None) -> Buckets:
    """Stable bucket of `data` rows by `dest` in [0, k) with fixed capacity.

    Paper Alg. 8 lines 2-7 ("append to elp_d; if full, send") under static
    shapes.  Stable: records to the same destination keep their relative
    order (for a sorted `dest`, bucket_sorted_runs gives the same buckets
    without the sort).  Rows with valid=False are discarded silently (they
    consume no capacity and are not counted as drops) — used by callers that
    carry fixed-size buffers with dead slots (data/walks.py).
    """
    n = dest.shape[0]
    with jax.named_scope("place"):
        dest = dest.astype(jnp.int32)
        if valid is not None:
            dest = jnp.where(valid, dest, k)                      # sentinel group
    # Rank of each record within its destination group, via stable sort:
    with jax.named_scope("sort"):
        order = jnp.argsort(dest, stable=True)                   # [N]
    with jax.named_scope("permute"):
        sorted_dest = dest[order]
    with jax.named_scope("search"):
        # start offset of each destination group among the sorted records
        group_start = jnp.searchsorted(sorted_dest, jnp.arange(k, dtype=jnp.int32), side="left")
        rank_sorted = jnp.arange(n, dtype=jnp.int32) - group_start[jnp.minimum(sorted_dest, k - 1)]
    with jax.named_scope("permute"):
        rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)  # rank within dest group
    with jax.named_scope("place"):
        keep = (rank < capacity) & (dest < k)
        slot = jnp.where(keep, dest * capacity + rank, k * capacity)  # overflow -> scratch slot
        flat_shape = (k * capacity + 1,) + data.shape[1:]
        flat = jnp.zeros(flat_shape, data.dtype).at[slot].set(data, mode="drop")
        # Occupancy is marked in int32, not bool: a large bool scatter (and the
        # bool all_to_all after it) takes the TPU compiler minutes and several
        # times the host memory of the int32 one.
        occupied = jnp.zeros((k * capacity + 1,), jnp.int32).at[slot].set(1, mode="drop")
        dropped = jnp.sum((rank >= capacity) & (dest < k)).astype(jnp.int32)
        # a group of c records ranks its last one c - 1
        peak = jnp.max(jnp.where(dest < k, rank + 1, 0), initial=0)
        data_rows = flat[:-1].reshape((k, capacity) + data.shape[1:])
        valid_rows = occupied[:-1].reshape(k, capacity) == 1
    return Buckets(data=data_rows, valid=valid_rows, position=slot, dropped=dropped, peak=peak)


def bucket_sorted_runs(data: jnp.ndarray, dest: jnp.ndarray, k: int, capacity: int) -> Buckets:
    """bucket_by_destination for a non-decreasing `dest` in [0, k), with no
    sort and no scatter: the same Buckets, bit for bit.

    Each destination's records are then one contiguous run of the input, in
    the input's order, and a record's rank is its distance from the start of
    its run.  So row d is the run's first `capacity` records, one slice of
    the input; k boundary searches find the runs.
    """
    n = dest.shape[0]
    with jax.named_scope("search"):
        dest = dest.astype(jnp.int32)
        starts = jnp.searchsorted(dest, jnp.arange(k + 1, dtype=jnp.int32),
                                  side="left").astype(jnp.int32)
        counts = starts[1:] - starts[:-1]
    with jax.named_scope("place"):
        # padded so that a slice from any start <= n stays inside the array
        padded = jnp.concatenate(
            [data, jnp.zeros((capacity,) + data.shape[1:], data.dtype)], axis=0)
        rows = jnp.stack([lax.dynamic_slice_in_dim(padded, starts[d], capacity)
                          for d in range(k)])
        valid_rows = jnp.arange(capacity, dtype=jnp.int32) < jnp.minimum(counts, capacity)[:, None]
        # empty slots hold 0, as bucket_by_destination leaves them
        mask = valid_rows.reshape(valid_rows.shape + (1,) * (data.ndim - 1))
        data_rows = jnp.where(mask, rows, jnp.zeros((), data.dtype))
        dropped = jnp.sum(jnp.maximum(counts - capacity, 0)).astype(jnp.int32)
        peak = jnp.max(counts)
        rank = jnp.arange(n, dtype=jnp.int32) - starts[dest]
        slot = jnp.where(rank < capacity, dest * capacity + rank, k * capacity)
    return Buckets(data=data_rows, valid=valid_rows, position=slot, dropped=dropped, peak=peak)


def unbucket(buckets_data: jnp.ndarray, position: jnp.ndarray, fill=0) -> jnp.ndarray:
    """Inverse of bucket_by_destination for the *return trip*: gather each
    record's (possibly transformed) payload back to its original position.

    Dropped records receive `fill`.
    """
    k, capacity = buckets_data.shape[:2]
    with jax.named_scope("place"):
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
    peak_load: jnp.ndarray  # [] int32 most records any shard sent to any one shard (pmax'd),
                            #          the capacity a lossless exchange needed


def capacity_all_to_all(
    data: jnp.ndarray,
    dest: jnp.ndarray,
    *,
    axis: str,
    capacity: int,
    valid: Optional[jnp.ndarray] = None,
    dest_sorted: bool = False,
) -> ExchangeResult:
    """Bucket records by destination shard and exchange them (k:1 pattern).

    Must be called inside shard_map over `axis`.  `data` is [N, ...] local
    records, `dest` [N] destination shard ids in [0, k).  Rows with
    valid=False are discarded without consuming capacity.  A caller whose
    `dest` is non-decreasing, because it sorted its records by destination
    itself, says so with dest_sorted=True: the buckets are then its runs
    (bucket_sorted_runs), with no sort and no scatter.  Besides the drops,
    the result counts the load of the fullest (sender, destination) pair,
    which tells how near the exchange came to dropping.
    """
    k = lax.axis_size(axis)
    with jax.named_scope("exchange"):
        if dest_sorted:
            assert valid is None, "the run bucketing takes every row"
            b = bucket_sorted_runs(data, dest, k, capacity)
        else:
            b = bucket_by_destination(data, dest, k, capacity, valid=valid)
        with jax.named_scope("collective"):
            recv = lax.all_to_all(b.data, axis, split_axis=0, concat_axis=0, tiled=False)
            # the mask crosses as int32 for the reason bucket_by_destination marks it so
            recv_valid = lax.all_to_all(b.valid.astype(jnp.int32), axis, split_axis=0,
                                        concat_axis=0, tiled=False) == 1
            dropped = lax.psum(b.dropped, axis)
            peak_load = lax.pmax(b.peak, axis)
    return ExchangeResult(recv, recv_valid, b.position, dropped, peak_load)


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
    with jax.named_scope("exchange"):
        with jax.named_scope("collective"):
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
    with jax.named_scope("collective"):
        return lax.ppermute(x, axis, perm)


# ---------------------------------------------------------------------------
# Sorted-merge helpers (paper §III-B7)
# ---------------------------------------------------------------------------


def merge_sorted_runs(keys: jnp.ndarray, payload: jnp.ndarray):
    """Merge k sorted runs [k, run_len], `payload` (the keys' shape) carried
    along, into one sorted run of k * run_len.

    Stable: equal keys keep run order, then position within the run.  For
    k > 1 this is one stable sort of the runs concatenated in run order; one
    run is returned as it is, with no sort.  On a TPU the sort is far
    cheaper than a tree of pairwise merges by binary-search ranks, whose
    O(m log k) steps run as searches in HBM (PERF.md has both readings).
    """
    if keys.shape[0] == 1:
        return keys[0], payload[0]
    return lax.sort((keys.reshape(-1), payload.reshape(-1)), num_keys=1, is_stable=True)
