"""Hypothesis property tests on the system's invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.types import GraphConfig, owner_of, quadrant_thresholds
from repro.distributed.collectives import (
    bucket_by_destination, bucket_sorted_runs, merge_sorted_runs, merge_two_sorted, unbucket)
from repro.kernels import ref
from repro.serve.sampling import SamplingParams, sample
from repro.train.fault import StragglerPolicy

SETTINGS = settings(max_examples=30, deadline=None)


# ---------------------------------------------------------------------------
# bucketing (the paper's Alg. 8 under static shapes)
# ---------------------------------------------------------------------------


@SETTINGS
@given(
    n=st.integers(1, 300),
    k=st.integers(1, 8),
    cap_frac=st.floats(0.1, 3.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_bucket_invariants(n, k, cap_frac, seed):
    rng = np.random.default_rng(seed)
    dest = rng.integers(0, k, n).astype(np.int32)
    data = rng.integers(0, 1 << 30, n).astype(np.int32)
    capacity = max(1, int(n * cap_frac / k))
    b = bucket_by_destination(jnp.asarray(data), jnp.asarray(dest), k, capacity)

    data_np = np.asarray(b.data)
    valid_np = np.asarray(b.valid)
    # 1. dropped count is exact
    exp_dropped = sum(max(0, int((dest == j).sum()) - capacity) for j in range(k))
    assert int(b.dropped) == exp_dropped
    # 2. kept records form a sub-multiset, stable within destination
    for j in range(k):
        want = data[dest == j][:capacity]
        got = data_np[j][valid_np[j]]
        np.testing.assert_array_equal(got, want)
    # 3. round trip: unbucket returns every kept record to its origin
    back = np.asarray(unbucket(b.data, b.position, fill=-1))
    kept = back != -1
    np.testing.assert_array_equal(back[kept], data[kept])
    assert kept.sum() == n - exp_dropped


@SETTINGS
@given(
    n=st.integers(1, 300),
    k=st.integers(1, 8),
    cap_frac=st.floats(0.1, 3.0),
    seed=st.integers(0, 2**31 - 1),
    width=st.sampled_from([(), (2,)]),
)
def test_sorted_runs_bucket_as_the_general_bucketing(n, k, cap_frac, seed, width):
    """On a non-decreasing dest the run bucketing gives bucket_by_destination's
    result bit for bit, empty and overflowing destinations included."""
    rng = np.random.default_rng(seed)
    used = rng.choice(k, size=rng.integers(1, k + 1), replace=False)  # others stay empty
    dest = np.sort(rng.choice(used, n)).astype(np.int32)
    data = rng.integers(0, 1 << 30, (n,) + width).astype(np.int32)
    capacity = max(1, int(n * cap_frac / k))
    want = bucket_by_destination(jnp.asarray(data), jnp.asarray(dest), k, capacity)
    got = bucket_sorted_runs(jnp.asarray(data), jnp.asarray(dest), k, capacity)
    for field in want._fields:
        w, g = np.asarray(getattr(want, field)), np.asarray(getattr(got, field))
        assert g.dtype == w.dtype and g.shape == w.shape, field
        np.testing.assert_array_equal(g, w, err_msg=field)
    # round trip: unbucket returns every kept record to its origin
    back = np.asarray(unbucket(got.data, got.position, fill=-1))
    kept = np.asarray(got.position) < k * capacity
    np.testing.assert_array_equal(back[kept], data[kept])
    assert (back[~kept] == -1).all()
    assert kept.sum() == n - int(got.dropped)


@SETTINGS
@given(
    n=st.integers(1, 300),
    k=st.integers(1, 8),
    cap_frac=st.floats(0.1, 3.0),
    seed=st.integers(0, 2**31 - 1),
    masked=st.booleans(),
)
def test_bucket_peak_is_the_fullest_destination(n, k, cap_frac, seed, masked):
    """Both bucketings count the records bound for the fullest destination,
    kept or dropped; rows masked invalid count for none."""
    rng = np.random.default_rng(seed)
    dest = np.sort(rng.integers(0, k, n)).astype(np.int32)
    data = rng.integers(0, 1 << 30, n).astype(np.int32)
    valid = rng.random(n) < 0.7 if masked else np.ones(n, bool)
    capacity = max(1, int(n * cap_frac / k))
    want = max(int(((dest == j) & valid).sum()) for j in range(k))
    b = bucket_by_destination(jnp.asarray(data), jnp.asarray(dest), k, capacity,
                              valid=jnp.asarray(valid) if masked else None)
    assert int(b.peak) == want
    assert (int(b.dropped) > 0) == (want > capacity)
    if not masked:
        assert int(bucket_sorted_runs(jnp.asarray(data), jnp.asarray(dest), k, capacity).peak) == want


@SETTINGS
@given(n=st.integers(0, 200), m=st.integers(0, 200), seed=st.integers(0, 2**31 - 1))
def test_merge_two_sorted(n, m, seed):
    rng = np.random.default_rng(seed)
    a = np.sort(rng.integers(0, 1000, n)).astype(np.int32)
    b = np.sort(rng.integers(0, 1000, m)).astype(np.int32)
    out = np.asarray(merge_two_sorted(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(out, np.sort(np.concatenate([a, b]), kind="stable"))


@SETTINGS
@given(logk=st.integers(0, 3), run=st.integers(1, 64), seed=st.integers(0, 2**31 - 1))
def test_merge_sorted_runs(logk, run, seed):
    k = 1 << logk
    rng = np.random.default_rng(seed)
    runs = np.sort(rng.integers(0, 10_000, (k, run)), axis=1).astype(np.int32)
    out = np.asarray(merge_sorted_runs(jnp.asarray(runs)))
    np.testing.assert_array_equal(out, np.sort(runs.reshape(-1)))


def test_merge_sorted_runs_payload():
    rng = np.random.default_rng(0)
    keys = np.sort(rng.integers(0, 100, (4, 16)), axis=1).astype(np.int32)
    payload = keys * 7 + 1
    k, p = merge_sorted_runs(jnp.asarray(keys), jnp.asarray(payload))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(k) * 7 + 1)


# ---------------------------------------------------------------------------
# R-MAT / graph config invariants
# ---------------------------------------------------------------------------


@SETTINGS
@given(scale=st.integers(2, 24), count=st.integers(1, 2000), seed=st.integers(0, 2**31 - 1))
def test_rmat_ref_in_range_and_deterministic(scale, count, seed):
    cfg = GraphConfig(scale=scale, seed=seed)
    s1, d1 = ref.rmat_ref(cfg, 0, count)
    s2, d2 = ref.rmat_ref(cfg, 0, count)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert int(jnp.min(s1)) >= 0 and int(jnp.max(s1)) < cfg.n
    assert int(jnp.min(d1)) >= 0 and int(jnp.max(d1)) < cfg.n


def test_quadrant_thresholds_sum():
    cfg = GraphConfig()
    t_src, t_dst0, t_dst1 = quadrant_thresholds(cfg)
    # P(src=1) = c + d = 0.24
    assert abs(t_src / 2**32 - (cfg.c + cfg.d)) < 1e-6
    assert abs(t_dst0 / 2**32 - cfg.b / (cfg.a + cfg.b)) < 1e-6
    assert abs(t_dst1 / 2**32 - cfg.d / (cfg.c + cfg.d)) < 1e-6


@SETTINGS
@given(v=st.integers(0, 2**20 - 1), logb=st.integers(0, 20))
def test_owner_of(v, logb):
    B = 1 << logb
    assert int(owner_of(jnp.asarray(v), B)) == v // B


# ---------------------------------------------------------------------------
# straggler planning
# ---------------------------------------------------------------------------


@SETTINGS
@given(
    n=st.integers(1, 16),
    mb_per=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_straggler_plan_conserves_work(n, mb_per, seed):
    rng = np.random.default_rng(seed)
    times = rng.uniform(0.5, 5.0, n)
    policy = StragglerPolicy()
    micro = n * mb_per
    plan = policy.plan(times, micro)
    assert sum(plan) == micro
    assert all(p >= policy.min_share for p in plan)


def test_straggler_plan_shifts_work():
    policy = StragglerPolicy(slow_factor=1.5)
    times = [1.0, 1.0, 1.0, 10.0]   # worker 3 is 10x slower
    plan = policy.plan(times, 16)
    assert plan[3] < 4              # sheds load
    assert max(plan[:3]) > 4        # fast workers pick it up
    assert sum(plan) == 16


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


@SETTINGS
@given(seed=st.integers(0, 1000), step=st.integers(0, 100))
def test_sampling_greedy_and_topk(seed, step):
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal(50)
    assert sample(logits, SamplingParams(temperature=0.0), step) == int(np.argmax(logits))
    tok = sample(logits, SamplingParams(temperature=1.0, top_k=5, seed=seed), step)
    top5 = np.argsort(logits)[-5:]
    assert tok in top5
    # determinism
    tok2 = sample(logits, SamplingParams(temperature=1.0, top_k=5, seed=seed), step)
    assert tok == tok2
