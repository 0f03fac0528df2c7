"""Hypothesis property tests for the keyed invertible Feistel permutation
family (core/hostgen.py) and its jax/Pallas twins — the recompute shuffle's
correctness hinges on exactly these invariants:

  * feistel_perm_np is a BIJECTION on [0, 2**nbits) for every key/rounds,
    and feistel_perm_inv_np inverts it exactly;
  * keyed_perm_np cycle-walks any non-power-of-two [0, n) to a permutation
    (termination is a theorem — the walk traverses a cycle of a bijection
    on the covering power of two — but we assert it empirically too);
  * the three containers (numpy uint64, jnp uint32, Pallas int32 lanes)
    agree bit for bit on their shared domains, across input dtypes.
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.hostgen import (
    FEISTEL_ROUNDS,
    feistel_perm_inv_np,
    feistel_perm_np,
    graph_perm_inv_np,
    graph_perm_key,
    graph_perm_np,
    keyed_perm_inv_np,
    keyed_perm_np,
    perm_domain_bits,
)

SETTINGS = settings(max_examples=40, deadline=None)
KEYS = st.integers(0, 2**32 - 1)
EVEN_ROUNDS = st.sampled_from([2, 4, 6, 8])


# ---------------------------------------------------------------------------
# numpy family: bijectivity + inverse
# ---------------------------------------------------------------------------


@SETTINGS
@given(key=KEYS, nbits=st.integers(1, 12), rounds=EVEN_ROUNDS)
def test_feistel_full_bijection_small_domains(key, nbits, rounds):
    x = np.arange(1 << nbits, dtype=np.uint64)
    y = feistel_perm_np(x, key, nbits, rounds=rounds)
    assert y.dtype == np.uint64
    # bijection on the full domain: output is a permutation of the input
    np.testing.assert_array_equal(np.sort(y), x)
    np.testing.assert_array_equal(feistel_perm_inv_np(y, key, nbits,
                                                      rounds=rounds), x)


@SETTINGS
@given(key=KEYS, nbits=st.integers(1, 62), seed=st.integers(0, 2**31 - 1),
       rounds=EVEN_ROUNDS)
def test_feistel_inverse_round_trip_sampled(key, nbits, seed, rounds):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 1 << nbits, 257, dtype=np.uint64)
    y = feistel_perm_np(x, key, nbits, rounds=rounds)
    assert int(y.max(initial=0)) < (1 << nbits)
    np.testing.assert_array_equal(feistel_perm_inv_np(y, key, nbits,
                                                      rounds=rounds), x)


@SETTINGS
@given(key=KEYS, rounds=st.sampled_from([0, 1, 3, 5, -2]))
def test_feistel_rejects_odd_or_tiny_rounds(key, rounds):
    with pytest.raises(ValueError):
        feistel_perm_np(np.arange(4, dtype=np.uint64), key, 2, rounds=rounds)


# ---------------------------------------------------------------------------
# cycle-walking: arbitrary domains
# ---------------------------------------------------------------------------


@SETTINGS
@given(key=KEYS, n=st.integers(1, 5000), rounds=EVEN_ROUNDS)
def test_cycle_walk_is_permutation_and_terminates(key, n, rounds):
    x = np.arange(n, dtype=np.int64)
    y = keyed_perm_np(x, key, n, rounds=rounds)   # termination: returns at all
    assert y.dtype == np.int64
    np.testing.assert_array_equal(np.sort(y), x)
    np.testing.assert_array_equal(keyed_perm_inv_np(y, key, n, rounds=rounds), x)


@SETTINGS
@given(key=KEYS, n=st.integers(1, 5000))
def test_cycle_walk_rejects_out_of_range(key, n):
    with pytest.raises(ValueError):
        keyed_perm_np(np.asarray([n], np.int64), key, n)
    with pytest.raises(ValueError):
        keyed_perm_np(np.asarray([-1], np.int64), key, n)


@SETTINGS
@given(seed=st.integers(0, 2**32 - 1), scale=st.integers(1, 16))
def test_graph_perm_matches_keyed_perm(seed, scale):
    n = 1 << scale
    x = np.arange(0, n, max(1, n // 64), dtype=np.int64)
    np.testing.assert_array_equal(
        graph_perm_np(seed, x, n),
        keyed_perm_np(x, graph_perm_key(seed), n))
    y = graph_perm_np(seed, x, n)
    np.testing.assert_array_equal(graph_perm_inv_np(seed, y, n), x)


# ---------------------------------------------------------------------------
# twin agreement: numpy / jnp / Pallas
# ---------------------------------------------------------------------------


@SETTINGS
@given(key=KEYS, nbits=st.integers(1, 32), seed=st.integers(0, 2**31 - 1),
       rounds=EVEN_ROUNDS,
       dtype=st.sampled_from([np.int64, np.uint64, np.int32, np.uint32]))
def test_numpy_jnp_twins_agree(key, nbits, seed, rounds, dtype):
    from repro.core.shuffle import feistel_perm

    rng = np.random.default_rng(seed)
    hi = min(1 << nbits, np.iinfo(dtype).max)
    x = rng.integers(0, max(1, hi), 129).astype(dtype)
    want = feistel_perm_np(x.astype(np.uint64), key, nbits, rounds=rounds)
    got = np.asarray(feistel_perm(np.asarray(x, np.uint32), key, nbits,
                                  rounds=rounds), np.uint64)
    np.testing.assert_array_equal(got, want)


@SETTINGS
@given(key=KEYS, n=st.integers(2, 5000), seed=st.integers(0, 2**31 - 1))
def test_numpy_jnp_cycle_walk_agree_non_power_of_two(key, n, seed):
    from repro.core.shuffle import keyed_perm

    rng = np.random.default_rng(seed)
    x = rng.integers(0, n, 65, dtype=np.int64)
    want = keyed_perm_np(x, key, n)
    got = np.asarray(keyed_perm(np.asarray(x, np.uint32), key, n), np.int64)
    np.testing.assert_array_equal(got, want)


@SETTINGS
@given(key=KEYS, nbits=st.integers(10, 14), seed=st.integers(0, 2**31 - 1))
def test_pallas_twin_agrees_on_power_of_two_tiles(key, nbits, seed):
    from repro.kernels.rmat import TILE, feistel_perm_pallas

    rng = np.random.default_rng(seed)
    x = rng.integers(0, 1 << nbits, TILE, dtype=np.int32)
    want = feistel_perm_np(x.astype(np.uint64), key, nbits)
    got = np.asarray(feistel_perm_pallas(np.asarray(x), key, nbits, interpret=True), np.uint64)
    np.testing.assert_array_equal(got, want)


def test_perm_domain_bits():
    assert perm_domain_bits(1) == 1
    assert perm_domain_bits(2) == 1
    assert perm_domain_bits(3) == 2
    assert perm_domain_bits(1 << 20) == 20
    assert perm_domain_bits((1 << 20) + 1) == 21
    assert FEISTEL_ROUNDS % 2 == 0 and FEISTEL_ROUNDS >= 2
