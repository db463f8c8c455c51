"""Property-based tests (hypothesis) for the paper's core invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.metadata import create_store, record_accesses, record_new_keys
from repro.core.ownership import (
    eligible_hosts,
    max_coefficient,
    ownership_fraction,
    validate_coefficient,
)
from repro.core.placement import PlacementDaemon, sweep
from repro.core.costmodel import budget_plan

counts_strategy = st.integers(2, 24).flatmap(
    lambda n: st.integers(1, 64).flatmap(
        lambda k: st.lists(
            st.lists(st.integers(0, 1000), min_size=n, max_size=n),
            min_size=k,
            max_size=k,
        ).map(lambda rows: np.array(rows, np.float32))
    )
)


@settings(max_examples=40, deadline=None)
@given(counts_strategy)
def test_no_starvation(counts):
    """Eq. 3: with H <= 1/n every key with traffic keeps >= 1 eligible host."""
    n = counts.shape[1]
    h = max_coefficient(n)
    elig = np.asarray(eligible_hosts(jnp.asarray(counts), h))
    has_traffic = counts.sum(-1) > 0
    assert np.all(elig[has_traffic].any(-1)), "a live key lost all hosts"


@settings(max_examples=40, deadline=None)
@given(counts_strategy)
def test_fractions_sum_to_one(counts):
    f = np.asarray(ownership_fraction(jnp.asarray(counts)))
    s = f.sum(-1)
    has = counts.sum(-1) > 0
    np.testing.assert_allclose(s[has], 1.0, atol=1e-5)
    np.testing.assert_allclose(s[~has], 0.0, atol=1e-6)


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 32))
def test_uniform_traffic_qualifies_everyone(n):
    """Uniform access -> f = 1/n for all -> with H = 1/n all nodes qualify
    (the paper's degenerate-gracefully case for evenly-accessed objects)."""
    counts = jnp.full((5, n), 7.0)
    elig = np.asarray(eligible_hosts(counts, max_coefficient(n)))
    assert elig.all()


def test_validate_coefficient_bounds():
    validate_coefficient(0.25, 4)
    validate_coefficient(1.0 / 3.0, 3)
    with pytest.raises(ValueError):
        validate_coefficient(0.26, 4)  # H > 1/n violates eq. 3
    with pytest.raises(ValueError):
        validate_coefficient(0.0, 4)
    with pytest.raises(ValueError):
        validate_coefficient(0.1, 0)


@settings(max_examples=25, deadline=None)
@given(counts_strategy, st.floats(0.01, 0.5))
def test_sweep_invariants(counts, h_frac):
    """Algorithm 3 output invariants for any traffic matrix."""
    k, n = counts.shape
    h = min(h_frac, 1.0 / n)
    store = create_store(k, n)
    hosts = counts > np.median(counts)  # arbitrary current placement
    store = store._replace(
        access_counts=jnp.asarray(counts, jnp.int32),
        hosts=jnp.asarray(hosts),
        live=jnp.ones((k,), bool),
    )
    plan, new_store = sweep(store, h, now=0)
    owners = np.asarray(plan.owners)
    to_add = np.asarray(plan.to_add)
    to_drop = np.asarray(plan.to_drop)
    # adds and drops are disjoint and consistent with owners/current hosts
    assert not np.any(to_add & to_drop)
    assert np.all(to_add <= owners)
    assert np.all(to_add <= ~hosts)
    assert np.all(to_drop <= hosts)
    np.testing.assert_array_equal(owners, (hosts | to_add) & ~to_drop)
    # keys with traffic keep at least one replica (no starvation)
    has = counts.sum(-1) > 0
    assert np.all(owners[has].any(-1))
    # silence = no churn
    silent = ~has
    np.testing.assert_array_equal(owners[silent], hosts[silent])


def test_sweep_expiry():
    store = create_store(4, 3)
    store = store._replace(
        hosts=jnp.ones((4, 3), bool),
        live=jnp.ones((4,), bool),
        last_access=jnp.asarray([0, 50, 99, 100], jnp.int32),
    )
    plan, new_store = sweep(store, 1 / 3, now=100, expiry=10)
    np.testing.assert_array_equal(
        np.asarray(plan.expired), [True, True, False, False]
    )
    assert not np.asarray(new_store.live)[0]
    assert not np.asarray(plan.owners)[0].any()


@settings(max_examples=25, deadline=None)
@given(counts_strategy)
def test_budget_plan_infinite_is_identity(counts):
    k, n = counts.shape
    store = create_store(k, n)
    store = store._replace(
        access_counts=jnp.asarray(counts, jnp.int32),
        live=jnp.ones((k,), bool),
    )
    plan, _ = sweep(store, 1.0 / n, now=0)
    obj_bytes = jnp.ones((k,)) * 100.0
    trimmed = budget_plan(plan, jnp.asarray(counts), obj_bytes, float("inf"))
    np.testing.assert_array_equal(np.asarray(trimmed.to_add), np.asarray(plan.to_add))


def test_budget_plan_respects_budget():
    k, n = 10, 2
    counts = jnp.asarray(np.arange(k * n).reshape(k, n), jnp.float32)
    store = create_store(k, n)._replace(
        access_counts=jnp.asarray(np.arange(k * n).reshape(k, n), jnp.int32),
        live=jnp.ones((k,), bool),
    )
    plan, _ = sweep(store, 1.0 / n, now=0)
    obj_bytes = jnp.full((k,), 100.0)
    trimmed = budget_plan(plan, counts, obj_bytes, node_budget_bytes=250.0)
    per_node = np.asarray(trimmed.to_add).sum(0) * 100.0
    assert np.all(per_node <= 250.0)


def test_metadata_record_roundtrip():
    store = create_store(8, 3)
    keys = jnp.asarray([0, 1, 1, 7], jnp.int32)
    nodes = jnp.asarray([0, 1, 1, 2], jnp.int32)
    store = record_new_keys(store, keys, nodes, now=5)
    assert bool(store.live[0]) and bool(store.live[7]) and not bool(store.live[3])
    assert int(store.access_counts[1, 1]) == 2
    assert int(store.total_access_count()[1]) == 2
    store = record_accesses(store, keys, nodes, now=9)
    assert int(store.access_counts[1, 1]) == 4
    assert int(store.last_access[7]) == 9


def _flat_fold(counts, last, keys, nodes, now, weights, valid):
    """The reference fold: scatter into the counts flattened row-major, by
    ``key * N + node``, then fold them back to ``[K, N]``."""
    k, n = counts.shape
    if weights is None:
        weights = jnp.ones_like(keys, dtype=jnp.int32)
    sel = keys
    if valid is not None:
        weights = jnp.where(valid, weights, 0)
        sel = jnp.where(valid, keys, k)
    flat = sel.astype(jnp.int32) * n + nodes.astype(jnp.int32)
    counts = counts.reshape(-1).at[flat].add(
        weights.astype(jnp.int32), mode="drop"
    )
    last = last.at[sel].max(jnp.asarray(now, dtype=jnp.int32), mode="drop")
    return counts.reshape(k, n), last


@pytest.mark.parametrize(
    "k, n, b, weighted, masked, seed",
    [
        (1, 1, 8, False, False, 0),
        (16, 5, 64, False, False, 1),
        (16, 5, 64, True, True, 2),
        (97, 3, 500, True, False, 3),
        (97, 3, 500, False, True, 4),
        (1000, 5, 4096, True, True, 5),
    ],
)
def test_record_accesses_matches_the_flat_fold(k, n, b, weighted, masked, seed):
    """The fold in the counts' own ``[K, N]`` shape gives, bit for bit, what
    the flat-index fold gave: repeated ``(key, node)`` pairs, keys at K - 1,
    weights, and rows a ``valid`` mask drops."""
    rng = np.random.default_rng(seed)
    # Half the rows on the last four keys, so pairs repeat at K - 1.
    keys = rng.integers(0, k, size=b).astype(np.int32)
    hot = rng.random(b) < 0.5
    keys[hot] = rng.integers(max(k - 4, 0), k, size=int(hot.sum()))
    keys[0] = k - 1
    nodes = rng.integers(0, n, size=b).astype(np.int32)
    weights = rng.integers(0, 7, size=b).astype(np.int32) if weighted else None
    valid = rng.random(b) < 0.7 if masked else None
    store = create_store(k, n)._replace(
        access_counts=jnp.asarray(rng.integers(0, 50, size=(k, n)), jnp.int32),
        last_access=jnp.asarray(rng.integers(0, 20, size=k), jnp.int32),
    )
    args = (
        jnp.asarray(keys), jnp.asarray(nodes), 21,
        None if weights is None else jnp.asarray(weights),
        None if valid is None else jnp.asarray(valid),
    )
    got = record_accesses(store, *args)
    want_counts, want_last = _flat_fold(
        store.access_counts, store.last_access, *args
    )
    np.testing.assert_array_equal(np.asarray(got.access_counts), want_counts)
    np.testing.assert_array_equal(np.asarray(got.last_access), want_last)
    assert got.access_counts.dtype == jnp.int32


def test_record_accesses_folds_the_counts_without_a_reshape():
    """The lowered fold scatters into the ``[K, N]`` counts in their own
    shape: no ``reshape`` takes them to or from a flat ``[K * N]`` view,
    which on a TPU makes XLA relay them through a lane-padded layout."""
    k, n, b = 1000, 5, 64
    store = create_store(k, n)
    text = jax.jit(record_accesses).lower(
        store, jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.int32(3), jnp.ones((b,), jnp.int32), jnp.ones((b,), bool),
    ).as_text()
    assert "stablehlo.scatter" in text
    reshapes = [line for line in text.splitlines() if "stablehlo.reshape" in line]
    counts_types = (f"tensor<{k}x{n}xi32>", f"tensor<{k * n}xi32>")
    assert not [r for r in reshapes if any(t in r for t in counts_types)], reshapes
