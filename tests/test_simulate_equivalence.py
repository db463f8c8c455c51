"""Regression guard for the scan-fused simulation engine: the single
``lax.scan`` program (`run_scenario`) must match the retained per-chunk
Python reference loop (`run_scenario_reference`) field-for-field, and the
degenerate flat-RTT topology must reproduce the seed Fig 2/3 numbers."""

import numpy as np
import pytest

from repro.kvsim import (
    ClusterConfig,
    RedynisPolicy,
    SimResult,
    StaticPolicy,
    WorkloadConfig,
    flat_rtt,
    run_scenario,
    run_scenario_reference,
    wan5_cluster,
    wan5_workload,
)

# Reference accumulates busy-time in float64 host-side, the fused engine in
# float32 on device: allclose, not bit-identical.
RTOL = 1e-4

# The four seed-era baselines, as policies (the legacy Scenario spellings).
BASELINES = {
    "local": StaticPolicy(mode="local"),
    "remote": StaticPolicy(mode="remote"),
    "optimized": RedynisPolicy(),
    "replicated": StaticPolicy(mode="replicated"),
}


def assert_results_match(a: SimResult, b: SimResult, ctx: str = ""):
    for field, x, y in zip(SimResult._fields, a, b):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=RTOL, err_msg=f"{ctx} {field}"
        )


@pytest.mark.parametrize("name", sorted(BASELINES))
def test_scan_matches_reference_all_scenarios(name):
    policy = BASELINES[name]
    wl = WorkloadConfig(num_requests=4_000, num_keys=200, skewed=True)
    cl = ClusterConfig()
    a = run_scenario(wl, cl, policy, seed=2, daemon_interval=500)
    b = run_scenario_reference(wl, cl, policy, seed=2, daemon_interval=500)
    assert_results_match(a, b, name)


def test_scan_matches_reference_padded_trace():
    """Trace length not divisible by daemon_interval exercises the fixed-shape
    padding (valid-masked) path of the fused engine."""
    wl = WorkloadConfig(num_requests=3_300, num_keys=150)
    cl = ClusterConfig()
    a = run_scenario(wl, cl, RedynisPolicy(), seed=1, daemon_interval=500)
    b = run_scenario_reference(wl, cl, RedynisPolicy(), seed=1, daemon_interval=500)
    assert_results_match(a, b, "padded")


def test_scan_matches_reference_wan5_topology():
    wl = wan5_workload(num_requests=4_000, num_keys=200)
    cl = wan5_cluster()
    a = run_scenario(wl, cl, RedynisPolicy(), seed=0, daemon_interval=500)
    b = run_scenario_reference(wl, cl, RedynisPolicy(), seed=0, daemon_interval=500)
    assert_results_match(a, b, "wan5")


def test_scan_matches_reference_finite_capacity():
    """Finite per-node replica budgets + a lognormal object-size distribution
    exercise the capacity-projection stage inside the scan body; the fused
    engine must still match the per-chunk oracle on every metric, including
    the new eviction/occupancy fields."""
    wl = WorkloadConfig(
        num_requests=4_000, num_keys=200, skewed=True, object_bytes_sigma=0.5
    )
    cl = ClusterConfig(capacity_bytes=24 * 1024.0)
    a = run_scenario(wl, cl, RedynisPolicy(), seed=2, daemon_interval=500)
    b = run_scenario_reference(wl, cl, RedynisPolicy(), seed=2, daemon_interval=500)
    assert_results_match(a, b, "capacity")
    assert a.capacity_evictions > 0


def test_scan_matches_reference_heterogeneous_capacity():
    """wan5 with one small edge node (heterogeneous budget tuple)."""
    from repro.kvsim import wan5_edge_cluster

    wl = wan5_workload(num_requests=4_000, num_keys=200)
    cl = wan5_edge_cluster(edge_capacity_bytes=8 * 1024.0)
    a = run_scenario(wl, cl, RedynisPolicy(), seed=0, daemon_interval=500)
    b = run_scenario_reference(wl, cl, RedynisPolicy(), seed=0, daemon_interval=500)
    assert_results_match(a, b, "wan5-edge")


def test_scan_matches_reference_daemon_options():
    """Expiry + decay + non-unit period take the due-masked branch of
    `masked_step`; they must still match the host-side daemon exactly."""
    wl = WorkloadConfig(num_requests=4_000, num_keys=200, skewed=True, affinity=0.8)
    cl = ClusterConfig()
    policy = RedynisPolicy(
        h=0.2,
        expiry=4,
        decay=0.5,
        period=2,  # odd chunks take masked_step's not-due branch
    )
    a = run_scenario(wl, cl, policy, seed=3, daemon_interval=250)
    b = run_scenario_reference(wl, cl, policy, seed=3, daemon_interval=250)
    assert_results_match(a, b, "daemon-options")


def test_masked_step_not_due_is_identity():
    """The scan-compatible daemon step must leave the store untouched and
    report zero moves on off ticks (the branch period>1 schedules exercise)."""
    import jax.numpy as jnp

    from repro.core.metadata import create_store, record_accesses
    from repro.core.placement import masked_step

    store = create_store(8, 3)._replace(live=jnp.ones((8,), bool))
    store = record_accesses(
        store, jnp.arange(8, dtype=jnp.int32), jnp.zeros((8,), jnp.int32), now=1
    )
    stats, out = masked_step(
        store, 2, jnp.bool_(False), h=1 / 3, expiry=5, decay=0.5
    )
    assert all(float(v) == 0.0 for v in stats), stats
    for field, a, b in zip(store._fields, store, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=field)


def test_flat_rtt_tuple_is_degenerate_topology():
    """An explicit flat [N, N] matrix must be indistinguishable from the
    legacy remote_ms/local_ms constants (the paper's testbed model)."""
    wl = WorkloadConfig(num_requests=5_000)
    implicit = ClusterConfig()
    explicit = ClusterConfig(rtt=flat_rtt(3, 100.0, 0.0))
    for name, policy in BASELINES.items():
        a = run_scenario(wl, implicit, policy, seed=0)
        b = run_scenario(wl, explicit, policy, seed=0)
        assert a.throughput_ops_s == b.throughput_ops_s, name
        assert a.hit_rate == b.hit_rate, name
        np.testing.assert_array_equal(a.node_busy_ms, b.node_busy_ms)


# Seed goldens: the pre-refactor engine's outputs on the default flat config
# (WorkloadConfig(num_requests=20_000), ClusterConfig(), seed=0). Pinning
# these guarantees the RTT-matrix generalisation reproduces the repo's
# original Fig 2/3 numbers as the degenerate topology. Re-derived once for
# jax's partitionable threefry stream (the default since jax 0.5), which
# draws a different trace from the same seed; run_scenario_reference gives
# the same values.
SEED_GOLDENS = {
    "local": (281.49190710767067, 1.0, 10.0, 0.0),
    "remote": (25.590173373424605, 0.0, 110.0, 0.0),
    "optimized": (161.35538523598225, 0.92155, 17.845, 1000.0),
    "replicated": (281.49190710767067, 1.0, 10.0, 0.0),
}


@pytest.mark.parametrize("name", sorted(SEED_GOLDENS))
def test_flat_topology_reproduces_seed_goldens(name):
    wl = WorkloadConfig(num_requests=20_000)
    r = run_scenario(wl, ClusterConfig(), BASELINES[name], seed=0)
    tput, hit, mean_lat, moves = SEED_GOLDENS[name]
    np.testing.assert_allclose(r.throughput_ops_s, tput, rtol=1e-5)
    np.testing.assert_allclose(r.hit_rate, hit, rtol=1e-5)
    np.testing.assert_allclose(r.mean_latency_ms, mean_lat, rtol=1e-5)
    np.testing.assert_allclose(r.replication_moves, moves, rtol=0)
