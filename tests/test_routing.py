"""Routing-tier test tier (ISSUE-8 acceptance).

Pins the stale-directory routing tier (``RoutingConfig`` — router-site
ownership caches, versioned lagged publishes, mis-route pricing):

1. Routing OFF (``routing=None`` and ``RoutingConfig(enabled=False)``)
   compiles the exact pre-routing program — bit-identical results across
   both engines × both replay backends × both trace modes, still
   reproducing the seed Fig 2/3 goldens.
2. Kernel ⇄ reference parity: the Pallas chunk-replay kernel fed the
   canonical ``routing_extra_ms_ref`` pre-pass output must agree with the
   jnp oracle across topologies × read modes — histograms bit-exact,
   busy/lat_sum allclose — plus the pre-pass's own outcome invariants
   (fresh consults are free, misses fetch, flags are consistent).
3. Zero lag + unbounded cache ⇒ every consult prices at exactly 0.0 and
   the engine results are bit-identical to the no-routing run (the
   ``lat + 0.0`` identity).
4. Staleness axis: mis-routes and mean latency are monotone in
   ``publish_lag_chunks``; shrinking ``cache_entries`` only adds
   directory fetches; ``cache_entries >= K`` collapses to the unbounded
   cache program.
5. Engine agreement with routing ON: fused scan == per-chunk reference ==
   Pallas replay == streamed traces (counts bit-exact, latency allclose),
   and the telemetry per-chunk series sum to the aggregate counters.
6. 2-rank ``shard_map`` equivalence with routing on (``run_multi_rank``).

Hypothesis (when installed) fuzzes the pre-pass invariants over random
maps, published views, and cache states.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chunk_replay.ops import chunk_replay
from repro.kernels.chunk_replay.ref import (
    READ_MODES,
    chunk_replay_ref,
    routing_extra_ms_ref,
)
from repro.kvsim import (
    ClusterConfig,
    RedynisPolicy,
    RoutingConfig,
    SimResult,
    StaticPolicy,
    TelemetryConfig,
    WorkloadConfig,
    diurnal_workload,
    normalize_routing,
    run_scenario,
    run_scenario_reference,
    wan5_cluster,
    wan5_edge_cluster,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


TOPOLOGIES = {
    "flat": ClusterConfig().rtt_matrix(),
    "wan5": wan5_cluster().rtt_matrix(),
    "wan5_edge": wan5_edge_cluster().rtt_matrix(),
}

BASELINES = {
    "local": StaticPolicy(mode="local"),
    "remote": StaticPolicy(mode="remote"),
    "optimized": RedynisPolicy(),
    "replicated": StaticPolicy(mode="replicated"),
}

# The seed Fig 2/3 goldens (see tests/test_simulate_equivalence.py) — the
# routing tier must leave them untouched while it is off.
SEED_GOLDENS = {
    "local": (281.49190710767067, 1.0, 10.0, 0.0),
    "remote": (25.590173373424605, 0.0, 110.0, 0.0),
    "optimized": (161.35538523598225, 0.92155, 17.845, 1000.0),
    "replicated": (281.49190710767067, 1.0, 10.0, 0.0),
}

ENGINES = [
    ("scan-jax-materialized", lambda wl, cl, pol: run_scenario(
        wl, cl, pol, seed=0)),
    ("scan-jax-streamed", lambda wl, cl, pol: run_scenario(
        wl, cl, pol, seed=0, trace_mode="streamed")),
    ("scan-pallas-materialized", lambda wl, cl, pol: run_scenario(
        wl, cl, pol, seed=0, replay_backend="pallas")),
    ("scan-pallas-streamed", lambda wl, cl, pol: run_scenario(
        wl, cl, pol, seed=0, replay_backend="pallas",
        trace_mode="streamed")),
    ("reference", lambda wl, cl, pol: run_scenario_reference(
        wl, cl, pol, seed=0)),
]


def assert_results_equal(a: SimResult, b: SimResult, ctx: str):
    for field, x, y in zip(SimResult._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{ctx} {field}"
        )


# A staleness-rich scenario: diurnal hotset rotation keeps the daemon
# moving keys that are still being read cross-region, so lagged publishes
# genuinely mis-route (affinity < 1 creates the non-local consult stream).
def _staleness_scenario():
    return (
        diurnal_workload(
            num_requests=20_000, num_keys=400, affinity=0.8,
            read_fraction=0.7,
        ),
        wan5_cluster(),
    )


STALE_INTERVAL = 100


# ---------------------------------------------------------------------------
# 1. Routing off is a structural no-op: seed goldens stay bit-exact.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(BASELINES))
@pytest.mark.parametrize("engine", [e[0] for e in ENGINES])
def test_routing_off_is_bitexact_and_reproduces_goldens(name, engine):
    """routing=None and RoutingConfig(enabled=False) are the SAME static
    (normalize_routing collapses both), so the compiled program — and every
    result bit — is identical to the pre-routing engine, which the seed
    goldens pin."""
    run = dict(ENGINES)[engine]
    wl = WorkloadConfig(num_requests=20_000)
    plain = run(wl, ClusterConfig(), BASELINES[name])
    disabled = run(
        wl, ClusterConfig(routing=RoutingConfig(enabled=False)),
        BASELINES[name],
    )
    assert_results_equal(plain, disabled, f"{engine}/{name}")
    assert plain.router_consults == 0.0
    assert plain.mis_routes == 0.0
    tput, hit, mean_lat, moves = SEED_GOLDENS[name]
    np.testing.assert_allclose(plain.throughput_ops_s, tput, rtol=1e-4)
    np.testing.assert_allclose(plain.hit_rate, hit, rtol=1e-5)
    np.testing.assert_allclose(plain.mean_latency_ms, mean_lat, rtol=1e-4)
    np.testing.assert_allclose(plain.replication_moves, moves, rtol=0)


# ---------------------------------------------------------------------------
# 2. Kernel ⇄ reference parity: routing extra_ms through the Pallas kernel.
# ---------------------------------------------------------------------------


def _random_routed_chunk(seed, b, k, n, move_fraction=0.15):
    """Random authoritative map + a published view that re-homed a slice of
    the keys + random cache/freshness state (the engine always derives
    fresh ⊆ cached; the pre-pass must hold up under that invariant)."""
    rng = np.random.default_rng(seed)
    hosts = rng.random((k, n)) < 0.4
    pub = hosts.copy()
    moved = rng.random(k) < move_fraction
    pub[moved] = rng.random((int(moved.sum()), n)) < 0.4
    cached = rng.random(b) < 0.7
    fresh = cached & (rng.random(b) < 0.6)
    return (
        jnp.asarray(hosts),
        jnp.asarray(pub),
        jnp.asarray(cached),
        jnp.asarray(fresh),
        jnp.asarray(rng.integers(0, k, b).astype(np.int32)),
        jnp.asarray(rng.integers(0, n, b).astype(np.int32)),
        jnp.asarray(rng.random(b) < 0.8),  # is_read
        jnp.asarray(rng.random(b) < 0.9),  # valid (padding path)
    )


def check_routed_kernel_matches_ref(
    rtt, seed, b, k, read_mode="map", home_node=0, tr=256, tkey=128
):
    n = rtt.shape[0]
    hosts, pub, cached, fresh, keys, nodes, is_read, valid = (
        _random_routed_chunk(seed, b, k, n)
    )
    extra, consult, fetches, stale, mis = routing_extra_ms_ref(
        hosts, pub, cached, fresh, keys, nodes, is_read, valid, rtt,
        read_mode=read_mode, home_node=home_node,
    )
    # Outcome invariants of the canonical pre-pass.
    consult_n, fetch_n = np.asarray(consult), np.asarray(fetches)
    stale_n, mis_n = np.asarray(stale), np.asarray(mis)
    cached_n, fresh_n = np.asarray(cached), np.asarray(fresh)
    extra_n = np.asarray(extra)
    assert not np.any(fetch_n & ~consult_n)
    assert not np.any(stale_n & ~consult_n)
    assert not np.any(mis_n & ~consult_n)
    assert not np.any(fetch_n & cached_n)
    assert not np.any(stale_n & ~cached_n)
    assert not np.any(mis_n & fresh_n)
    # Fresh (or non-consulting) requests are free; the real topologies are
    # metric, so detours and fetches can only add latency.
    assert np.all(extra_n[fresh_n | ~consult_n] == 0.0)
    assert np.all(extra_n >= 0.0)
    kw = dict(
        service_ms=10.0, master=0, xfer_read_ms=2.0, xfer_write_ms=3.0,
        read_mode=read_mode, num_bins=64, lo=1.0, hi=5_000.0,
    )
    ref = chunk_replay_ref(
        hosts, keys, nodes, is_read, valid, rtt, extra_ms=extra, **kw
    )
    ker = chunk_replay(
        hosts, keys, nodes, is_read, valid, rtt, extra_ms=extra,
        backend="pallas", tr=tr, tkey=tkey, interpret=True, **kw,
    )
    np.testing.assert_allclose(
        np.asarray(ker[0]), np.asarray(ref[0]), rtol=1e-5, err_msg="busy"
    )
    np.testing.assert_allclose(
        float(ker[1]), float(ref[1]), rtol=1e-5, err_msg="lat_sum"
    )
    for i, name in ((2, "hits"), (3, "reads"), (4, "count")):
        assert float(ker[i]) == float(ref[i]), (name, ker[i], ref[i])
    # The kernel adds extra_ms in the oracle's elementwise position, so the
    # mis-routed f32 latency bits — and the histogram buckets — match.
    np.testing.assert_array_equal(np.asarray(ker[5]), np.asarray(ref[5]))


PARITY_GRID = [
    (topo, mode, home)
    for topo in TOPOLOGIES
    for mode in READ_MODES
    for home in (0, 2)
]


@pytest.mark.parametrize(
    "topo,mode,home", PARITY_GRID,
    ids=[f"{t}-{m}-home{h}" for t, m, h in PARITY_GRID],
)
def test_routed_kernel_matches_ref(topo, mode, home):
    check_routed_kernel_matches_ref(
        TOPOLOGIES[topo], seed=hash((topo, mode, home)) % 2**32,
        b=777, k=333, read_mode=mode, home_node=home,
    )


if HAVE_HYPOTHESIS:
    routed_strategy = st.tuples(
        st.integers(0, 2**31 - 1),  # numpy seed
        st.integers(1, 400),  # b requests
        st.integers(1, 200),  # k keys
        st.sampled_from(sorted(TOPOLOGIES)),
        st.sampled_from(READ_MODES),
    )

    @settings(max_examples=30, deadline=None)
    @given(routed_strategy)
    def test_routed_pre_pass_fuzz(params):
        """The pre-pass invariants over random maps/views/cache states."""
        seed, b, k, topo, mode = params
        rtt = TOPOLOGIES[topo]
        n = rtt.shape[0]
        check_routed_kernel_matches_ref(
            rtt, seed=seed, b=b, k=k, read_mode=mode,
            home_node=seed % n,
        )


# ---------------------------------------------------------------------------
# 3. Zero lag + unbounded cache is the bit-exact identity.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["optimized", "local"])
def test_zero_lag_unbounded_cache_is_identity(name):
    """L=0 publishes instantly and the warm cache never misses, so every
    consult prices at exactly 0.0 — and lat + 0.0 is a bit-exact f32
    identity on the engine's positive latencies."""
    wl = WorkloadConfig(num_requests=20_000)
    off = run_scenario(wl, ClusterConfig(), BASELINES[name], seed=0)
    on = run_scenario(
        wl, ClusterConfig(routing=RoutingConfig()), BASELINES[name], seed=0
    )
    for field in (
        "throughput_ops_s", "hit_rate", "mean_latency_ms", "node_busy_ms",
        "replication_moves", "deletion_moves", "evictions",
        "capacity_evictions", "peak_occupancy_bytes",
    ):
        np.testing.assert_array_equal(
            np.asarray(getattr(off, field)), np.asarray(getattr(on, field)),
            err_msg=field,
        )
    assert on.mis_routes == 0.0
    assert on.directory_fetches == 0.0
    if name == "optimized":
        assert on.router_consults > 0.0


# ---------------------------------------------------------------------------
# 4. The staleness/consistency axis.
# ---------------------------------------------------------------------------


def test_mis_routes_monotone_in_publish_lag():
    """More propagation lag can only widen the window in which routers
    hold moved keys' old owners: mis-routes, stale consults, and mean
    latency are non-decreasing along the lag ladder (strictly more
    mis-routes at the far end)."""
    wl, cl = _staleness_scenario()
    rows = []
    for lag in (0, 2, 8, 32):
        r = run_scenario(
            wl, cl._replace(routing=RoutingConfig(publish_lag_chunks=lag)),
            RedynisPolicy(), seed=0, daemon_interval=STALE_INTERVAL,
        )
        rows.append((lag, r))
    for (_, a), (_, b) in zip(rows, rows[1:]):
        assert b.mis_routes >= a.mis_routes
        assert b.stale_consults >= a.stale_consults
        assert b.mean_latency_ms >= a.mean_latency_ms
        assert b.router_consults == a.router_consults
    assert rows[0][1].mis_routes == 0.0
    assert rows[-1][1].mis_routes > rows[0][1].mis_routes


def test_smaller_cache_only_adds_fetches():
    """Shrinking cache_entries converts consults into directory fetches
    (monotonically costlier) without changing WHICH requests mis-route —
    staleness is a property of the publish lag, not the cache; and a cache
    at/above the keyspace is the unbounded program, bit-exactly."""
    wl, cl = _staleness_scenario()

    def run(entries):
        return run_scenario(
            wl,
            cl._replace(routing=RoutingConfig(
                publish_lag_chunks=4, cache_entries=entries, decay=0.9,
            )),
            RedynisPolicy(), seed=0, daemon_interval=STALE_INTERVAL,
        )

    unbounded = run(0)
    at_k = run(wl.num_keys)
    assert_results_equal(unbounded, at_k, "cache>=K collapse")
    assert unbounded.directory_fetches == 0.0
    prev = unbounded
    for entries in (50, 10):
        r = run(entries)
        assert r.directory_fetches > prev.directory_fetches
        assert r.mean_latency_ms > prev.mean_latency_ms
        assert r.mis_routes == unbounded.mis_routes
        prev = r


def test_routing_validation():
    with pytest.raises(ValueError, match="num_routers"):
        RoutingConfig(num_routers=-1).validate()
    with pytest.raises(ValueError, match="cache_entries"):
        RoutingConfig(cache_entries=-1).validate()
    with pytest.raises(ValueError, match="publish_lag_chunks"):
        RoutingConfig(publish_lag_chunks=-1).validate()
    with pytest.raises(ValueError, match="decay"):
        RoutingConfig(decay=0.0).validate()
    assert normalize_routing(None) is None
    assert normalize_routing(RoutingConfig(enabled=False)) is None
    assert normalize_routing(RoutingConfig()) == RoutingConfig()
    wl = WorkloadConfig(num_requests=100)
    with pytest.raises(ValueError, match="home_node"):
        run_scenario(
            wl, ClusterConfig(routing=RoutingConfig(home_node=7)),
            RedynisPolicy(), seed=0,
        )
    with pytest.raises(ValueError, match="num_routers"):
        run_scenario(
            wl, ClusterConfig(routing=RoutingConfig(num_routers=9)),
            RedynisPolicy(), seed=0,
        )


# ---------------------------------------------------------------------------
# 5. Engine agreement with routing ON + telemetry consistency.
# ---------------------------------------------------------------------------


def test_engines_agree_with_routing_on():
    wl, cl = _staleness_scenario()
    cfg = cl._replace(routing=RoutingConfig(
        publish_lag_chunks=8, cache_entries=50, decay=0.9, home_node=2,
    ))
    kw = dict(seed=0, daemon_interval=STALE_INTERVAL)
    runs = {
        "jax": run_scenario(wl, cfg, RedynisPolicy(), **kw),
        "pallas": run_scenario(
            wl, cfg, RedynisPolicy(), replay_backend="pallas", **kw
        ),
        "streamed": run_scenario(
            wl, cfg, RedynisPolicy(), trace_mode="streamed", **kw
        ),
        "reference": run_scenario_reference(wl, cfg, RedynisPolicy(), **kw),
    }
    base = runs["jax"]
    assert base.mis_routes > 0.0 and base.directory_fetches > 0.0
    for name, r in runs.items():
        # Counts are integer surfaces: bit-exact across all engines.
        assert r.router_consults == base.router_consults, name
        assert r.directory_fetches == base.directory_fetches, name
        assert r.mis_routes == base.mis_routes, name
        assert r.stale_consults == base.stale_consults, name
        # The reference engine divides its (identical) hit/read counts in
        # float64 where the fused engine divides in f32.
        np.testing.assert_allclose(
            r.hit_rate, base.hit_rate, rtol=1e-6, err_msg=name
        )
        np.testing.assert_allclose(
            r.mean_latency_ms, base.mean_latency_ms, rtol=1e-5,
            err_msg=name,
        )
        np.testing.assert_allclose(
            r.node_busy_ms, base.node_busy_ms, rtol=1e-4, err_msg=name
        )


def test_telemetry_series_sum_to_aggregates():
    wl, cl = _staleness_scenario()
    cfg = cl._replace(routing=RoutingConfig(
        publish_lag_chunks=8, cache_entries=50, decay=0.9,
    ))
    result, trace = run_scenario(
        wl, cfg, RedynisPolicy(), seed=0, daemon_interval=STALE_INTERVAL,
        telemetry=TelemetryConfig(),
    )
    np.testing.assert_allclose(
        trace.router_consults.sum(), result.router_consults
    )
    np.testing.assert_allclose(
        trace.directory_fetches.sum(), result.directory_fetches
    )
    np.testing.assert_allclose(trace.mis_routes.sum(), result.mis_routes)
    np.testing.assert_allclose(
        trace.stale_consults.sum(), result.stale_consults
    )
    # Every stale consult lands in exactly one staleness-age bin.
    np.testing.assert_allclose(
        trace.stale_age_hist.sum(), result.stale_consults
    )
    np.testing.assert_allclose(
        trace.stale_age_hist.sum(axis=1), trace.stale_consults
    )
    rate = trace.mis_route_rate
    assert rate.shape == trace.mis_routes.shape
    assert np.all((rate >= 0.0) & (rate <= 1.0))
    # The reference engine's trace agrees chunk-for-chunk on the counters.
    _, ref_trace = run_scenario_reference(
        wl, cfg, RedynisPolicy(), seed=0, daemon_interval=STALE_INTERVAL,
        telemetry=TelemetryConfig(),
    )
    np.testing.assert_array_equal(
        trace.mis_routes, ref_trace.mis_routes
    )
    np.testing.assert_array_equal(
        trace.stale_age_hist, ref_trace.stale_age_hist
    )


# ---------------------------------------------------------------------------
# 6. Sharded equivalence with routing on (2 virtual ranks).
# ---------------------------------------------------------------------------


SHARDED_ROUTING_SCRIPT = r"""
import numpy as np
from repro.kvsim import (run_scenario, diurnal_workload, wan5_cluster,
                         RedynisPolicy, RoutingConfig, TelemetryConfig)

wl = diurnal_workload(num_requests=20000, num_keys=401, affinity=0.8,
                      read_fraction=0.7)
cl = wan5_cluster()._replace(routing=RoutingConfig(
    publish_lag_chunks=8, cache_entries=50, decay=0.9))
for trace_mode in ('materialized', 'streamed'):
    kw = dict(seed=3, daemon_interval=100, telemetry=TelemetryConfig(),
              trace_mode=trace_mode)
    r1, t1 = run_scenario(wl, cl, RedynisPolicy(), **kw)
    r2, t2 = run_scenario(wl, cl, RedynisPolicy(), num_shards=2, **kw)
    assert r1.mis_routes > 0.0
    # Counter surfaces: bit-exact under psum (and K=401 exercises the
    # ceil-division padding alongside the sharded router caches).
    for f in ('router_consults', 'directory_fetches', 'mis_routes',
              'stale_consults', 'hit_rate', 'replication_moves',
              'deletion_moves'):
        assert getattr(r1, f) == getattr(r2, f), (f, trace_mode)
    np.testing.assert_array_equal(t1.mis_routes, t2.mis_routes)
    np.testing.assert_array_equal(t1.stale_age_hist, t2.stale_age_hist)
    np.testing.assert_allclose(r1.node_busy_ms, r2.node_busy_ms, rtol=1e-4)
    np.testing.assert_allclose(r1.mean_latency_ms, r2.mean_latency_ms,
                               rtol=1e-4)
    print('OK', trace_mode)
print('SHARDED_ROUTING_EQUIVALENCE_OK')
"""


def test_sharded_routing_matches_single_device(run_multi_rank):
    out = run_multi_rank(SHARDED_ROUTING_SCRIPT, num_devices=2, timeout=600)
    assert "SHARDED_ROUTING_EQUIVALENCE_OK" in out
