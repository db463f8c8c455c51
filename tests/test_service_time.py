"""Contention test tier (ISSUE-6 acceptance).

Pins the queueing-aware service-time model (``ServiceConfig`` — M/M/1-style
load factors from per-node demand folds and per-key object bytes):

1. Kernel ⇄ reference parity under contention: the Pallas chunk-replay
   kernel fed the canonical ``contention_extra_ms_ref`` pre-pass output must
   agree with the jnp oracle across load levels × object-size distributions
   × topologies — histograms bit-exact, busy/lat_sum allclose. Hypothesis
   widens the search over the busy-fold inputs when installed.
2. Busy-fold properties: the load factor equals an independent NumPy
   recomputation, respects the stability clamp, ignores invalid rows, and
   the M/M/1 wait is non-negative and monotone in rho.
3. Golden pinning: contention OFF (``service=None`` and
   ``ServiceConfig(enabled=False)``) compiles the exact pre-contention
   program — bit-identical results across both engines × both replay
   backends, still reproducing the seed Fig 2/3 goldens.
4. Engine agreement under contention: fused scan == per-chunk reference ==
   Pallas replay (and the static fast path == reference for frozen maps).
5. Monotonicity: hotter traffic concentration ⇒ higher load factor on the
   owning node (deterministic ref-level sweep + engine-level telemetry).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chunk_replay.ops import chunk_replay
from repro.kernels.chunk_replay.ref import (
    READ_MODES,
    chunk_replay_ref,
    contention_extra_ms_ref,
    contention_wait_ref,
    load_factor_ref,
    service_demand_ref,
    serving_node_ref,
)
from repro.kvsim import (
    ClusterConfig,
    RedynisPolicy,
    ServiceConfig,
    SimResult,
    StaticPolicy,
    TelemetryConfig,
    WorkloadConfig,
    normalize_service,
    run_scenario,
    run_scenario_reference,
    wan5_cluster,
    wan5_edge_cluster,
    wan5_workload,
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

SERVICE_MS = 10.0


# ---------------------------------------------------------------------------
# 1. Kernel ⇄ reference parity under contention.
# ---------------------------------------------------------------------------


def _random_contended_chunk(seed, b, k, n, sigma, read_fraction=0.8):
    """Random frozen map + request slab + lognormal per-key object sizes
    (``sigma=0`` is the constant-size degenerate distribution)."""
    rng = np.random.default_rng(seed)
    hosts = rng.random((k, n)) < 0.4
    obj = (1024.0 * np.exp(rng.normal(0.0, sigma, k))).astype(np.float32)
    return (
        jnp.asarray(hosts),
        jnp.asarray(rng.integers(0, k, b).astype(np.int32)),
        jnp.asarray(rng.integers(0, n, b).astype(np.int32)),
        jnp.asarray(rng.random(b) < read_fraction),
        jnp.asarray(rng.random(b) < 0.9),  # valid mask (padding path)
        jnp.asarray(obj),
    )


def check_contended_kernel_matches_ref(
    rtt, seed, b, k, capacity_factor, sigma,
    read_mode="map", tr=256, tkey=128, rho_max=0.95,
):
    n = rtt.shape[0]
    hosts, keys, nodes, is_read, valid, obj = _random_contended_chunk(
        seed, b, k, n, sigma
    )
    service = ServiceConfig(
        serve_bytes_per_ms=512.0, capacity_factor=capacity_factor,
        rho_max=rho_max,
    )
    extra, rho = contention_extra_ms_ref(
        hosts, keys, nodes, is_read, valid, rtt, obj,
        read_mode=read_mode, service_ms=SERVICE_MS,
        serve_bytes_per_ms=service.serve_bytes_per_ms,
        capacity_ms=service.capacity_ms(b, SERVICE_MS),
        rho_max=service.rho_max,
    )
    assert float(jnp.max(rho)) <= rho_max + 1e-6
    assert float(jnp.min(extra)) >= 0.0
    kw = dict(
        service_ms=SERVICE_MS, master=0, xfer_read_ms=2.0, xfer_write_ms=3.0,
        read_mode=read_mode, num_bins=64, lo=1.0, hi=5_000.0,
    )
    ref = chunk_replay_ref(
        hosts, keys, nodes, is_read, valid, rtt, extra_ms=extra, **kw
    )
    ker = chunk_replay(
        hosts, keys, nodes, is_read, valid, rtt, extra_ms=extra,
        backend="pallas", tr=tr, tkey=tkey, interpret=True, **kw,
    )
    # busy / lat_sum: reductions re-associate across tiles -> allclose.
    np.testing.assert_allclose(
        np.asarray(ker[0]), np.asarray(ref[0]), rtol=1e-5, err_msg="busy"
    )
    np.testing.assert_allclose(
        float(ker[1]), float(ref[1]), rtol=1e-5, err_msg="lat_sum"
    )
    for i, name in ((2, "hits"), (3, "reads"), (4, "count")):
        assert float(ker[i]) == float(ref[i]), (name, ker[i], ref[i])
    # The kernel adds extra_ms in the oracle's elementwise position, so the
    # contended f32 latency bits — and the histogram buckets — match exactly.
    np.testing.assert_array_equal(np.asarray(ker[5]), np.asarray(ref[5]))
    np.testing.assert_allclose(float(jnp.sum(ker[5])), float(ker[4]))


# Load levels (capacity_factor: saturated -> light) × object-size
# distributions (sigma) × topologies; odd b/k exercise the pad paths.
PARITY_GRID = [
    (topo, cf, sigma)
    for topo in TOPOLOGIES
    for cf in (0.25, 1.0, 4.0)
    for sigma in (0.0, 1.2)
]


@pytest.mark.parametrize(
    "topo,cf,sigma", PARITY_GRID,
    ids=[f"{t}-cf{c}-sig{s}" for t, c, s in PARITY_GRID],
)
def test_contended_kernel_matches_ref(topo, cf, sigma):
    check_contended_kernel_matches_ref(
        TOPOLOGIES[topo], seed=hash((topo, cf, sigma)) % 2**32,
        b=777, k=333, capacity_factor=cf, sigma=sigma,
    )


@pytest.mark.parametrize("mode", READ_MODES)
def test_contended_kernel_matches_ref_all_read_modes(mode):
    check_contended_kernel_matches_ref(
        TOPOLOGIES["wan5"], seed=17, b=500, k=200,
        capacity_factor=0.5, sigma=0.8, read_mode=mode,
    )


if HAVE_HYPOTHESIS:
    fold_strategy = st.tuples(
        st.integers(0, 2**31 - 1),  # numpy seed
        st.integers(1, 400),  # b requests
        st.integers(1, 200),  # k keys
        st.integers(2, 8),  # n nodes
        st.floats(0.05, 4.0),  # capacity_factor (saturated -> light)
        st.floats(0.0, 2.0),  # object-size lognormal sigma
        st.sampled_from(READ_MODES),
        st.floats(0.5, 0.99),  # rho_max
    )

    @settings(max_examples=30, deadline=None)
    @given(fold_strategy)
    def test_busy_fold_properties_fuzz(params):
        """The pre-pass vs an independent NumPy recomputation of the
        per-serving-node demand fold."""
        seed, b, k, n, cf, sigma, mode, rho_max = params
        rtt = TOPOLOGIES["flat"]
        rng = np.random.default_rng(seed + 1)
        rtt = jnp.asarray(
            np.where(np.eye(n, dtype=bool), 0.0,
                     rng.uniform(1.0, 400.0, (n, n))).astype(np.float32)
        )
        hosts, keys, nodes, is_read, valid, obj = _random_contended_chunk(
            seed, b, k, n, sigma
        )
        capacity_ms = cf * b * SERVICE_MS
        serving = (
            np.asarray(nodes) if mode == "ideal"
            else np.asarray(serving_node_ref(
                hosts[keys], nodes, is_read, rtt, read_mode=mode
            ))
        )
        demand = np.asarray(service_demand_ref(
            obj[keys], service_ms=SERVICE_MS, serve_bytes_per_ms=512.0
        ))
        rho = np.asarray(load_factor_ref(
            jnp.asarray(serving), jnp.asarray(demand), valid,
            num_nodes=n, capacity_ms=capacity_ms, rho_max=rho_max,
        ))
        # Independent fold: demand summed per serving node, invalid rows
        # contributing nothing, clamped at the stability bound.
        fold = np.zeros(n, np.float32)
        np.add.at(fold, serving[np.asarray(valid)], demand[np.asarray(valid)])
        np.testing.assert_allclose(
            rho, np.minimum(fold / capacity_ms, rho_max), rtol=1e-5
        )
        assert (rho >= 0.0).all() and (rho <= rho_max + 1e-6).all()
        assert (serving >= 0).all() and (serving < n).all()
        assert (demand >= SERVICE_MS).all()
        wait = np.asarray(contention_wait_ref(
            jnp.asarray(demand), jnp.asarray(rho), jnp.asarray(serving)
        ))
        assert np.isfinite(wait).all() and (wait >= 0.0).all()
        # Monotone in rho: scaling every load factor up raises every wait.
        hotter = np.asarray(contention_wait_ref(
            jnp.asarray(demand),
            jnp.asarray(np.minimum(rho * 1.5, 0.99).astype(np.float32)),
            jnp.asarray(serving),
        ))
        assert (hotter >= wait - 1e-6).all()

    @settings(max_examples=15, deadline=None)
    @given(fold_strategy)
    def test_contended_kernel_matches_ref_fuzz(params):
        seed, b, k, n, cf, sigma, mode, rho_max = params
        rng = np.random.default_rng(seed + 1)
        rtt = jnp.asarray(
            np.where(np.eye(n, dtype=bool), 0.0,
                     rng.uniform(1.0, 400.0, (n, n))).astype(np.float32)
        )
        check_contended_kernel_matches_ref(
            rtt, seed=seed, b=b, k=k, capacity_factor=cf, sigma=sigma,
            read_mode=mode, tr=int(rng.choice([64, 256])),
            tkey=int(rng.choice([32, 128])), rho_max=rho_max,
        )


# ---------------------------------------------------------------------------
# 2. ServiceConfig validation + normalisation.
# ---------------------------------------------------------------------------


def test_service_config_validation():
    with pytest.raises(ValueError, match="serve_bytes_per_ms"):
        ServiceConfig(serve_bytes_per_ms=0.0).validate()
    with pytest.raises(ValueError, match="capacity_factor"):
        ServiceConfig(capacity_factor=-1.0).validate()
    with pytest.raises(ValueError, match="stability bound"):
        ServiceConfig(rho_max=1.0).validate()
    with pytest.raises(ValueError, match="stability bound"):
        ServiceConfig(rho_max=0.0).validate()
    assert normalize_service(None) is None
    assert normalize_service(ServiceConfig(enabled=False)) is None
    svc = ServiceConfig()
    assert normalize_service(svc) == svc
    assert svc.capacity_ms(1000, 10.0) == 10_000.0


# ---------------------------------------------------------------------------
# 3. Golden pinning: contention OFF is the exact pre-contention program.
# ---------------------------------------------------------------------------

BASELINES = {
    "local": StaticPolicy(mode="local"),
    "remote": StaticPolicy(mode="remote"),
    "optimized": RedynisPolicy(),
    "replicated": StaticPolicy(mode="replicated"),
}

# The seed Fig 2/3 goldens (see tests/test_simulate_equivalence.py) — the
# queueing model must leave them untouched while it is off.
SEED_GOLDENS = {
    "local": (281.49190710767067, 1.0, 10.0, 0.0),
    "remote": (25.590173373424605, 0.0, 110.0, 0.0),
    "optimized": (161.35538523598225, 0.92155, 17.845, 1000.0),
    "replicated": (281.49190710767067, 1.0, 10.0, 0.0),
}

ENGINES = [
    ("scan-jax", lambda wl, cl, pol: run_scenario(wl, cl, pol, seed=0)),
    ("scan-pallas", lambda wl, cl, pol: run_scenario(
        wl, cl, pol, seed=0, replay_backend="pallas")),
    ("reference", lambda wl, cl, pol: run_scenario_reference(wl, cl, pol, seed=0)),
]


def assert_results_equal(a: SimResult, b: SimResult, ctx: str):
    for field, x, y in zip(SimResult._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{ctx} {field}"
        )


@pytest.mark.parametrize("name", sorted(BASELINES))
@pytest.mark.parametrize("engine", [e[0] for e in ENGINES])
def test_service_off_is_bitexact_and_reproduces_goldens(name, engine):
    """service=None and ServiceConfig(enabled=False) are the SAME static
    (normalize_service collapses both), so the compiled program — and every
    result bit — is identical to the pre-ServiceConfig engine, which the
    seed goldens pin."""
    run = dict((label, fn) for label, fn in ENGINES)[engine]
    wl = WorkloadConfig(num_requests=20_000)
    plain = run(wl, ClusterConfig(), BASELINES[name])
    disabled = run(
        wl, ClusterConfig(service=ServiceConfig(enabled=False)), BASELINES[name]
    )
    assert_results_equal(plain, disabled, f"{engine}/{name}")
    tput, hit, mean_lat, moves = SEED_GOLDENS[name]
    np.testing.assert_allclose(plain.throughput_ops_s, tput, rtol=1e-4)
    np.testing.assert_allclose(plain.hit_rate, hit, rtol=1e-5)
    np.testing.assert_allclose(plain.mean_latency_ms, mean_lat, rtol=1e-4)
    np.testing.assert_allclose(plain.replication_moves, moves, rtol=0)


def test_contention_on_strictly_raises_latency():
    """Sanity direction: switching the queueing model on can only add wait."""
    wl = WorkloadConfig(num_requests=4_000, num_keys=200, skewed=True)
    off = run_scenario(wl, ClusterConfig(), RedynisPolicy(), seed=0)
    on = run_scenario(
        wl,
        ClusterConfig(service=ServiceConfig(
            serve_bytes_per_ms=512.0, capacity_factor=0.5
        )),
        RedynisPolicy(),
        seed=0,
    )
    assert on.mean_latency_ms > off.mean_latency_ms
    assert on.hit_rate == off.hit_rate  # contention delays, never re-routes


# ---------------------------------------------------------------------------
# 4. Engine agreement under contention.
# ---------------------------------------------------------------------------

_SVC = ServiceConfig(serve_bytes_per_ms=512.0, capacity_factor=0.5)


@pytest.mark.parametrize("topo", ["flat", "wan5"])
def test_engines_agree_under_contention(topo):
    """Fused scan == per-chunk reference == Pallas replay with the queueing
    model on (lognormal sizes load the size-aware demand term)."""
    if topo == "flat":
        wl = WorkloadConfig(
            num_requests=4_000, num_keys=200, skewed=True,
            object_bytes_sigma=0.8,
        )
        cl = ClusterConfig(service=_SVC)
    else:
        wl = wan5_workload(
            num_requests=4_000, num_keys=200, object_bytes_sigma=0.8
        )
        cl = wan5_cluster()._replace(service=_SVC)
    a = run_scenario(wl, cl, RedynisPolicy(), seed=2, daemon_interval=500)
    b = run_scenario_reference(
        wl, cl, RedynisPolicy(), seed=2, daemon_interval=500
    )
    c = run_scenario(
        wl, cl, RedynisPolicy(), seed=2, daemon_interval=500,
        replay_backend="pallas",
    )
    for field, x, y, z in zip(SimResult._fields, a, b, c):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-4, err_msg=f"ref {field}"
        )
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(z), rtol=1e-4, err_msg=f"pallas {field}"
        )


@pytest.mark.parametrize("mode", ["local", "remote", "replicated"])
def test_static_fast_path_contention_matches_reference(mode):
    """Frozen maps take the vectorized whole-trace shortcut; its per-chunk
    contention vmap must agree with the reference engine's chunk loop."""
    wl = WorkloadConfig(
        num_requests=4_000, num_keys=200, skewed=True, object_bytes_sigma=0.5
    )
    cl = ClusterConfig(service=_SVC)
    a = run_scenario(
        wl, cl, StaticPolicy(mode=mode), seed=1, daemon_interval=500
    )
    b = run_scenario_reference(
        wl, cl, StaticPolicy(mode=mode), seed=1, daemon_interval=500
    )
    for field, x, y in zip(SimResult._fields, a, b):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-4, err_msg=f"{mode} {field}"
        )


def test_contended_telemetry_histograms_match_across_backends():
    """With contention on, the jax and pallas replay paths see the same f32
    latency bits, so telemetry histograms stay bit-identical."""
    wl = wan5_workload(num_requests=3_000, num_keys=150, object_bytes_sigma=0.5)
    cl = wan5_cluster()._replace(service=_SVC)
    _, ta = run_scenario(
        wl, cl, RedynisPolicy(), seed=0, daemon_interval=500,
        telemetry=TelemetryConfig(),
    )
    _, tb = run_scenario(
        wl, cl, RedynisPolicy(), seed=0, daemon_interval=500,
        telemetry=TelemetryConfig(), replay_backend="pallas",
    )
    np.testing.assert_array_equal(ta.hist_group, tb.hist_group)
    np.testing.assert_allclose(ta.load_factor, tb.load_factor, rtol=1e-6)


# ---------------------------------------------------------------------------
# 5. Monotonicity: concentration ⇒ load factor on the owning node.
# ---------------------------------------------------------------------------


def test_load_factor_monotone_in_concentration_ref():
    """Deterministic sweep: key 0 lives only on node 0; shifting more of the
    chunk's reads onto key 0 monotonically raises node 0's load factor until
    the stability clamp."""
    n, k, b = 4, 8, 400
    rtt = ClusterConfig(num_nodes=n).rtt_matrix()
    hosts = np.zeros((k, n), bool)
    hosts[0, 0] = True
    for key in range(1, k):  # the rest spread over the other nodes
        hosts[key, 1 + (key % (n - 1))] = True
    obj = jnp.full((k,), 1024.0, jnp.float32)
    rhos = []
    for frac in (0.0, 0.25, 0.5, 0.75, 1.0):
        hot = int(frac * b)
        keys = np.r_[np.zeros(hot), 1 + np.arange(b - hot) % (k - 1)]
        _, rho = contention_extra_ms_ref(
            jnp.asarray(hosts),
            jnp.asarray(keys.astype(np.int32)),
            jnp.asarray((np.arange(b) % n).astype(np.int32)),
            jnp.ones((b,), bool),
            jnp.ones((b,), bool),
            rtt, obj,
            read_mode="map", service_ms=SERVICE_MS,
            serve_bytes_per_ms=512.0, capacity_ms=2.0 * b * SERVICE_MS,
            rho_max=0.95,
        )
        rhos.append(float(rho[0]))
    assert rhos == sorted(rhos), rhos
    assert rhos[-1] > rhos[0]


def test_engine_load_factor_telemetry_and_concentration():
    """SimTrace.load_factor: [C, N], bounded by rho_max, all-zero with the
    model off — and a hotter (skewed) workload posts a higher peak load
    factor on the owning node than uniform traffic under the same
    single-replica placement."""
    svc = ServiceConfig(serve_bytes_per_ms=512.0, capacity_factor=3.0)
    peaks = {}
    for skew in (False, True):
        wl = WorkloadConfig(num_requests=4_000, num_keys=200, skewed=skew)
        _, tr = run_scenario(
            wl, ClusterConfig(service=svc), StaticPolicy(mode="remote"),
            seed=0, daemon_interval=500, telemetry=TelemetryConfig(),
        )
        assert tr.load_factor.shape == (8, 3)
        assert (tr.load_factor >= 0.0).all()
        assert (tr.load_factor <= svc.rho_max + 1e-6).all()
        peaks[skew] = float(tr.load_factor.max())
    assert peaks[True] > peaks[False], peaks
    # Model off -> the leaf is present but identically zero.
    wl = WorkloadConfig(num_requests=2_000, num_keys=100, skewed=True)
    _, off = run_scenario(
        wl, ClusterConfig(), StaticPolicy(mode="remote"), seed=0,
        daemon_interval=500, telemetry=TelemetryConfig(),
    )
    assert (off.load_factor == 0.0).all()
