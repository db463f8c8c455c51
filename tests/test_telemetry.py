"""Telemetry subsystem guard rails.

Four pinned properties (the ISSUE-4 acceptance criteria):

  1. Kernel ⇄ reference parity: the Pallas ``latency_histogram`` (one-hot
     matmul accumulation, interpret mode on CPU) must agree with the
     pure-jnp scatter-add oracle — exactly for 0/1 weights (integer counts
     are order-independent in f32 below 2**24), allclose for real weights.
  2. Quantile interpolation: ``SimTrace`` quantiles read off the log-bin
     histogram must land within ONE relative bin width of ``np.percentile``
     over the reference engine's raw per-request latencies.
  3. Telemetry-off (and telemetry-on) aggregates are bit-identical to the
     pre-telemetry engines, for both engines × both sweep backends — the
     scan carry is untouched by telemetry, it only adds ``ys``.
  4. Merge associativity: histograms accumulated under the seed-vmapped
     batched engine and summed equal the sum of independently-run per-seed
     histograms (and the reference engine's), so ``run_experiment`` merging
     by summation is sound.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.latency_histogram.ops import latency_histogram
from repro.kernels.latency_histogram.ref import (
    bin_edges,
    bin_index,
    latency_histogram_ref,
)
from repro.kvsim import (
    ClusterConfig,
    RedynisPolicy,
    SimResult,
    StaticPolicy,
    TelemetryConfig,
    WorkloadConfig,
    confidence_interval_99,
    histogram_quantile,
    run_experiment,
    run_scenario,
    run_scenario_reference,
    wan5_cluster,
    wan5_workload,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# 1. Histogram kernel ⇄ reference parity.
# ---------------------------------------------------------------------------


def _random_chunk(seed, r, g, lo, hi):
    """Latencies spanning under/overflow, random groups, 0/1 weights."""
    rng = np.random.default_rng(seed)
    # Log-uniform over [lo/10, hi*10] guarantees traffic in the underflow
    # and overflow buckets as well as every interior decade.
    lat = np.exp(
        rng.uniform(np.log(max(lo / 10, 1e-6)), np.log(hi * 10), size=r)
    ).astype(np.float32)
    group = rng.integers(0, g, size=r).astype(np.int32)
    weight = (rng.random(r) < 0.8).astype(np.float32)
    return jnp.asarray(lat), jnp.asarray(group), jnp.asarray(weight)


def check_kernel_matches_ref(seed, r, g, b, lo, hi, tr):
    lat, group, weight = _random_chunk(seed, r, g, lo, hi)
    kw = dict(num_groups=g, num_bins=b, lo=lo, hi=hi)
    ref = latency_histogram_ref(lat, group, weight, **kw)
    ker = latency_histogram(lat, group, weight, tr=tr, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))
    # Conservation: every weighted request lands in exactly one bucket.
    np.testing.assert_allclose(
        float(jnp.sum(ker)), float(jnp.sum(weight)), rtol=1e-6
    )


# Fixed grid: odd R (pad path), single-tile and multi-tile, tight and wide
# bin ranges, group counts from the simulator's 2N=6 up to 16.
KERNEL_GRID = [
    (0, 512, 6, 64, 1.0, 10_000.0, 256),
    (1, 1000, 6, 128, 1.0, 10_000.0, 256),  # daemon_interval-sized, pad path
    (2, 77, 10, 32, 5.0, 500.0, 64),  # odd R, narrow range
    (3, 2048, 16, 128, 0.1, 1e6, 1024),
    (4, 1, 2, 8, 1.0, 100.0, 64),  # single request
]


@pytest.mark.parametrize("params", KERNEL_GRID)
def test_latency_histogram_kernel_matches_ref(params):
    check_kernel_matches_ref(*params)


def test_latency_histogram_real_weights_allclose():
    """Non-0/1 weights: matmul and scatter-add sum in different orders, so
    the guarantee weakens from bit-exact to allclose."""
    rng = np.random.default_rng(7)
    lat, group, _ = _random_chunk(7, 800, 6, 1.0, 10_000.0)
    weight = jnp.asarray(rng.random(800).astype(np.float32))
    kw = dict(num_groups=6, num_bins=64, lo=1.0, hi=10_000.0)
    ref = latency_histogram_ref(lat, group, weight, **kw)
    ker = latency_histogram(lat, group, weight, tr=256, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), rtol=1e-5)


def test_bin_index_boundaries():
    """Pinned bucket semantics: underflow < lo, overflow >= hi, interior
    edges land in the bucket they open."""
    lo, hi, b = 1.0, 1000.0, 32
    idx = bin_index(jnp.asarray([0.0, 0.5, 1.0, 999.9, 1000.0, 1e9]), lo, hi, b)
    assert int(idx[0]) == 0 and int(idx[1]) == 0  # underflow
    assert int(idx[2]) == 1  # first interior bucket opens at lo
    assert int(idx[3]) == b - 2  # last interior bucket
    assert int(idx[4]) == b - 1 and int(idx[5]) == b - 1  # overflow


if HAVE_HYPOTHESIS:
    chunk_strategy = st.tuples(
        st.integers(0, 2**31 - 1),  # numpy seed
        st.integers(1, 600),  # r requests (odd sizes exercise the pad)
        st.integers(2, 12),  # g groups
        st.sampled_from([8, 32, 128]),  # b bins
        st.floats(0.05, 50.0),  # lo
        st.floats(2.0, 1e5),  # hi / lo ratio
        st.sampled_from([64, 256]),  # tile
    )

    @settings(max_examples=25, deadline=None)
    @given(chunk_strategy)
    def test_latency_histogram_kernel_matches_ref_fuzz(params):
        seed, r, g, b, lo, ratio, tr = params
        check_kernel_matches_ref(seed, r, g, b, lo, lo * ratio, tr)


# ---------------------------------------------------------------------------
# 2. Quantile interpolation vs np.percentile.
# ---------------------------------------------------------------------------


def assert_within_one_bin(interp, exact, edges, label=""):
    """Log-spaced bins have constant relative width rho = edges[2]/edges[1];
    one-bin-width accuracy means interp/exact lies in [1/rho, rho]."""
    rho = float(edges[2] / edges[1])
    assert exact / rho <= interp <= exact * rho * (1 + 1e-9), (
        f"{label}: interpolated {interp} vs exact {exact} "
        f"(allowed factor {rho})"
    )


def test_histogram_quantile_vs_percentile_synthetic():
    rng = np.random.default_rng(3)
    samples = np.exp(rng.normal(3.0, 1.2, size=20_000)).astype(np.float32)
    lo, hi, b = 1.0, 10_000.0, 128
    hist = np.asarray(latency_histogram_ref(
        jnp.asarray(samples), jnp.zeros(len(samples), jnp.int32),
        jnp.ones(len(samples), jnp.float32),
        num_groups=1, num_bins=b, lo=lo, hi=hi,
    ))[0]
    edges = bin_edges(lo, hi, b)
    for q in (0.5, 0.9, 0.95, 0.99, 0.999):
        interp = histogram_quantile(hist, edges, q)
        exact = float(np.percentile(samples, 100 * q))
        assert_within_one_bin(interp, exact, edges, f"q={q}")


def test_reference_engine_quantiles_vs_raw_latencies():
    """The oracle path: SimTrace quantiles vs np.percentile of the raw
    per-request latencies only the reference engine materialises."""
    wl = WorkloadConfig(
        num_requests=4_000, num_keys=200, skewed=True, read_fraction=0.9,
        affinity=0.8,
    )
    _, trace = run_scenario_reference(
        wl, ClusterConfig(), RedynisPolicy(), seed=2, daemon_interval=500,
        telemetry=TelemetryConfig(),
    )
    raw = trace.raw_latency_ms
    assert raw.shape == (4_000,)
    for q in (0.5, 0.9, 0.99):
        assert_within_one_bin(
            trace.quantile(q), float(np.percentile(raw, 100 * q)),
            trace.edges, f"q={q}",
        )


def test_acceptance_wan5_fused_p99_matches_reference_percentile():
    """ISSUE-4 acceptance: with telemetry enabled, run_scenario(policy=
    RedynisPolicy(...)) on wan5 returns a SimTrace whose interpolated P99
    matches np.percentile of the reference engine's raw per-request
    latencies within one histogram-bin width."""
    wl = wan5_workload(num_requests=4_000, num_keys=200, affinity=0.8)
    cl = wan5_cluster()
    cfg = TelemetryConfig()
    _, fused = run_scenario(
        wl, cl, RedynisPolicy(h=0.2), seed=0, daemon_interval=500,
        telemetry=cfg,
    )
    _, ref = run_scenario_reference(
        wl, cl, RedynisPolicy(h=0.2), seed=0, daemon_interval=500,
        telemetry=cfg,
    )
    # Same f32 latencies -> same buckets: the two engines' histograms are
    # identical, not merely close.
    np.testing.assert_array_equal(fused.hist_group, ref.hist_group)
    exact_p99 = float(np.percentile(ref.raw_latency_ms, 99))
    assert_within_one_bin(fused.quantile(0.99), exact_p99, fused.edges, "p99")


# ---------------------------------------------------------------------------
# 3. Telemetry-off (and on) bit-exactness, both engines × both backends.
# ---------------------------------------------------------------------------


def assert_results_equal(a: SimResult, b: SimResult, ctx: str):
    for field, x, y in zip(SimResult._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{ctx} {field}"
        )


@pytest.mark.parametrize("engine", ["scan", "reference"])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_telemetry_is_a_bitexact_noop(engine, backend):
    """Enabling telemetry must not perturb a single aggregate bit — the
    PR-3 goldens stay valid with or without a SimTrace attached."""
    run = run_scenario if engine == "scan" else run_scenario_reference
    wl = WorkloadConfig(
        num_requests=2_000, num_keys=150, skewed=True, affinity=0.8
    )
    cl = ClusterConfig(capacity_bytes=24 * 1024.0)
    pol = RedynisPolicy(backend=backend, expiry=4, decay=0.5)
    plain = run(wl, cl, pol, seed=3, daemon_interval=500)
    on, trace = run(
        wl, cl, pol, seed=3, daemon_interval=500, telemetry=TelemetryConfig()
    )
    assert isinstance(plain, SimResult)
    assert_results_equal(plain, on, f"{engine}/{backend}")
    assert float(trace.requests.sum()) == 2_000.0
    # A disabled config is the same static as no config at all.
    off = run(
        wl, cl, pol, seed=3, daemon_interval=500,
        telemetry=TelemetryConfig(enabled=False),
    )
    assert isinstance(off, SimResult)
    assert_results_equal(plain, off, f"{engine}/{backend} disabled-config")


def test_pallas_telemetry_backend_matches_jax_inside_scan():
    """The Pallas histogram kernel runs INSIDE the fused lax.scan body
    (vmap-compatible, interpret off-TPU) and must reproduce the pure-JAX
    telemetry backend's SimTrace exactly."""
    wl = WorkloadConfig(num_requests=2_000, num_keys=150, skewed=True)
    a_res, a = run_scenario(
        wl, ClusterConfig(), RedynisPolicy(), seed=1,
        daemon_interval=500, telemetry=TelemetryConfig(backend="jax"),
    )
    b_res, b = run_scenario(
        wl, ClusterConfig(), RedynisPolicy(), seed=1,
        daemon_interval=500, telemetry=TelemetryConfig(backend="pallas"),
    )
    assert_results_equal(a_res, b_res, "telemetry-backend")
    np.testing.assert_array_equal(a.hist_group, b.hist_group)
    np.testing.assert_array_equal(a.chunk_hist, b.chunk_hist)


# ---------------------------------------------------------------------------
# 4. vmap-merge associativity + run_experiment surface.
# ---------------------------------------------------------------------------

_EXPERIMENT_KW = dict(
    read_fractions=(0.9,), skewed=True, iterations=3, num_requests=3_000,
    num_keys=150, affinity=0.8,
)


def test_vmap_merged_histogram_equals_sum_of_per_seed_runs():
    """Sum of independently-run per-seed histograms == the seed-vmapped
    batched engine's merged histogram (integer counts: exact)."""
    cfg = TelemetryConfig()
    pols = [RedynisPolicy(), RedynisPolicy(h=0.05, decay=0.9)]
    res = run_experiment(policies=pols, telemetry=cfg, **_EXPERIMENT_KW)
    wl = WorkloadConfig(
        num_requests=3_000, num_keys=150, skewed=True, read_fraction=0.9,
        affinity=0.8,
    )
    for pol, (label, rows) in zip(pols, res["policies"].items()):
        per_seed = [
            run_scenario(wl, ClusterConfig(), pol, seed=s, telemetry=cfg)[1]
            for s in range(3)
        ]
        np.testing.assert_array_equal(
            rows[0]["trace"].hist_group,
            sum(t.hist_group for t in per_seed),
            err_msg=label,
        )
        assert float(rows[0]["trace"].requests.sum()) == 3 * 3_000.0
        # Occupancy is a point sample, not a counter: the seed-merged
        # trace must AVERAGE it, not inflate it by the seed count.
        np.testing.assert_allclose(
            rows[0]["trace"].occupancy_bytes,
            np.mean([t.occupancy_bytes for t in per_seed], axis=0),
            rtol=1e-6, err_msg=label,
        )


def test_experiment_reference_engine_matches_scan_telemetry():
    cfg = TelemetryConfig()
    pols = [RedynisPolicy()]
    scan = run_experiment(policies=pols, telemetry=cfg, **_EXPERIMENT_KW)
    ref = run_experiment(
        policies=pols, telemetry=cfg, engine="reference", **_EXPERIMENT_KW
    )
    a = scan["policies"]["redynis(h=0.3333333333333333)"][0]
    b = ref["policies"]["redynis(h=0.3333333333333333)"][0]
    np.testing.assert_array_equal(
        a["trace"].hist_group, b["trace"].hist_group
    )
    np.testing.assert_allclose(
        a["p99_latency_ms"], b["p99_latency_ms"], rtol=1e-9
    )


def test_experiment_rows_report_p99_ci_bands():
    res = run_experiment(
        policies=[RedynisPolicy(), StaticPolicy(mode="remote")],
        telemetry=TelemetryConfig(), **_EXPERIMENT_KW,
    )
    wl = WorkloadConfig(
        num_requests=3_000, num_keys=150, skewed=True, read_fraction=0.9,
        affinity=0.8,
    )
    cfg = TelemetryConfig()
    one_family = 0
    for pol, (label, rows) in zip(
        [RedynisPolicy(), StaticPolicy(mode="remote")],
        res["policies"].items(),
    ):
        row = rows[0]
        assert row["p99_ci99"] >= 0.0, label
        assert row["p99_latency_ms"] > 0.0, label
        assert set(row["quantiles"]) == {"p50", "p90", "p95", "p99", "p999"}
        # The CI is over per-seed interpolated P99 samples: the reported
        # centre is their mean, and the merged-histogram P99 (a quantile of
        # the seeds' mixture) lies between the smallest and largest of them,
        # up to one bin width of interpolation.
        p99s = [
            run_scenario(wl, ClusterConfig(), pol, seed=s, telemetry=cfg)[1]
            .quantile(0.99)
            for s in range(3)
        ]
        np.testing.assert_allclose(
            row["p99_latency_ms"], np.mean(p99s), rtol=1e-9, err_msg=label
        )
        merged = row["trace"].quantile(0.99)
        rho = float(row["trace"].edges[2] / row["trace"].edges[1])
        assert min(p99s) / rho <= merged <= max(p99s) * rho, (
            label, merged, p99s,
        )
        if max(p99s) <= min(p99s) * rho:
            # Seeds of one distribution family (their P99s within one bin
            # of each other): the centre is within one bin of the merged
            # P99 as well.
            assert_within_one_bin(
                row["p99_latency_ms"], merged, row["trace"].edges, label,
            )
            one_family += 1
    assert one_family >= 1
    # Every policy row carries the same quantile surface (no legacy grid:
    # the row-building path is shared).
    more = run_experiment(
        policies=[StaticPolicy(mode="local")],
        read_fractions=(0.9,), iterations=2, num_requests=2_000,
        telemetry=TelemetryConfig(),
    )
    assert "p99_latency_ms" in more["policies"]["static(mode='local')"][0]


def test_confidence_interval_accepts_quantile_sample_stacks():
    """[S] scalars keep the legacy float contract; [S, Q] per-seed quantile
    stacks reduce along the seed axis and return arrays."""
    m, ci = confidence_interval_99(np.array([1.0, 2.0, 3.0]))
    assert isinstance(m, float) and isinstance(ci, float)
    np.testing.assert_allclose(m, 2.0)
    stack = np.array([[1.0, 10.0], [3.0, 30.0], [2.0, 20.0]])
    mv, civ = confidence_interval_99(stack)
    np.testing.assert_allclose(mv, [2.0, 20.0])
    np.testing.assert_allclose(civ[1], civ[0] * 10.0)
    m1, ci1 = confidence_interval_99(np.array([5.0]))
    assert (m1, ci1) == (5.0, 0.0)


# ---------------------------------------------------------------------------
# SimTrace views + convergence diagnostics + config validation.
# ---------------------------------------------------------------------------


def test_simtrace_views_are_consistent():
    wl = WorkloadConfig(num_requests=3_000, num_keys=150, skewed=True,
                        read_fraction=0.75)
    _, trace = run_scenario(
        wl, ClusterConfig(), RedynisPolicy(), telemetry=TelemetryConfig()
    )
    np.testing.assert_allclose(trace.hist, trace.hist_read + trace.hist_write)
    np.testing.assert_allclose(trace.hist, trace.hist_node.sum(axis=0))
    np.testing.assert_allclose(trace.hist, trace.chunk_hist.sum(axis=0))
    assert float(trace.hist.sum()) == 3_000.0
    # ~75% reads; the read/write split must reflect the trace mix.
    assert 0.6 < trace.hist_read.sum() / 3_000.0 < 0.9
    assert trace.num_nodes == 3
    assert trace.occupancy_bytes.shape == (trace.hit_rate.shape[0], 3)


def test_convergence_diagnostics():
    wl = WorkloadConfig(num_requests=4_000, num_keys=150, skewed=True,
                        affinity=0.8)
    cfg = TelemetryConfig()
    # A static map is converged from chunk 0 and never moves a replica.
    _, static = run_scenario(
        wl, ClusterConfig(), StaticPolicy(mode="remote"), telemetry=cfg,
        daemon_interval=500,
    )
    assert static.convergence_chunk(1e-6) == 0
    assert static.post_convergence_moves() == 0.0
    np.testing.assert_array_equal(static.moves, 0.0)
    # Redynis digs out of the offsite placement: hit-rate must climb, and
    # the first chunk (cold map) cannot already be within eps of terminal.
    _, adaptive = run_scenario(
        wl, ClusterConfig(), RedynisPolicy(), telemetry=cfg,
        daemon_interval=500,
    )
    c = adaptive.convergence_chunk(0.02)
    assert 0 < c < adaptive.hit_rate.shape[0]
    assert adaptive.hit_rate[-1] > adaptive.hit_rate[0]
    assert adaptive.moves[0] > 0  # the first sweep replicates hot keys


def test_telemetry_config_validation():
    with pytest.raises(ValueError, match="num_bins"):
        TelemetryConfig(num_bins=3).validate()
    with pytest.raises(ValueError, match="lo_ms"):
        TelemetryConfig(lo_ms=10.0, hi_ms=1.0).validate()
    with pytest.raises(ValueError, match="backend"):
        TelemetryConfig(backend="cuda").validate()
    with pytest.raises(ValueError):
        run_scenario(
            WorkloadConfig(num_requests=100, num_keys=10),
            ClusterConfig(),
            RedynisPolicy(),
            telemetry=TelemetryConfig(num_bins=2),
        )
