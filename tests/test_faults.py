"""Failure-injection test tier (ISSUE-10 acceptance).

Pins the fault subsystem (``FaultConfig`` — declarative membership
timelines, degraded-mode serving, write failover, daemon re-replication,
availability/blast-radius telemetry):

1. Faults OFF (``faults=None``, ``FaultConfig(enabled=False)``, and an
   empty event list) compiles the exact pre-fault program — bit-identical
   results across both engines × both replay backends, still reproducing
   the seed Fig 2/3 goldens — and an all-up schedule (every event past the
   trace end) runs the fault machinery yet stays bit-exact with OFF (the
   ``x - x ≡ +0.0`` write-delta identity).
2. Schedule compiler: event/config validation, ``normalize_faults``
   off-collapse, window clipping, domain lowering (node/zone/region, flat
   fallback, labelling mismatches), the full-blackout rejection, and
   ``blast_radius_rows`` windows.
3. The canonical oracle ``fault_extra_ms_ref``: verdict invariants
   (unavailable/failover ⊆ valid, failovers are served writes under a dead
   master, reads never price a fault delta, all-up is bitwise zero) and
   availability-monotonicity (reviving nodes never creates new
   unavailability) — Hypothesis-fuzzed over random chunks when installed.
4. Engine agreement with faults ON: fused scan == per-chunk reference
   (fault counters bit-exact, latency allclose) == Pallas replay ==
   streamed traces, runs are deterministic, and the per-chunk telemetry
   series sum to the aggregate counters.
5. Degraded-mode behaviour: availability dips exactly inside the outage
   window and returns to 1.0 after it; blast-radius fractions live in
   [0, 1] and peak inside the window; redynis re-replicates crash-wiped
   keys (``repair_moves > 0``, finite ``recovery_chunks``) while a static
   policy never repairs.
6. 2-rank ``shard_map`` equivalence with faults on (``run_multi_rank``).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chunk_replay.ref import fault_extra_ms_ref
from repro.kvsim import (
    ClusterConfig,
    FaultConfig,
    FaultEvent,
    RedynisPolicy,
    SimResult,
    StaticPolicy,
    TelemetryConfig,
    WorkloadConfig,
    blast_radius_rows,
    compile_schedule,
    normalize_faults,
    region_outage,
    run_scenario,
    run_scenario_reference,
    wan5_cluster,
    wan5_workload,
)
from repro.kvsim.faults import domain_nodes, event_windows

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


BASELINES = {
    "local": StaticPolicy(mode="local"),
    "remote": StaticPolicy(mode="remote"),
    "optimized": RedynisPolicy(),
    "replicated": StaticPolicy(mode="replicated"),
}

# The seed Fig 2/3 goldens (see tests/test_simulate_equivalence.py) — the
# fault tier must leave them untouched while it is off.
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
    ("reference", lambda wl, cl, pol: run_scenario_reference(
        wl, cl, pol, seed=0)),
]

FAULT_COUNTERS = (
    "unavailable_reads", "unavailable_writes", "failovers", "repair_moves",
)


def assert_results_equal(a: SimResult, b: SimResult, ctx: str):
    for field, x, y in zip(SimResult._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{ctx} {field}"
        )


# A fault-rich scenario: region-skewed wan5 traffic, the hottest region
# (region 0, weight 0.35; each wan5 node is its own region) crashed for a
# mid-trace window, recovered before the end.
FAULT_INTERVAL = 100
NUM_CHUNKS = 200  # 20_000 requests / interval
OUTAGE_START, OUTAGE_LEN = 60, 40
OUTAGE_END = OUTAGE_START + OUTAGE_LEN


def _fault_scenario():
    return (
        wan5_workload(
            num_requests=20_000, num_keys=400, affinity=0.8,
            read_fraction=0.7,
        ),
        wan5_cluster(),
    )


def _outage():
    return region_outage(0, OUTAGE_START, OUTAGE_LEN, mode="crash")


# ---------------------------------------------------------------------------
# 1. Faults off is a structural no-op: seed goldens stay bit-exact.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(BASELINES))
@pytest.mark.parametrize("engine", [e[0] for e in ENGINES])
def test_fault_off_is_bitexact_and_reproduces_goldens(name, engine):
    """faults=None, FaultConfig(enabled=False), and an empty event list are
    the SAME static (normalize_faults collapses all three), so the compiled
    program — and every result bit — is identical to the pre-fault engine,
    which the seed goldens pin."""
    run = dict(ENGINES)[engine]
    wl = WorkloadConfig(num_requests=20_000)
    plain = run(wl, ClusterConfig(), BASELINES[name])
    for off in (FaultConfig(enabled=False), FaultConfig(events=())):
        disabled = run(wl, ClusterConfig(faults=off), BASELINES[name])
        assert_results_equal(plain, disabled, f"{engine}/{name}")
    for counter in FAULT_COUNTERS:
        assert getattr(plain, counter) == 0.0
    tput, hit, mean_lat, moves = SEED_GOLDENS[name]
    np.testing.assert_allclose(plain.throughput_ops_s, tput, rtol=1e-4)
    np.testing.assert_allclose(plain.hit_rate, hit, rtol=1e-5)
    np.testing.assert_allclose(plain.mean_latency_ms, mean_lat, rtol=1e-4)
    np.testing.assert_allclose(plain.replication_moves, moves, rtol=0)


@pytest.mark.parametrize("engine", ["scan-jax", "reference"])
def test_allup_schedule_is_bitexact_with_off(engine):
    """A schedule whose every event lies past the trace end keeps the fault
    machinery ON (avail ≡ True, crash ≡ False) yet must reproduce the OFF
    program bit-for-bit: the write-failover delta is ``x - x`` on identical
    f32 operands (+0.0), unavailability is identically False, and the zero
    extra folds through ``lat + 0.0`` unchanged."""
    run = dict(ENGINES)[engine]
    wl, cl = _fault_scenario()
    allup = FaultConfig(
        events=(FaultEvent(kind="node", target=1, start_chunk=10**6),)
    )
    plain = run(wl, cl, RedynisPolicy())
    noop = run(wl, cl._replace(faults=allup), RedynisPolicy())
    assert_results_equal(plain, noop, f"{engine}/all-up")


# ---------------------------------------------------------------------------
# 2. Schedule compiler: validation, windows, domains, blackout rejection.
# ---------------------------------------------------------------------------


def test_event_and_config_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultEvent(kind="rack").validate()
    with pytest.raises(ValueError, match="mode"):
        FaultEvent(mode="flaky").validate()
    with pytest.raises(ValueError, match="target"):
        FaultEvent(target=-1).validate()
    with pytest.raises(ValueError, match="start_chunk"):
        FaultEvent(start_chunk=-3).validate()
    with pytest.raises(TypeError, match="FaultEvent"):
        FaultConfig(events=("node-0-down",)).validate()


def test_normalize_faults_collapses_every_off_state():
    assert normalize_faults(None) is None
    assert normalize_faults(FaultConfig(enabled=False)) is None
    assert normalize_faults(FaultConfig(events=())) is None
    on = FaultConfig(events=(FaultEvent(target=1),))
    assert normalize_faults(on) is on


def test_compile_schedule_windows_and_crash_oneshot():
    cfg = FaultConfig(events=(
        FaultEvent(kind="node", target=1, start_chunk=3, duration_chunks=4,
                   mode="crash"),
        FaultEvent(kind="node", target=2, start_chunk=8, duration_chunks=0,
                   mode="partition"),
    ))
    avail, crash = compile_schedule(cfg, num_nodes=4, num_chunks=12)
    assert avail.shape == crash.shape == (12, 4)
    # Node 1 down exactly [3, 7); crash wipe only at the start chunk.
    assert not avail[3:7, 1].any() and avail[:3, 1].all() and avail[7:, 1].all()
    assert crash[3, 1] and not crash[4:, 1].any() and not crash[:3, 1].any()
    # Node 2 partitioned until the end (duration <= 0), never wiped.
    assert not avail[8:, 2].any() and avail[:8, 2].all()
    assert not crash[:, 2].any()
    # Untargeted nodes untouched.
    assert avail[:, 0].all() and avail[:, 3].all()


def test_compile_schedule_drops_events_past_trace_end():
    cfg = FaultConfig(events=(FaultEvent(target=0, start_chunk=50),))
    avail, crash = compile_schedule(cfg, num_nodes=3, num_chunks=10)
    assert avail.all() and not crash.any()
    assert event_windows(cfg, 10) == []


def test_domain_lowering_zone_region_and_flat_fallback():
    region_of = (0, 0, 1, 1, 2)
    ev = FaultEvent(kind="region", target=1, start_chunk=0,
                    duration_chunks=2)
    mask = domain_nodes(ev, num_nodes=5, region_of=region_of)
    np.testing.assert_array_equal(
        mask, [False, False, True, True, False]
    )
    # Absent labelling degrades to the flat hierarchy (node == region).
    np.testing.assert_array_equal(
        domain_nodes(ev, num_nodes=5), [False, True, False, False, False]
    )
    avail, _ = compile_schedule(
        FaultConfig(events=(ev,)), num_nodes=5, num_chunks=4,
        region_of=region_of,
    )
    assert not avail[0:2, 2:4].any() and avail[2:].all()
    with pytest.raises(ValueError, match="labels no node"):
        domain_nodes(FaultEvent(kind="zone", target=9), num_nodes=3,
                     zone_of=(0, 0, 1))
    with pytest.raises(ValueError, match="entries"):
        domain_nodes(FaultEvent(kind="zone", target=0), num_nodes=3,
                     zone_of=(0, 0))


def test_full_blackout_rejected():
    cfg = FaultConfig(events=(
        FaultEvent(kind="node", target=0, start_chunk=2, duration_chunks=3),
        FaultEvent(kind="node", target=1, start_chunk=4, duration_chunks=3),
    ))
    with pytest.raises(ValueError, match="chunk 4"):
        compile_schedule(cfg, num_nodes=2, num_chunks=10)


def test_blast_radius_rows_windows_and_peaks():
    cfg = FaultConfig(events=(
        FaultEvent(target=0, start_chunk=2, duration_chunks=3),
        FaultEvent(target=1, start_chunk=8, duration_chunks=0,
                   mode="partition"),
    ))
    unreach = np.zeros(10)
    unreach[3], unreach[9] = 0.25, 0.5
    wiped = np.zeros(10)
    wiped[4] = 0.125
    rows = blast_radius_rows(
        cfg, num_chunks=10, unreachable_frac=unreach, wiped_frac=wiped
    )
    assert [r["start_chunk"] for r in rows] == [2, 8]
    assert [r["end_chunk"] for r in rows] == [5, 10]
    assert rows[0]["blast_radius_unreachable"] == 0.25
    assert rows[0]["blast_radius_wiped"] == 0.125
    assert rows[1]["blast_radius_unreachable"] == 0.5
    assert rows[1]["blast_radius_wiped"] == 0.0
    assert rows[1]["mode"] == "partition"


# ---------------------------------------------------------------------------
# 3. The canonical fault oracle: verdict invariants.
# ---------------------------------------------------------------------------


def _random_fault_chunk(seed, b, k, n):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.random((k, n)) < 0.4),  # hosts
        jnp.asarray(rng.integers(0, k, b).astype(np.int32)),  # keys
        jnp.asarray(rng.integers(0, n, b).astype(np.int32)),  # nodes
        jnp.asarray(rng.random(b) < 0.7),  # is_read
        jnp.asarray(rng.random(b) < 0.9),  # valid
        jnp.asarray(rng.random(k) < 0.05),  # wiped
        rng,
    )


def check_fault_prepass_invariants(seed, b=256, k=64, n=5, read_mode="map"):
    hosts, keys, nodes, is_read, valid, wiped, rng = _random_fault_chunk(
        seed, b, k, n
    )
    avail_n = rng.random(n) < 0.6
    if not avail_n.any():
        avail_n[rng.integers(n)] = True  # engine guarantees >= 1 live node
    avail = jnp.asarray(avail_n)
    rtt = jnp.asarray(
        np.where(np.eye(n), 0.0, 40.0 + rng.random((n, n)) * 60.0)
    ).astype(jnp.float32)
    kw = dict(read_mode=read_mode, master=0, xfer_write_ms=10.0)
    extra, unav, fo = fault_extra_ms_ref(
        hosts, keys, nodes, is_read, valid, avail, rtt, wiped=wiped, **kw
    )
    extra_n, unav_n, fo_n = map(np.asarray, (extra, unav, fo))
    valid_n, read_n = np.asarray(valid), np.asarray(is_read)
    # Verdicts never escape the valid mask; refused requests price nothing.
    assert not np.any(unav_n & ~valid_n)
    assert not np.any(fo_n & ~valid_n)
    assert not np.any(fo_n & unav_n)
    # Failover is a served-write event, and only under a dead master.
    assert not np.any(fo_n & read_n)
    if avail_n[0]:
        assert not fo_n.any()
    # Reads never carry a fault delta (theirs is priced via hosts_eff).
    np.testing.assert_array_equal(extra_n[read_n], 0.0)
    assert np.all(np.isfinite(extra_n))
    # A down origin refuses everything it issues.
    origin_down = ~avail_n[np.asarray(nodes)]
    np.testing.assert_array_equal(
        unav_n[origin_down & valid_n], True
    )
    # Determinism: the oracle is a pure function (failover re-election
    # included).
    extra2, unav2, fo2 = fault_extra_ms_ref(
        hosts, keys, nodes, is_read, valid, avail, rtt, wiped=wiped, **kw
    )
    np.testing.assert_array_equal(extra_n, np.asarray(extra2))
    np.testing.assert_array_equal(unav_n, np.asarray(unav2))
    np.testing.assert_array_equal(fo_n, np.asarray(fo2))
    # Monotone in availability: reviving nodes never creates new
    # unavailability or new failovers.
    _, unav_up, fo_up = fault_extra_ms_ref(
        hosts, keys, nodes, is_read, valid, jnp.ones_like(avail), rtt,
        wiped=jnp.zeros_like(wiped), **kw
    )
    assert not np.asarray(unav_up).any()
    assert not np.asarray(fo_up).any()


@pytest.mark.parametrize("read_mode", ["map", "no_local", "ideal"])
def test_fault_prepass_invariants(read_mode):
    for seed in range(4):
        check_fault_prepass_invariants(seed, read_mode=read_mode)


def test_allup_prepass_is_bitwise_zero():
    """All nodes live + nothing wiped ⇒ the delta is x - x on identical f32
    operands: bitwise +0.0, no verdicts — the identity the engines' fault-on
    ≡ fault-off bit-exactness rests on."""
    hosts, keys, nodes, is_read, valid, _, rng = _random_fault_chunk(
        7, 512, 64, 5
    )
    rtt = jnp.asarray(
        np.where(np.eye(5), 0.0, 40.0 + rng.random((5, 5)) * 60.0)
    ).astype(jnp.float32)
    extra, unav, fo = fault_extra_ms_ref(
        hosts, keys, nodes, is_read, valid, jnp.ones((5,), bool), rtt,
        read_mode="map", master=0, xfer_write_ms=10.0,
    )
    assert not np.asarray(unav).any() and not np.asarray(fo).any()
    # Bitwise zero, positive sign — not merely allclose.
    assert np.array_equal(
        np.asarray(extra).view(np.uint32), np.zeros(512, np.uint32)
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        b=st.integers(1, 128),
        k=st.integers(1, 64),
        n=st.integers(2, 6),
        read_mode=st.sampled_from(["map", "no_local", "ideal"]),
    )
    def test_fault_prepass_invariants_fuzzed(seed, b, k, n, read_mode):
        check_fault_prepass_invariants(seed, b=b, k=k, n=n,
                                       read_mode=read_mode)

    @settings(max_examples=50, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        num_nodes=st.integers(1, 6),
        num_chunks=st.integers(1, 24),
        num_events=st.integers(1, 5),
    )
    def test_compile_schedule_fuzzed(seed, num_nodes, num_chunks, num_events):
        """Random schedules either compile to consistent timelines or are
        rejected as full blackouts — never anything else."""
        rng = np.random.default_rng(seed)
        events = tuple(
            FaultEvent(
                kind="node",
                target=int(rng.integers(num_nodes)),
                start_chunk=int(rng.integers(num_chunks + 2)),
                duration_chunks=int(rng.integers(-1, num_chunks + 2)),
                mode=("crash", "partition")[int(rng.integers(2))],
            )
            for _ in range(num_events)
        )
        cfg = FaultConfig(events=events)
        try:
            avail, crash = compile_schedule(
                cfg, num_nodes=num_nodes, num_chunks=num_chunks
            )
        except ValueError as e:
            assert "no node available" in str(e)
            return
        assert avail.any(axis=1).all()  # never a fully-dark chunk
        assert not np.any(crash & avail)  # a wiping node is never serving
        # avail is exactly the complement of the event-window union.
        expect = np.ones((num_chunks, num_nodes), bool)
        starts = np.zeros((num_chunks, num_nodes), bool)
        for ev, start, end in event_windows(cfg, num_chunks):
            expect[start:end, ev.target] = False
            if ev.mode == "crash":
                starts[start, ev.target] = True
        np.testing.assert_array_equal(avail, expect)
        np.testing.assert_array_equal(crash, starts)


# ---------------------------------------------------------------------------
# 4. Engine agreement with faults on.
# ---------------------------------------------------------------------------


def _run_fault(engine_kwargs, policy=None, telemetry=None):
    wl, cl = _fault_scenario()
    return engine_kwargs["run"](
        wl, cl._replace(faults=_outage()), policy or RedynisPolicy(),
        daemon_interval=FAULT_INTERVAL, seed=0, telemetry=telemetry,
    )


def test_engines_agree_under_region_crash():
    wl, cl = _fault_scenario()
    cl = cl._replace(faults=_outage())
    kw = dict(daemon_interval=FAULT_INTERVAL, seed=0)
    scan = run_scenario(wl, cl, RedynisPolicy(), **kw)
    ref = run_scenario_reference(wl, cl, RedynisPolicy(), **kw)
    pallas = run_scenario(wl, cl, RedynisPolicy(),
                          replay_backend="pallas", **kw)
    streamed = run_scenario(wl, cl, RedynisPolicy(),
                            trace_mode="streamed", **kw)
    assert scan.unavailable_reads > 0.0  # the drill genuinely degrades
    assert scan.failovers > 0.0
    assert scan.repair_moves > 0.0
    for counter in FAULT_COUNTERS + ("hits", "replication_moves"):
        if not hasattr(scan, counter):
            continue
        assert getattr(scan, counter) == getattr(ref, counter), counter
        assert getattr(scan, counter) == getattr(pallas, counter), counter
    np.testing.assert_allclose(scan.hit_rate, ref.hit_rate, rtol=1e-6)
    np.testing.assert_allclose(
        scan.mean_latency_ms, ref.mean_latency_ms, rtol=1e-5
    )
    np.testing.assert_allclose(
        scan.mean_latency_ms, pallas.mean_latency_ms, rtol=1e-5
    )
    # Streamed trace generation is the same program: bit-exact.
    assert_results_equal(scan, streamed, "streamed")
    # And the whole thing is deterministic run-to-run.
    again = run_scenario(wl, cl, RedynisPolicy(), **kw)
    assert_results_equal(scan, again, "determinism")


def test_fault_telemetry_series_sum_to_counters():
    wl, cl = _fault_scenario()
    cl = cl._replace(faults=_outage())
    kw = dict(daemon_interval=FAULT_INTERVAL, seed=0,
              telemetry=TelemetryConfig())
    res, trace = run_scenario(wl, cl, RedynisPolicy(), **kw)
    np.testing.assert_allclose(
        trace.unavailable_reads.sum(), res.unavailable_reads
    )
    np.testing.assert_allclose(
        trace.unavailable_writes.sum(), res.unavailable_writes
    )
    np.testing.assert_allclose(trace.failovers.sum(), res.failovers)
    np.testing.assert_allclose(trace.repair_moves.sum(), res.repair_moves)
    # The reference engine's trace agrees chunk-for-chunk.
    _, ref_trace = run_scenario_reference(wl, cl, RedynisPolicy(), **kw)
    for leaf in ("unavailable_reads", "unavailable_writes", "failovers",
                 "repair_moves"):
        np.testing.assert_array_equal(
            getattr(trace, leaf), getattr(ref_trace, leaf), err_msg=leaf
        )
    np.testing.assert_allclose(
        trace.unreachable_frac, ref_trace.unreachable_frac, atol=1e-7
    )
    np.testing.assert_allclose(
        trace.wiped_frac, ref_trace.wiped_frac, atol=1e-7
    )


# ---------------------------------------------------------------------------
# 5. Degraded-mode behaviour: availability, blast radius, re-convergence.
# ---------------------------------------------------------------------------


def test_availability_dips_inside_outage_and_recovers():
    wl, cl = _fault_scenario()
    res, trace = run_scenario(
        wl, cl._replace(faults=_outage()), RedynisPolicy(),
        daemon_interval=FAULT_INTERVAL, seed=0,
        telemetry=TelemetryConfig(),
    )
    avail = trace.availability
    assert avail.shape == (NUM_CHUNKS,)
    np.testing.assert_array_equal(avail[:OUTAGE_START], 1.0)
    assert avail[OUTAGE_START:OUTAGE_END].min() < 1.0
    # After the region rejoins, one chunk of dark reads on still-wiped keys
    # remains (the daemon re-seeds at that chunk's END); from the next
    # chunk on nothing is refused.
    assert avail[OUTAGE_END] > avail[OUTAGE_START:OUTAGE_END].min()
    np.testing.assert_array_equal(avail[OUTAGE_END + 1:], 1.0)
    # Blast radius: fractions are sane, peak inside the window, and the
    # crash wiped a strictly positive slice of the keyspace.
    assert np.all((trace.unreachable_frac >= 0.0)
                  & (trace.unreachable_frac <= 1.0))
    assert np.all((trace.wiped_frac >= 0.0) & (trace.wiped_frac <= 1.0))
    np.testing.assert_array_equal(trace.unreachable_frac[:OUTAGE_START], 0.0)
    rows = blast_radius_rows(
        _outage(), num_chunks=NUM_CHUNKS,
        unreachable_frac=trace.unreachable_frac,
        wiped_frac=trace.wiped_frac,
    )
    assert len(rows) == 1
    assert rows[0]["blast_radius_unreachable"] > 0.0
    assert rows[0]["blast_radius_wiped"] > 0.0
    assert (rows[0]["blast_radius_wiped"]
            <= rows[0]["blast_radius_unreachable"])
    # Effective hit rate (unavailable reads count as misses) recovers to
    # 95% of its pre-outage steady state at a finite chunk.
    rec = trace.recovery_chunks(OUTAGE_START)
    assert rec >= 0
    assert OUTAGE_START + rec < NUM_CHUNKS


def test_redynis_repairs_static_cannot():
    wl, cl = _fault_scenario()
    cl = cl._replace(faults=_outage())
    kw = dict(daemon_interval=FAULT_INTERVAL, seed=0)
    dyn = run_scenario(wl, cl, RedynisPolicy(), **kw)
    static = run_scenario(wl, cl, StaticPolicy(mode="replicated"), **kw)
    # The daemon re-seeds crash-wiped keys; a static map never sweeps, so
    # its crashed copies stay lost for the rest of the trace.
    assert dyn.repair_moves > 0.0
    assert static.repair_moves == 0.0
    assert static.unavailable_reads > 0.0


def test_partition_is_loss_free():
    """The same outage as a partition refuses requests while it lasts but
    wipes nothing: no repair work exists even for redynis, and the map
    serves again the chunk the partition heals."""
    wl, cl = _fault_scenario()
    part = region_outage(0, OUTAGE_START, OUTAGE_LEN, mode="partition")
    res, trace = run_scenario(
        wl, cl._replace(faults=part), RedynisPolicy(),
        daemon_interval=FAULT_INTERVAL, seed=0,
        telemetry=TelemetryConfig(),
    )
    assert res.unavailable_reads > 0.0
    np.testing.assert_array_equal(trace.wiped_frac, 0.0)
    np.testing.assert_array_equal(trace.availability[OUTAGE_END:], 1.0)


# ---------------------------------------------------------------------------
# 6. Sharded equivalence with faults on (2 virtual ranks).
# ---------------------------------------------------------------------------


SHARDED_FAULT_SCRIPT = r"""
import numpy as np
from repro.kvsim import (run_scenario, wan5_workload, wan5_cluster,
                         RedynisPolicy, TelemetryConfig, region_outage)

wl = wan5_workload(num_requests=20000, num_keys=401, affinity=0.8,
                   read_fraction=0.7)
cl = wan5_cluster()._replace(faults=region_outage(0, 60, 40))
kw = dict(seed=3, daemon_interval=100, telemetry=TelemetryConfig())
r1, t1 = run_scenario(wl, cl, RedynisPolicy(), **kw)
r2, t2 = run_scenario(wl, cl, RedynisPolicy(), num_shards=2, **kw)
assert r1.unavailable_reads > 0.0 and r1.repair_moves > 0.0
# Counter surfaces: bit-exact under psum (K=401 exercises the
# ceil-division padding alongside the sharded wiped-key carry).
for f in ('unavailable_reads', 'unavailable_writes', 'failovers',
          'repair_moves', 'hit_rate', 'replication_moves'):
    assert getattr(r1, f) == getattr(r2, f), f
np.testing.assert_array_equal(t1.unavailable_reads, t2.unavailable_reads)
np.testing.assert_array_equal(t1.repair_moves, t2.repair_moves)
# The blast-radius fractions are emitted globally at the sample point, so
# shard counts must agree exactly too.
np.testing.assert_allclose(t1.unreachable_frac, t2.unreachable_frac,
                           atol=1e-7)
np.testing.assert_allclose(t1.wiped_frac, t2.wiped_frac, atol=1e-7)
np.testing.assert_allclose(r1.mean_latency_ms, r2.mean_latency_ms,
                           rtol=1e-4)
print('SHARDED_FAULT_EQUIVALENCE_OK')
"""


def test_sharded_faults_match_single_device(run_multi_rank):
    out = run_multi_rank(SHARDED_FAULT_SCRIPT, num_devices=2, timeout=600)
    assert "SHARDED_FAULT_EQUIVALENCE_OK" in out
