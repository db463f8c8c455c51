"""Engine-throughput benchmark: simulator requests/sec across engines.

The scan engine is the product's hot loop — every Figure 2/3 point, policy
grid, capacity sweep, and tail-latency table replays millions of requests
through the per-chunk request path. This benchmark plants the
``BENCH_engine_throughput.json`` trendline later PRs defend:

  * **grid rows** — warm-run ``run_scenario`` throughput in simulated
    requests/sec across engine × chunk-replay backend × daemon_interval ×
    num_keys (× policy, × telemetry on/off).
  * **speedup rows** — the same configs replayed through a faithful
    in-file replica of the PRE-fusion engine (``_legacy_simulate``: four
    separate latency passes, per-chunk O(K·N) occupancy for every policy,
    the telemetry histogram as a separate dispatch), so the fusion win is
    measurable from a single post-PR checkout.
  * **acceptance row** (``--acceptance``) — the ISSUE-5 criterion: warm
    ``run_scenario`` with telemetry on, wan5 topology, skewed traffic,
    1M requests, at the paper's access density (100 accesses/key ⇒
    num_keys = num_requests/100) must beat the pre-fusion engine ≥ 2x.

Methodology: sim-requests/sec = num_requests / wall-clock of one warm
``run_scenario`` call (compile + cache warmup excluded; median of
``--repeats`` (default 5) timed calls is the recorded trendline number).
Speedup ratios divide the per-side *minima* instead — contention noise on
shared runners is strictly additive, so min is the robust estimator of
true program cost (see ``_measure``). Timed work includes trace
generation and host-side result/trace materialisation, exactly what
every driver pays.

Since the scale-out fabric (ISSUE 7) the grid also measures
``trace_mode="streamed"`` rows for the scan engine (in-scan trace
generation, bit-exact with the materialised path), every row records
``peak_live_bytes`` — the analytic peak live-buffer footprint (trace
window + per-key state planes, the O(requests) → O(chunk + keys/shard)
memory win as a tracked column — see ``_peak_live_bytes``), and
``--trendline`` adds the multi-device scaling trendline: for each shard
count S, this process runs the key-sharded streamed engine over
``jax.devices()[:S]`` — routing tier off AND on — and reports
requests/sec, ``scaling_vs_1shard``, and ``routing_on_off_ratio`` (the
directory tier's wall-clock cost multiple, a machine-independent ratio
since PR 8). One process holds every device, so on a TPU host no child
ever competes for a chip; the CPU rehearsal sets
``XLA_FLAGS=--xla_force_host_platform_device_count=S`` (the largest
count) once, before the script starts. The spec-scale run targets 100M+
requests over 10⁷ keys
(``--trendline-requests 100000000 --trendline-keys 10000000``);
the checked-in baseline records a CI-tractable configuration of the same
shape. ``--scale-acceptance`` times one ≥10M-request streamed run on a
single device (the run the materialised path cannot fit at accelerator
HBM scale).

``--baseline PATH`` (default: the checked-in
``benchmarks/baselines/BENCH_engine_throughput.json``) warns —
``WARNING,engine_throughput_regression,...`` lines — when any matching grid
row regresses more than 20%. Absolute requests/sec warnings never fail the
job (wall-clock noise across runners makes that gate flaky), but
``--fail-on-regression`` promotes the *ratio* warnings to a hard nonzero
exit: fused and legacy engines run on the same box, so the
``speedup_vs_legacy`` ratio is machine-independent and a >20% drop there is
a genuine code-path regression, not runner noise — and the same logic
covers the trendline's sharded-vs-single-device ``scaling_vs_1shard``
ratios (both sides of that ratio also share one box).

Note on ``--backends pallas`` off-TPU: the Mosaic kernel runs in interpret
mode on CPU (a correctness/compile-path row, orders of magnitude slower
than compiled code); perf rows for the pallas backend are only meaningful
on a real TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    WAN5_WORKLOAD_KWARGS,
    banner,
    emit,
    enable_compile_cache,
    write_bench_json,
)
from repro.core.metadata import record_accesses
from repro.core.policy import (
    PolicyContext,
    parse_policy,
    policy_masked_step,
    split_policy,
)
from repro.kvsim import (
    RoutingConfig,
    SimResult,
    TelemetryConfig,
    WorkloadConfig,
    run_scenario,
    wan5_cluster,
)
from repro.kvsim.simulate import (
    _chunk_latency,
    _initial_hosts,
    _node_occupancy,
    _seed_store,
)
from repro.kvsim.telemetry import (
    TelemetryLeaves,
    build_trace,
    chunk_histogram,
    normalize_telemetry,
)
from repro.kvsim.workload import generate_trace

DEFAULT_BASELINE = os.path.join(
    os.path.dirname(__file__), "baselines", "BENCH_engine_throughput.json"
)


# ---------------------------------------------------------------------------
# The pre-fusion engine, preserved verbatim as the speedup baseline.
# ---------------------------------------------------------------------------


def _legacy_simulate(
    keys, nodes, is_read, natural, object_bytes, params, *,
    cluster, policy, daemon_interval, telemetry=None,
):
    """The PRE-ISSUE-5 scan body: separate read/write/hit/busy passes over
    [B, N] intermediates, the O(K·N) occupancy sample paid per chunk for
    EVERY policy (including static maps that never change), and the
    telemetry histogram folded by a separate dispatch after the latency
    pass. Kept verbatim so ``speedup_vs_legacy`` measures exactly what the
    fusion + hoist bought."""
    r = keys.shape[0]
    num_keys = natural.shape[0]
    n = cluster.num_nodes
    rtt = cluster.rtt_matrix()
    obj = jnp.asarray(object_bytes, jnp.float32)
    capacity = (
        cluster.capacity_vector() if cluster.has_finite_capacity else None
    )
    ctx = PolicyContext(
        rtt=rtt, object_bytes=obj, capacity_bytes=capacity, params=params
    )
    num_chunks = -(-r // daemon_interval)
    pad = num_chunks * daemon_interval - r

    def chunked(x):
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
        return x.reshape(num_chunks, daemon_interval)

    xs = (
        jnp.arange(num_chunks, dtype=jnp.int32),
        chunked(keys), chunked(nodes), chunked(is_read),
        (jnp.arange(num_chunks * daemon_interval) < r).reshape(
            num_chunks, daemon_interval
        ),
    )
    store = _seed_store(
        _initial_hosts(natural, num_keys, n, policy.initial_placement),
        num_keys, n,
    )
    pstate = policy.init(store, ctx)
    zero = jnp.float32(0.0)
    init = (
        store, pstate, jnp.zeros((n,), jnp.float32), zero, zero, zero, zero,
        zero, zero, zero, _node_occupancy(store.hosts, obj),
    )

    def body(carry, x):
        (store, pstate, busy, lat_sum, hits, reads, repl, drop, evic,
         cap_evic, peak) = carry
        c, ck, cn, cr, cv = x
        lat, read_hits = _chunk_latency(
            store.hosts, ck, cn, cr, rtt, cluster, policy.read_mode
        )
        lat = jnp.where(cv, lat, 0.0)
        chunk_lat = jnp.sum(lat)
        chunk_hits = jnp.sum((read_hits & cv).astype(jnp.float32))
        chunk_reads = jnp.sum((cr & cv).astype(jnp.float32))
        busy = busy.at[cn].add(lat)
        lat_sum = lat_sum + chunk_lat
        hits = hits + chunk_hits
        reads = reads + chunk_reads
        occ = _node_occupancy(store.hosts, obj)  # paid per chunk, always
        peak = jnp.maximum(peak, occ)
        zero = jnp.float32(0.0)
        chunk_moves = (zero, zero, zero, zero)
        if policy.is_active:
            store = record_accesses(store, ck, cn, now=c, valid=cv)
            stats, pstate, store = policy_masked_step(
                policy, pstate, store, c, (c % policy.period) == 0, ctx
            )
            repl, drop = repl + stats.adds, drop + stats.drops
            evic = evic + stats.expiry_evictions
            cap_evic = cap_evic + stats.capacity_evictions
            chunk_moves = (
                stats.adds, stats.drops, stats.expiry_evictions,
                stats.capacity_evictions,
            )
        if telemetry is None:
            ys = None
        else:
            w = cv.astype(jnp.float32)
            ys = TelemetryLeaves(
                hist=chunk_histogram(
                    lat, cn * 2 + cr.astype(jnp.int32), w, telemetry, n
                ),
                hits=chunk_hits, reads=chunk_reads, lat_sum=chunk_lat,
                count=jnp.sum(w), adds=chunk_moves[0], drops=chunk_moves[1],
                expiry_evictions=chunk_moves[2],
                capacity_evictions=chunk_moves[3], occupancy=occ,
            )
        return (
            store, pstate, busy, lat_sum, hits, reads, repl, drop, evic,
            cap_evic, peak,
        ), ys

    (_, _, busy, lat_sum, hits, reads, repl, drop, evic, cap_evic, peak), ys = (
        jax.lax.scan(body, init, xs)
    )
    makespan_ms = jnp.max(busy)
    return (
        r / (makespan_ms / 1000.0), hits / jnp.maximum(reads, 1.0),
        lat_sum / r, busy, repl, drop, evic, cap_evic, peak,
    ), ys


_legacy_simulate_jit = partial(
    jax.jit, static_argnames=("cluster", "policy", "daemon_interval", "telemetry")
)(_legacy_simulate)


def legacy_run_scenario(workload, cluster, policy, seed=0,
                        daemon_interval=1000, telemetry=None):
    """``run_scenario``-equivalent driver over the pre-fusion engine (same
    host-side work: trace generation, result + trace materialisation)."""
    policy = policy.resolve(workload.num_nodes)
    policy.validate(workload.num_nodes)
    static, params = split_policy(policy)
    telemetry = normalize_telemetry(telemetry)
    trace = generate_trace(workload, seed)
    leaves, telem = _legacy_simulate_jit(
        trace.keys, trace.nodes, trace.is_read, trace.natural_node,
        trace.object_bytes, params, cluster=cluster, policy=static,
        daemon_interval=daemon_interval, telemetry=telemetry,
    )
    tput, hit, mean_lat, busy, repl, drop, evic, cap_evic, peak = leaves
    result = SimResult(
        throughput_ops_s=float(tput), hit_rate=float(hit),
        mean_latency_ms=float(mean_lat),
        node_busy_ms=np.asarray(busy, dtype=np.float64),
        replication_moves=float(repl), deletion_moves=float(drop),
        evictions=float(evic), capacity_evictions=float(cap_evic),
        peak_occupancy_bytes=np.asarray(peak, dtype=np.float64),
    )
    if telemetry is None:
        return result
    return result, build_trace(telem, telemetry)


# ---------------------------------------------------------------------------
# Measurement grid.
# ---------------------------------------------------------------------------


def _wan5_workload(num_requests, num_keys):
    return WorkloadConfig(
        num_requests=num_requests, num_keys=num_keys, skewed=True,
        read_fraction=0.9, **WAN5_WORKLOAD_KWARGS,
    )


def _peak_live_bytes(num_requests, num_keys, num_nodes, daemon_interval,
                     trace_mode, num_shards=1):
    """Analytic peak live-buffer bytes per device: trace window + per-key
    state planes (the buffers whose lifetime spans the scan — compiler
    scratch excluded, so this is the memory *model*, comparable across
    modes, not an allocator measurement).

    Trace rows cost 9 bytes (i32 key + i32 node + bool is_read): the whole
    ``[R]`` trace when materialised, one ``[daemon_interval]`` window when
    streamed. Per-key planes (sharded: ``K/S`` rows per device): natural +
    object_bytes (8 B) and the metadata store — access_counts ``[K, N]``
    i32, hosts ``[K, N]`` bool, last_access/live/home (9 B) — i.e.
    ``17 + 5·N`` bytes per key."""
    trace_rows = daemon_interval if trace_mode == "streamed" else num_requests
    keys_local = num_keys // num_shards
    return trace_rows * 9 + keys_local * (17 + 5 * num_nodes)


def _measure(engine, policy, workload, cluster, daemon_interval, telemetry,
             replay_backend, repeats, trace_mode="materialized",
             num_shards=1):
    """Warm wall-times of one full scenario run: ``(median_s, min_s)``.

    The JSON trendline records the median (the BENCH methodology); speedup
    ratios use the min of each side — on shared runners, contention noise
    is strictly additive, so the minimum is the robust estimator of the
    actual program cost and the ratio of minima is stable where a ratio of
    medians swings with whatever else the box is doing.
    """
    if engine == "legacy":
        fn = lambda: legacy_run_scenario(
            workload, cluster, policy, seed=0,
            daemon_interval=daemon_interval, telemetry=telemetry,
        )
    else:
        fn = lambda: run_scenario(
            workload, cluster, policy, seed=0,
            daemon_interval=daemon_interval, telemetry=telemetry,
            replay_backend=replay_backend, trace_mode=trace_mode,
            num_shards=num_shards,
        )
    for _ in range(2):  # compile + cache warmup
        fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), float(np.min(times))


def _row_key(row):
    return (
        row["engine"], row["policy"], row["replay_backend"],
        row["daemon_interval"], row["num_keys"], row["telemetry"],
        row["num_requests"], row.get("trace_mode", "materialized"),
        row.get("num_shards", 1),
    )


def _trendline_key(row):
    return (
        row["policy"], row["num_requests"], row["num_keys"],
        row["num_shards"],
    )


def _speedup_key(row):
    return (
        row["policy"], row["daemon_interval"], row["num_keys"],
        row["telemetry"], row["num_requests"],
    )


def check_regression(rows, baseline_path, threshold=0.20, speedups=None,
                     trendline=None):
    """Warn when a grid row is >20% below the checked-in baseline for the
    identical configuration; returns the warned rows, each tagged with
    ``"kind"`` so callers can gate selectively.

    Three signals: absolute requests/sec (``kind="throughput"``,
    machine-DEPENDENT — a slower runner trips it without any code change,
    so it only ever warns) and two machine-independent ratios
    ``--fail-on-regression`` hard-gates on: the ``speedup_vs_legacy``
    ratios (``kind="speedup"`` — fused and legacy engines run on the same
    box) and the trendline's ``scaling_vs_1shard`` ratios
    (``kind="scaling"`` — the sharded and 1-shard runs share one box too,
    so a drop means the sharded program itself regressed, e.g. a collective
    that grew from psum to all-gather). The trendline's
    ``routing_on_off_ratio`` (``kind="routing"`` — both sides share one
    process) gates in the OTHER direction: a ratio that GREW >20% over the
    baseline means the routing tier itself got more expensive."""
    if not os.path.exists(baseline_path):
        print(f"NOTE,no baseline at {baseline_path}, skipping regression check")
        return []
    with open(baseline_path) as fh:
        base_metrics = json.load(fh)["metrics"]
    base = {
        tuple(_row_key(r)): r["requests_per_s"]
        for r in base_metrics["rows"]
    }
    base_speedups = {
        tuple(_speedup_key(r)): r["speedup_vs_legacy"]
        for r in base_metrics.get("speedups", [])
    }
    base_trend = {
        tuple(_trendline_key(r)): r["scaling_vs_1shard"]
        for r in base_metrics.get("trendline", [])
    }
    base_routing = {
        tuple(_trendline_key(r)): r["routing_on_off_ratio"]
        for r in base_metrics.get("trendline", [])
        if "routing_on_off_ratio" in r
    }
    warned, matched = [], 0
    for row in trendline or []:
        ref = base_trend.get(tuple(_trendline_key(row)))
        if ref is not None and ref > 0 and row["num_shards"] > 1:
            ratio = row["scaling_vs_1shard"] / ref
            if ratio < 1.0 - threshold:
                warned.append({"kind": "scaling", **row})
                print(
                    "WARNING,engine_scaling_regression,"
                    f"shards={row['num_shards']}/nk={row['num_keys']},"
                    f"now={row['scaling_vs_1shard']:.2f}x,baseline={ref:.2f}x,"
                    f"ratio={ratio:.2f}",
                    flush=True,
                )
        ref = base_routing.get(tuple(_trendline_key(row)))
        if ref is not None and ref > 0 and "routing_on_off_ratio" in row:
            # Inverted sense: this ratio is a COST multiple (routing-on /
            # routing-off wall time), so growth is the regression.
            ratio = row["routing_on_off_ratio"] / ref
            if ratio > 1.0 + threshold:
                warned.append({"kind": "routing", **row})
                print(
                    "WARNING,engine_routing_overhead_regression,"
                    f"shards={row['num_shards']}/nk={row['num_keys']},"
                    f"now={row['routing_on_off_ratio']:.2f}x,"
                    f"baseline={ref:.2f}x,ratio={ratio:.2f}",
                    flush=True,
                )
    for row in speedups or []:
        ref = base_speedups.get(tuple(_speedup_key(row)))
        if ref is None or ref <= 0:
            continue
        ratio = row["speedup_vs_legacy"] / ref
        if ratio < 1.0 - threshold:
            warned.append({"kind": "speedup", **row})
            print(
                "WARNING,engine_speedup_regression,"
                f"{row['policy']}/di={row['daemon_interval']}/"
                f"nk={row['num_keys']},"
                f"now={row['speedup_vs_legacy']:.2f}x,baseline={ref:.2f}x,"
                f"ratio={ratio:.2f}",
                flush=True,
            )
    for row in rows:
        ref = base.get(tuple(_row_key(row)))
        if ref is None or ref <= 0:
            continue
        matched += 1
        ratio = row["requests_per_s"] / ref
        if ratio < 1.0 - threshold:
            warned.append({"kind": "throughput", **row})
            print(
                "WARNING,engine_throughput_regression,"
                f"{row['engine']}/{row['policy']}/{row['replay_backend']},"
                f"now={row['requests_per_s']:.0f},baseline={ref:.0f},"
                f"ratio={ratio:.2f} (absolute req/s — machine-dependent)",
                flush=True,
            )
    if matched == 0:
        # An all-clear here would hide a drifted sweep config silently
        # disabling the check.
        print(
            f"WARNING,engine_throughput_baseline_mismatch,0 of {len(rows)} "
            f"grid rows matched {baseline_path} — regression check did not "
            f"run (sweep config drifted from the checked-in baseline?)",
            flush=True,
        )
    elif not warned:
        print(
            f"NOTE,engine_throughput within 20% of baseline "
            f"({matched} rows compared)",
            flush=True,
        )
    return warned


# ---------------------------------------------------------------------------
# Multi-device trendline: every shard count measured in this one process.
# ---------------------------------------------------------------------------

TRENDLINE_DEVICE_COUNTS = (1, 2, 4, 8)


# The routing-tier configuration the trendline prices: lagged publishes
# (ring buffer in the carry) with the unbounded/warm cache — the always-on
# consult + mis-route-pricing path every routed request pays. The bounded
# decay-LFU cache is deliberately excluded: its per-chunk [R, K] top_k (+
# all_gather when sharded) costs 3-20x and scales with the shard count,
# which would swamp the ratio with one optional feature's cost and make
# the 20%-growth CI gate flaky.
def _trendline_routing(num_keys):
    return RoutingConfig(publish_lag_chunks=8)


def _trendline_row(num_shards, num_requests, num_keys, repeats,
                   daemon_interval, policy_spec):
    """Measure the streamed key-sharded run over ``num_shards`` devices
    with the routing tier OFF and ON.

    ``routing_on_off_ratio`` divides per-side minima (routing-on /
    routing-off wall time, so 1.10 = the directory tier costs 10%): both
    sides share one box AND one process, so the ratio is machine-
    independent and regression-gateable like ``speedup_vs_legacy``."""
    pol = parse_policy(policy_spec)
    wl = _wan5_workload(num_requests, num_keys)
    cluster = wan5_cluster()
    med, lo = _measure(
        "scan", pol, wl, cluster, daemon_interval, None, "jax",
        repeats, trace_mode="streamed", num_shards=num_shards,
    )
    routed = cluster._replace(routing=_trendline_routing(num_keys))
    med_on, lo_on = _measure(
        "scan", pol, wl, routed, daemon_interval, None, "jax",
        repeats, trace_mode="streamed", num_shards=num_shards,
    )
    return {
        "policy": policy_spec, "num_requests": num_requests,
        "num_keys": num_keys, "num_shards": num_shards,
        "daemon_interval": daemon_interval, "trace_mode": "streamed",
        "wall_s": med, "wall_s_min": lo,
        "requests_per_s": num_requests / med,
        "wall_s_routing_on": med_on, "wall_s_min_routing_on": lo_on,
        "requests_per_s_routing_on": num_requests / med_on,
        "routing_on_off_ratio": lo_on / lo,
        "peak_live_bytes": _peak_live_bytes(
            num_requests, num_keys, wl.num_nodes, daemon_interval,
            "streamed", num_shards,
        ),
    }


def run_trendline(device_counts, num_requests, num_keys, repeats,
                  daemon_interval, policy_spec):
    """The multi-device scaling trendline: one row per shard count, each
    measured over the first ``S`` of this process's devices.

    ``scaling_vs_1shard`` divides per-count minima (same robustness
    argument as ``speedup_vs_legacy``: both sides share one box)."""
    have = len(jax.devices())
    if max(device_counts) > have:
        raise SystemExit(
            f"FAIL,trendline needs {max(device_counts)} devices, have {have} "
            f"(CPU: set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{max(device_counts)} before starting the script)"
        )
    banner(
        f"trendline: streamed sharded engine, {num_requests:,} requests / "
        f"{num_keys:,} keys, device counts {tuple(device_counts)}"
    )
    rows = [
        _trendline_row(s, num_requests, num_keys, repeats, daemon_interval,
                       policy_spec)
        for s in device_counts
    ]
    base_min = rows[0]["wall_s_min"]
    for row in rows:
        row["scaling_vs_1shard"] = base_min / row["wall_s_min"]
        emit(
            "engine_trendline", round(row["requests_per_s"]), "req/s",
            num_shards=row["num_shards"], num_keys=row["num_keys"],
            num_requests=row["num_requests"],
            scaling_vs_1shard=round(row["scaling_vs_1shard"], 3),
            routing_on_off_ratio=round(row["routing_on_off_ratio"], 3),
            peak_live_mib=round(row["peak_live_bytes"] / 2**20, 1),
        )
    return rows


def run_scale_acceptance(num_requests, num_keys, daemon_interval,
                         policy_spec):
    """The ISSUE-7 streamed-scale criterion: a ≥10M-request streamed run
    completes on ONE device — peak live buffers O(daemon_interval + K), vs
    the O(R) trace the materialised path would have to hold."""
    banner(
        f"scale acceptance: streamed {num_requests:,}-request run, "
        "single device"
    )
    pol = parse_policy(policy_spec)
    wl = _wan5_workload(num_requests, num_keys)
    med, lo = _measure(
        "scan", pol, wl, wan5_cluster(), daemon_interval, None, "jax",
        repeats=1, trace_mode="streamed",
    )
    row = {
        "policy": policy_spec, "num_requests": num_requests,
        "num_keys": num_keys, "trace_mode": "streamed",
        "wall_s": med, "requests_per_s": num_requests / med,
        "peak_live_bytes": _peak_live_bytes(
            num_requests, num_keys, wl.num_nodes, daemon_interval,
            "streamed",
        ),
        "materialized_trace_bytes": _peak_live_bytes(
            num_requests, num_keys, wl.num_nodes, daemon_interval,
            "materialized",
        ),
        "passed": num_requests >= 10_000_000,
    }
    emit(
        "engine_scale_acceptance", round(row["requests_per_s"]), "req/s",
        num_requests=num_requests, num_keys=num_keys,
        peak_live_mib=round(row["peak_live_bytes"] / 2**20, 1),
        materialized_mib=round(row["materialized_trace_bytes"] / 2**20, 1),
    )
    print(
        f"ACCEPTANCE,{'PASS' if row['passed'] else 'FAIL'},streamed "
        f"{num_requests:,} requests in {med:.2f}s on one device "
        f"(live {row['peak_live_bytes'] / 2**20:.1f} MiB vs "
        f"{row['materialized_trace_bytes'] / 2**20:.1f} MiB materialised)",
        flush=True,
    )
    return row


def main(
    num_requests: int = 200_000,
    repeats: int = 5,
    daemon_intervals=(1000,),
    num_keys_grid=(1_000, 10_000),
    policy_specs=("replicated", "redynis"),
    backends=("jax",),
    engines=("scan", "legacy"),
    telemetry_modes=(True, False),
    trace_modes=("materialized", "streamed"),
    acceptance: bool = False,
    baseline: str | None = DEFAULT_BASELINE,
    policy=None,
    replay_backend: str | None = None,
    fail_on_regression: bool = False,
    trendline: bool = False,
    trendline_devices=TRENDLINE_DEVICE_COUNTS,
    trendline_requests: int = 2_000_000,
    trendline_keys: int = 200_000,
    trendline_policy: str = "redynis",
    scale_acceptance: bool = False,
    scale_requests: int = 10_000_000,
    scale_keys: int = 1_000_000,
    scale_policy: str = "replicated",
) -> dict:
    banner("engine_throughput: simulator requests/sec, fused vs pre-fusion")
    if replay_backend is not None:
        # benchmarks/run.py forwards a single --replay-backend; measure
        # that backend only.
        backends = (replay_backend,)
    if "jax" not in backends:
        # speedup_vs_legacy compares legacy/jax against scan/jax; without
        # a jax scan row the legacy timings would be dead weight.
        engines = tuple(e for e in engines if e != "legacy")
    cluster = wan5_cluster()
    telem_cfg = TelemetryConfig()
    rows, speedups = [], []
    t_start = time.perf_counter()

    candidates = [parse_policy(s) for s in policy_specs]
    if policy is not None:
        candidates.append(policy)

    for pol in candidates:
        label = getattr(type(pol), "name", type(pol).__name__)
        label = f"{label}:{pol.mode}" if hasattr(pol, "mode") else label
        for di in daemon_intervals:
            for nk in num_keys_grid:
                wl = _wan5_workload(num_requests, nk)
                for telem_on in telemetry_modes:
                    telem = telem_cfg if telem_on else None
                    times = {}
                    for engine in engines:
                        bkds = backends if engine == "scan" else ("jax",)
                        # Streamed trace generation exists only in the
                        # fused scan engine; the legacy replica predates it.
                        tms = (
                            trace_modes if engine == "scan"
                            else ("materialized",)
                        )
                        for bk in bkds:
                            for tm in tms:
                                med, lo = _measure(
                                    engine, pol, wl, cluster, di, telem, bk,
                                    repeats, trace_mode=tm,
                                )
                                if tm == "materialized":
                                    times[(engine, bk)] = lo
                                row = {
                                    "engine": engine, "policy": label,
                                    "replay_backend": bk,
                                    "daemon_interval": di,
                                    "num_keys": nk, "telemetry": telem_on,
                                    "num_requests": num_requests,
                                    "trace_mode": tm,
                                    "num_shards": 1,
                                    "wall_s": med,
                                    "wall_s_min": lo,
                                    "requests_per_s": num_requests / med,
                                    "peak_live_bytes": _peak_live_bytes(
                                        num_requests, nk, wl.num_nodes,
                                        di, tm,
                                    ),
                                }
                                rows.append(row)
                                emit(
                                    "engine_throughput",
                                    round(row["requests_per_s"]),
                                    "req/s",
                                    engine=engine, policy=label, backend=bk,
                                    daemon_interval=di, num_keys=nk,
                                    telemetry=int(telem_on), trace_mode=tm,
                                    wall_s=round(med, 4),
                                    peak_live_mib=round(
                                        row["peak_live_bytes"] / 2**20, 2
                                    ),
                                )
                    if ("legacy", "jax") in times and ("scan", "jax") in times:
                        speedup = times[("legacy", "jax")] / times[("scan", "jax")]
                        speedups.append({
                            "policy": label, "daemon_interval": di,
                            "num_keys": nk, "telemetry": telem_on,
                            "num_requests": num_requests,
                            "speedup_vs_legacy": speedup,
                        })
                        emit(
                            "engine_speedup", round(speedup, 2), "x",
                            policy=label, daemon_interval=di, num_keys=nk,
                            telemetry=int(telem_on),
                        )

    accept = None
    if acceptance:
        # ISSUE-5 acceptance: wan5, skewed, 1M requests, telemetry ON, the
        # paper's access density (100 accesses/key) held at scale. Both
        # daemon cadences are reported; speedups are ratios of per-side
        # minima (see _measure).
        banner("acceptance: 1M-request warm run_scenario vs pre-fusion engine")
        a_req = 1_000_000
        wl = _wan5_workload(a_req, a_req // 100)
        accept = {"num_requests": a_req, "num_keys": a_req // 100,
                  "telemetry": True, "rows": []}
        for di in (1000, 500):
            for spec in policy_specs:
                pol = parse_policy(spec)
                _, t_new = _measure("scan", pol, wl, cluster, di, telem_cfg,
                                    "jax", repeats)
                _, t_old = _measure("legacy", pol, wl, cluster, di, telem_cfg,
                                    "jax", repeats)
                speedup = t_old / t_new
                accept["rows"].append({
                    "policy": spec, "daemon_interval": di,
                    "fused_wall_s": t_new, "legacy_wall_s": t_old,
                    "fused_req_per_s": a_req / t_new,
                    "legacy_req_per_s": a_req / t_old,
                    "speedup_vs_legacy": speedup,
                })
                emit(
                    "engine_acceptance", round(speedup, 2), "x", policy=spec,
                    daemon_interval=di,
                    fused_req_per_s=round(a_req / t_new),
                    legacy_req_per_s=round(a_req / t_old),
                )
        best = max(v["speedup_vs_legacy"] for v in accept["rows"])
        accept["passed"] = best >= 2.0
        print(
            f"ACCEPTANCE,{'PASS' if accept['passed'] else 'FAIL'},"
            f"best_speedup={best:.2f}x (need >= 2x)",
            flush=True,
        )

    trend_rows = None
    if trendline:
        trend_rows = run_trendline(
            tuple(trendline_devices), trendline_requests, trendline_keys,
            repeats, daemon_intervals[0], trendline_policy,
        )
    scale_row = None
    if scale_acceptance:
        # A static policy by design: the criterion is the streamed-trace
        # MEMORY model (O(chunk + keys), policy-independent); an active
        # policy's O(K·N)-per-tick sweep would just drown the measurement.
        scale_row = run_scale_acceptance(
            scale_requests, scale_keys, daemon_intervals[0], scale_policy
        )

    warned = (
        check_regression(
            rows, baseline, speedups=speedups, trendline=trend_rows
        )
        if baseline else []
    )
    metrics = {
        "rows": rows,
        "speedups": speedups,
        "regressions": len(warned),
        "wall_time_s": time.perf_counter() - t_start,
    }
    if accept is not None:
        metrics["acceptance"] = accept
    if trend_rows is not None:
        metrics["trendline"] = trend_rows
    if scale_row is not None:
        metrics["scale_acceptance"] = scale_row
    write_bench_json(
        "engine_throughput", metrics,
        num_requests=num_requests, repeats=repeats,
        backend_platform=jax.default_backend(),
        topology="wan5", skewed=True, read_fraction=0.9,
    )
    if fail_on_regression:
        hard = [
            w for w in warned
            if w.get("kind") in ("speedup", "scaling", "routing")
        ]
        if hard:
            raise SystemExit(
                f"FAIL,engine_ratio_regression,{len(hard)} machine-"
                f"independent ratio(s) (fused-vs-legacy speedup, sharded-"
                f"vs-1-shard scaling, or routing-tier on/off overhead) "
                f">20% off baseline (see WARNING lines above)"
            )
    return metrics


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-requests", type=int, default=200_000)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--daemon-intervals", nargs="+", type=int, default=[1000])
    ap.add_argument("--num-keys", nargs="+", type=int, default=[1_000, 10_000])
    ap.add_argument(
        "--policies", nargs="+", default=["replicated", "redynis"],
        metavar="NAME[:k=v,...]",
    )
    ap.add_argument(
        "--backends", nargs="+", default=["jax"], choices=["jax", "pallas"],
        help="chunk-replay backends for the scan engine (pallas is "
        "interpret-mode off-TPU: correctness row, not a perf row)",
    )
    ap.add_argument(
        "--engines", nargs="+", default=["scan", "legacy"],
        choices=["scan", "legacy"],
    )
    ap.add_argument(
        "--telemetry", choices=["on", "off", "both"], default="both"
    )
    ap.add_argument(
        "--trace-modes", nargs="+", default=["materialized", "streamed"],
        choices=["materialized", "streamed"],
        help="trace generation modes for the scan engine (legacy is "
        "always materialized)",
    )
    ap.add_argument("--acceptance", action="store_true",
                    help="run the 1M-request ISSUE-5 acceptance comparison")
    ap.add_argument(
        "--trendline", action="store_true",
        help="measure the multi-device scaling trendline (streamed sharded "
        "engine over the first S devices, for each S of --trendline-devices)",
    )
    ap.add_argument(
        "--trendline-devices", nargs="+", type=int,
        default=list(TRENDLINE_DEVICE_COUNTS),
    )
    ap.add_argument("--trendline-requests", type=int, default=2_000_000)
    ap.add_argument("--trendline-keys", type=int, default=200_000)
    ap.add_argument("--trendline-policy", default="redynis")
    ap.add_argument(
        "--scale-acceptance", action="store_true",
        help="time one >=10M-request streamed run on a single device "
        "(the ISSUE-7 memory-model criterion)",
    )
    ap.add_argument("--scale-requests", type=int, default=10_000_000)
    ap.add_argument("--scale-keys", type=int, default=1_000_000)
    ap.add_argument("--scale-policy", default="replicated")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="checked-in BENCH json to warn against "
                    "('' disables)")
    ap.add_argument(
        "--fail-on-regression", action="store_true",
        help="exit nonzero when a fused-vs-legacy speedup ratio regresses "
        ">20% vs the baseline (absolute req/s stays warn-only: it is "
        "machine-dependent)",
    )
    args = ap.parse_args()
    main(
        num_requests=args.num_requests,
        repeats=args.repeats,
        daemon_intervals=tuple(args.daemon_intervals),
        num_keys_grid=tuple(args.num_keys),
        policy_specs=tuple(args.policies),
        backends=tuple(args.backends),
        engines=tuple(args.engines),
        telemetry_modes={
            "on": (True,), "off": (False,), "both": (True, False)
        }[args.telemetry],
        trace_modes=tuple(args.trace_modes),
        acceptance=args.acceptance,
        baseline=args.baseline or None,
        fail_on_regression=args.fail_on_regression,
        trendline=args.trendline,
        trendline_devices=tuple(args.trendline_devices),
        trendline_requests=args.trendline_requests,
        trendline_keys=args.trendline_keys,
        trendline_policy=args.trendline_policy,
        scale_acceptance=args.scale_acceptance,
        scale_requests=args.scale_requests,
        scale_keys=args.scale_keys,
        scale_policy=args.scale_policy,
    )
