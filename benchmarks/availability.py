"""Region-outage drill: availability, degraded-window P99, re-convergence.

The experiment the failure-injection subsystem (ISSUE 10) exists for: crash
the hottest wan5 region mid-trace, recover it later, and price what each
placement policy actually delivers while the cluster is degraded:

  * **policy drill** — redynis vs the realizable statics (``replicated``,
    ``remote``) under the same ``region_outage`` schedule: per-chunk
    availability (served / attempted) min + outage-window mean, the P99 over
    the outage window only (summed ``chunk_hist`` rows → interpolated
    quantile), unavailable read/write counts, write failovers, daemon
    repair moves, and ``recovery_chunks`` — chunks from outage start until
    the effective hit rate (unavailable reads count as misses) first
    returns to 95% of its pre-outage steady state. Redynis re-replicates
    crash-wiped keys on its next due sweep; a static policy never sweeps,
    so its crashed copies stay lost (``repair_moves == 0`` by
    construction) — the contrast the drill exists to measure.
  * **blast radius** — per scheduled failure, the peak fraction of the
    keyspace left with no live replica (``blast_radius_unreachable``) and
    with no surviving replica at all (``blast_radius_wiped``), read off the
    engine's per-chunk fault telemetry.
  * **duration ladder** — the same outage at growing durations; total
    unavailability must grow monotonically with the outage length (a
    machine-independent invariant ``--fail-on-regression`` hard-gates).
  * **acceptance checks** — the ISSUE-10 criteria, recorded in the JSON and
    promoted to a hard exit by ``--fail-on-regression``:
      1. fault-off bit-exactness: ``faults=None`` and
         ``FaultConfig(enabled=False)`` produce identical ``SimResult``s
         and telemetry arrays (the off-path is structurally the PR-9
         program);
      2. redynis recovers: ``recovery_chunks`` is finite (>= 0) — the
         post-outage effective hit rate reaches 95% of its pre-outage
         steady state before the trace ends;
      3. blast radius reported: one row per scheduled failure, all finite;
      4. unavailability monotone in outage duration (the ladder);
      5. repair asymmetry: redynis repairs (``repair_moves > 0``), the
         static policies cannot (``repair_moves == 0``).

Persists ``BENCH_availability.json`` (rows + blast radius + ladder + check
verdicts). CI smoke runs a smaller trace via ``--num-requests``.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks.common import (
    banner,
    emit,
    enable_compile_cache,
    write_bench_json,
)
from repro.kvsim import (
    FaultConfig,
    RedynisPolicy,
    StaticPolicy,
    TelemetryConfig,
    blast_radius_rows,
    histogram_quantile,
    region_outage,
    run_scenario,
    wan5_cluster,
    wan5_workload,
)

POLICY_ROWS = (
    ("redynis", lambda: RedynisPolicy()),
    ("static:replicated", lambda: StaticPolicy(mode="replicated")),
    ("static:remote", lambda: StaticPolicy(mode="remote")),
)
HOT_REGION = 0  # wan5_workload's heaviest region weight (0.35)


def _run(wl, cluster, policy, *, daemon_interval, seed, replay_backend,
         num_bins):
    return run_scenario(
        wl,
        cluster,
        policy,
        seed=seed,
        daemon_interval=daemon_interval,
        telemetry=TelemetryConfig(num_bins=num_bins),
        replay_backend=replay_backend,
    )


def _window_p99(trace, start: int, end: int) -> float:
    """Interpolated P99 over the outage window's summed chunk histograms."""
    return histogram_quantile(
        trace.chunk_hist[start:end].sum(axis=0), trace.edges, 0.99
    )


def _row(result, trace, *, outage_start: int, outage_end: int) -> dict:
    avail = trace.availability
    window = avail[outage_start:outage_end]
    return {
        "availability_min": float(avail.min()),
        "availability_outage_mean": float(window.mean()),
        "p99_outage_ms": _window_p99(trace, outage_start, outage_end),
        "p99_overall_ms": trace.quantile(0.99),
        "mean_latency_ms": float(result.mean_latency_ms),
        "hit_rate": float(result.hit_rate),
        "unavailable_reads": float(result.unavailable_reads),
        "unavailable_writes": float(result.unavailable_writes),
        "failovers": float(result.failovers),
        "repair_moves": float(result.repair_moves),
        "recovery_chunks": int(trace.recovery_chunks(outage_start)),
        "peak_unreachable_frac": float(trace.unreachable_frac.max()),
        "peak_wiped_frac": float(trace.wiped_frac.max()),
    }


def _check_fault_off_bitexact(wl, cluster, *, daemon_interval, seed,
                              replay_backend, num_bins) -> bool:
    """``FaultConfig(enabled=False)`` must be *the same program* as
    ``faults=None`` — bit-exact SimResult fields and telemetry arrays."""
    r_none, t_none = _run(
        wl, cluster, RedynisPolicy(), daemon_interval=daemon_interval,
        seed=seed, replay_backend=replay_backend, num_bins=num_bins,
    )
    r_off, t_off = _run(
        wl, cluster._replace(faults=FaultConfig(enabled=False)),
        RedynisPolicy(), daemon_interval=daemon_interval, seed=seed,
        replay_backend=replay_backend, num_bins=num_bins,
    )
    ok = True
    for name in r_none._fields:
        a, b = getattr(r_none, name), getattr(r_off, name)
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            print(f"BITEXACT_MISMATCH,SimResult.{name},{a!r},{b!r}")
            ok = False
    for name in ("hist_group", "hit_rate", "mean_latency_ms", "moves",
                 "occupancy_bytes", "availability", "effective_hit_rate"):
        if not np.array_equal(getattr(t_none, name), getattr(t_off, name)):
            print(f"BITEXACT_MISMATCH,SimTrace.{name}")
            ok = False
    return ok


def main(
    num_requests: int = 100_000,
    num_keys: int = 1_000,
    daemon_interval: int = 200,
    read_fraction: float = 0.7,
    affinity: float = 0.8,
    seed: int = 0,
    num_bins: int = 128,
    replay_backend: str = "jax",
    fail_on_regression: bool = False,
) -> dict:
    num_chunks = (num_requests + daemon_interval - 1) // daemon_interval
    outage_start = num_chunks // 3
    outage_len = max(num_chunks // 5, 2)
    outage_end = outage_start + outage_len
    banner(
        "availability: wan5 region-outage drill "
        f"({num_requests:,} requests / {num_keys:,} keys, crash region "
        f"{HOT_REGION} chunks [{outage_start}, {outage_end}))"
    )
    wl = wan5_workload(
        num_requests=num_requests,
        num_keys=num_keys,
        read_fraction=read_fraction,
        affinity=affinity,
    )
    cluster = wan5_cluster()
    faults = region_outage(HOT_REGION, outage_start, outage_len, mode="crash")
    t_start = time.perf_counter()

    checks = {}
    checks["fault_off_bitexact"] = _check_fault_off_bitexact(
        wl, cluster, daemon_interval=daemon_interval, seed=seed,
        replay_backend=replay_backend, num_bins=num_bins,
    )

    rows, blast = {}, []
    for label, make in POLICY_ROWS:
        res, trace = _run(
            wl, cluster._replace(faults=faults), make(),
            daemon_interval=daemon_interval, seed=seed,
            replay_backend=replay_backend, num_bins=num_bins,
        )
        rows[label] = _row(
            res, trace, outage_start=outage_start, outage_end=outage_end
        )
        if label == "redynis":
            blast = blast_radius_rows(
                faults,
                num_chunks=num_chunks,
                unreachable_frac=trace.unreachable_frac,
                wiped_frac=trace.wiped_frac,
            )
        emit(
            "availability",
            round(rows[label]["availability_min"], 4),
            "availability_min",
            policy=label,
            p99_outage=round(rows[label]["p99_outage_ms"], 2),
            unavailable_reads=int(rows[label]["unavailable_reads"]),
            failovers=int(rows[label]["failovers"]),
            repair_moves=int(rows[label]["repair_moves"]),
            recovery_chunks=rows[label]["recovery_chunks"],
        )

    # Duration ladder: same outage start, growing length — total
    # unavailability is monotone in the outage duration by construction,
    # and the check is machine-independent (pure counters).
    durations = sorted({
        max(outage_len // 4, 1), max(outage_len // 2, 1), outage_len,
    })
    ladder = []
    for d in durations:
        res = run_scenario(
            wl,
            cluster._replace(
                faults=region_outage(HOT_REGION, outage_start, d)
            ),
            RedynisPolicy(), seed=seed, daemon_interval=daemon_interval,
            replay_backend=replay_backend,
        )
        ladder.append({
            "duration_chunks": int(d),
            "unavailable_total": float(
                res.unavailable_reads + res.unavailable_writes
            ),
        })
    unav = [r["unavailable_total"] for r in ladder]
    checks["unavailability_monotone_in_duration"] = bool(
        np.all(np.diff(unav) >= 0)
    )
    checks["redynis_recovers"] = rows["redynis"]["recovery_chunks"] >= 0
    checks["blast_radius_reported"] = bool(blast) and all(
        np.isfinite(r["blast_radius_unreachable"])
        and np.isfinite(r["blast_radius_wiped"])
        for r in blast
    )
    checks["repair_asymmetry"] = (
        rows["redynis"]["repair_moves"] > 0
        and rows["static:replicated"]["repair_moves"] == 0
        and rows["static:remote"]["repair_moves"] == 0
    )
    emit(
        "availability_checks",
        int(all(checks.values())),
        "all_ok",
        recovery_chunks=rows["redynis"]["recovery_chunks"],
        **{k: int(v) for k, v in checks.items()},
    )

    write_bench_json(
        "availability",
        {
            "rows": rows,
            "blast_radius": blast,
            "duration_ladder": ladder,
            "outage": {
                "kind": "region",
                "target": HOT_REGION,
                "mode": "crash",
                "start_chunk": outage_start,
                "end_chunk": outage_end,
            },
            "checks": checks,
            "wall_time_s": time.perf_counter() - t_start,
        },
        num_requests=num_requests,
        num_keys=num_keys,
        daemon_interval=daemon_interval,
        read_fraction=read_fraction,
        affinity=affinity,
        seed=seed,
        num_bins=num_bins,
        replay_backend=replay_backend,
    )
    if fail_on_regression and not all(checks.values()):
        failed = [k for k, v in checks.items() if not v]
        print(f"FAIL,availability,checks_failed={';'.join(failed)}")
        sys.exit(1)
    return {"rows": rows, "blast_radius": blast, "ladder": ladder,
            "checks": checks}


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-requests", type=int, default=100_000)
    ap.add_argument("--num-keys", type=int, default=1_000)
    ap.add_argument("--daemon-interval", type=int, default=200)
    ap.add_argument("--read-fraction", type=float, default=0.7)
    ap.add_argument("--affinity", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--num-bins", type=int, default=128)
    ap.add_argument(
        "--replay-backend", choices=["jax", "pallas"], default="jax",
    )
    ap.add_argument(
        "--fail-on-regression", action="store_true",
        help="exit nonzero when any acceptance check fails (fault-off "
        "bit-exactness, finite recovery, blast-radius rows, availability "
        "monotonicity, repair asymmetry)",
    )
    args = ap.parse_args()
    main(
        num_requests=args.num_requests,
        num_keys=args.num_keys,
        daemon_interval=args.daemon_interval,
        read_fraction=args.read_fraction,
        affinity=args.affinity,
        seed=args.seed,
        num_bins=args.num_bins,
        replay_backend=args.replay_backend,
        fail_on_regression=args.fail_on_regression,
    )
