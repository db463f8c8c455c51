"""Directory-staleness frontier: redynis P99 vs ``publish_lag_chunks``.

The experiment the routing tier (ISSUE 8) exists for: a real deployment
never reads the daemon's ownership map synchronously — router sites hold a
cached view that lags the placement decisions by a publish interval, and
every chunk of lag converts some fraction of directory consults into
mis-routed detours. This sweep prices that staleness axis end to end on
the diurnal wan5 scenario (a rotating hot region, so placement genuinely
moves and a lagged directory genuinely mis-routes — a *static* hotset
yields zero staleness because the daemon only moves keys whose readers
already left):

  * **lag ladder** — redynis under ``RoutingConfig(publish_lag_chunks=L)``
    for each L in the sweep: mean/P50/P99/P99.9 latency off the in-scan
    telemetry histograms (overall AND read-split), plus the routing
    counters (consults, directory fetches, stale consults, mis-routes,
    peak per-chunk mis-route rate).
  * **static frontier** — the realizable static placements (``remote``,
    ``replicated``) on the same trace with the routing tier off. A static
    map never changes, so no lag can stale it; these are the lag-free
    alternatives a deployment would fall back to. The *best* static is
    chosen by mean latency — the metric a deployment would pick its
    placement policy on. (``static:local`` is the idealised
    everything-local bound — unbeatable by construction, reported in the
    JSON for scale but excluded from the "best static" frontier.)
  * **acceptance checks** — the ISSUE-8 criteria, recorded in the JSON and
    promoted to a hard exit by ``--fail-on-regression``:
      1. routing-off bit-exactness: ``routing=None`` and
         ``RoutingConfig(enabled=False)`` produce identical ``SimResult``s
         and telemetry leaves (the off-path is structurally the PR-7
         program);
      2. monotone degradation: redynis P99 never improves as
         ``publish_lag_chunks`` grows — overall and read-split, plus the
         mean and the mis-route count. At the default 30%-write mix the
         *overall* P99 is capped by the replication-write broadcast tail
         (every lag lands in the same histogram bin), so the strict
         staleness signal is the **read** P99: directory consults happen
         only on the read path, and every added chunk of lag detours more
         reads through a stale owner;
      3. finite crossover: some measured lag exists at which redynis still
         beats the best realizable static on BOTH mean latency and
         overall P99 — the staleness budget the routing tier buys before
         a lag-free static placement would serve the same traffic better.

Persists ``BENCH_directory_staleness.json`` (rows + quantiles blocks +
check verdicts). The checked-in baseline records the full ladder
(0..128); CI smoke runs a 3-point subset via ``--lags 0 8 64`` with a
smaller trace.
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
    RoutingConfig,
    StaticPolicy,
    RedynisPolicy,
    TelemetryConfig,
    diurnal_workload,
    run_scenario,
    wan5_cluster,
)

DEFAULT_LAGS = (0, 1, 2, 4, 8, 16, 32, 64, 128)
STATIC_MODES = ("remote", "replicated", "local")
REALIZABLE_STATICS = ("remote", "replicated")


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


def _row(result, trace) -> dict:
    q = trace.tail_summary()
    return {
        "mean_latency_ms": float(result.mean_latency_ms),
        "p50_ms": q["p50"],
        "p99_ms": q["p99"],
        "p999_ms": q["p999"],
        "p99_read_ms": trace.quantile(0.99, split="read"),
        "hit_rate": float(result.hit_rate),
        "throughput_ops_s": float(result.throughput_ops_s),
        "router_consults": float(result.router_consults),
        "directory_fetches": float(result.directory_fetches),
        "stale_consults": float(result.stale_consults),
        "mis_routes": float(result.mis_routes),
        "peak_mis_route_rate": float(trace.mis_route_rate.max()),
    }


def _check_routing_off_bitexact(wl, cluster, *, daemon_interval, seed,
                                replay_backend, num_bins) -> bool:
    """``RoutingConfig(enabled=False)`` must be *the same program* as
    ``routing=None`` — bit-exact SimResult fields and telemetry arrays."""
    r_none, t_none = _run(
        wl, cluster, RedynisPolicy(), daemon_interval=daemon_interval,
        seed=seed, replay_backend=replay_backend, num_bins=num_bins,
    )
    r_off, t_off = _run(
        wl, cluster._replace(routing=RoutingConfig(enabled=False)),
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
                 "occupancy_bytes", "load_factor"):
        if not np.array_equal(getattr(t_none, name), getattr(t_off, name)):
            print(f"BITEXACT_MISMATCH,SimTrace.{name}")
            ok = False
    return ok


def _monotone(values, rel_tol: float = 1e-6) -> bool:
    """Non-decreasing up to a relative tolerance (histogram quantiles can
    tie bit-for-bit across adjacent lags)."""
    v = np.asarray(values, dtype=np.float64)
    return bool(np.all(np.diff(v) >= -rel_tol * np.maximum(v[:-1], 1.0)))


def main(
    num_requests: int = 100_000,
    num_keys: int = 1_000,
    lags=DEFAULT_LAGS,
    daemon_interval: int = 200,
    affinity: float = 0.8,
    read_fraction: float = 0.7,
    cache_entries: int = 0,
    seed: int = 0,
    num_bins: int = 128,
    replay_backend: str = "jax",
    fail_on_regression: bool = False,
) -> dict:
    banner(
        "directory_staleness: redynis P99 vs publish lag, diurnal wan5 "
        f"({num_requests:,} requests / {num_keys:,} keys, "
        f"daemon_interval={daemon_interval})"
    )
    wl = diurnal_workload(
        num_requests=num_requests,
        num_keys=num_keys,
        affinity=affinity,
        read_fraction=read_fraction,
    )
    cluster = wan5_cluster()
    t_start = time.perf_counter()

    checks = {}
    checks["routing_off_bitexact"] = _check_routing_off_bitexact(
        wl, cluster, daemon_interval=daemon_interval, seed=seed,
        replay_backend=replay_backend, num_bins=num_bins,
    )

    static_rows, quantiles = {}, {}
    for mode in STATIC_MODES:
        res, trace = _run(
            wl, cluster, StaticPolicy(mode=mode),
            daemon_interval=daemon_interval, seed=seed,
            replay_backend=replay_backend, num_bins=num_bins,
        )
        static_rows[mode] = _row(res, trace)
        quantiles[f"static:{mode}"] = trace.tail_summary()
        emit(
            "directory_staleness_static",
            round(static_rows[mode]["p99_ms"], 2),
            "p99_ms",
            policy=f"static:{mode}",
            mean=round(static_rows[mode]["mean_latency_ms"], 4),
            realizable=int(mode in REALIZABLE_STATICS),
        )
    best_static = min(REALIZABLE_STATICS,
                      key=lambda m: static_rows[m]["mean_latency_ms"])
    best_static_mean = static_rows[best_static]["mean_latency_ms"]
    best_static_p99 = static_rows[best_static]["p99_ms"]

    lag_rows = []
    for lag in lags:
        routing = RoutingConfig(
            publish_lag_chunks=lag, cache_entries=cache_entries,
        )
        res, trace = _run(
            wl, cluster._replace(routing=routing), RedynisPolicy(),
            daemon_interval=daemon_interval, seed=seed,
            replay_backend=replay_backend, num_bins=num_bins,
        )
        row = {"publish_lag_chunks": lag, **_row(res, trace)}
        row["beats_best_static"] = bool(
            row["mean_latency_ms"] < best_static_mean
            and row["p99_ms"] < best_static_p99
        )
        lag_rows.append(row)
        quantiles[f"redynis/lag{lag}"] = trace.tail_summary()
        emit(
            "directory_staleness",
            round(row["p99_ms"], 2),
            "p99_ms",
            publish_lag_chunks=lag,
            p99_read=round(row["p99_read_ms"], 2),
            mean=round(row["mean_latency_ms"], 4),
            mis_routes=int(row["mis_routes"]),
            stale_consults=int(row["stale_consults"]),
            directory_fetches=int(row["directory_fetches"]),
            beats_best_static=int(row["beats_best_static"]),
        )

    checks["p99_monotone_in_lag"] = _monotone(
        [r["p99_ms"] for r in lag_rows]
    )
    checks["p99_read_monotone_in_lag"] = _monotone(
        [r["p99_read_ms"] for r in lag_rows]
    )
    checks["mean_monotone_in_lag"] = _monotone(
        [r["mean_latency_ms"] for r in lag_rows]
    )
    checks["mis_routes_monotone_in_lag"] = _monotone(
        [r["mis_routes"] for r in lag_rows]
    )
    winning = [r["publish_lag_chunks"] for r in lag_rows
               if r["beats_best_static"]]
    checks["finite_crossover_lag_exists"] = bool(winning)
    emit(
        "directory_staleness_checks",
        int(all(checks.values())),
        "all_ok",
        best_static=best_static,
        best_static_mean=round(best_static_mean, 4),
        best_static_p99=round(best_static_p99, 2),
        max_winning_lag=max(winning) if winning else -1,
        **{k: int(v) for k, v in checks.items()},
    )

    write_bench_json(
        "directory_staleness",
        {
            "lag_rows": lag_rows,
            "static_rows": static_rows,
            "best_realizable_static": best_static,
            "best_static_mean_ms": best_static_mean,
            "best_static_p99_ms": best_static_p99,
            "max_winning_lag": max(winning) if winning else None,
            "checks": checks,
            "wall_time_s": time.perf_counter() - t_start,
        },
        quantiles=quantiles,
        num_requests=num_requests,
        num_keys=num_keys,
        daemon_interval=daemon_interval,
        affinity=affinity,
        read_fraction=read_fraction,
        cache_entries=cache_entries,
        seed=seed,
        num_bins=num_bins,
        lags=list(lags),
        replay_backend=replay_backend,
    )
    if fail_on_regression and not all(checks.values()):
        failed = [k for k, v in checks.items() if not v]
        print(f"FAIL,directory_staleness,checks_failed={';'.join(failed)}")
        sys.exit(1)
    return {"lag_rows": lag_rows, "static_rows": static_rows,
            "checks": checks}


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-requests", type=int, default=100_000)
    ap.add_argument("--num-keys", type=int, default=1_000)
    ap.add_argument(
        "--lags", nargs="+", type=int, default=list(DEFAULT_LAGS),
        help="publish_lag_chunks ladder (ascending)",
    )
    ap.add_argument("--daemon-interval", type=int, default=200)
    ap.add_argument("--affinity", type=float, default=0.8)
    ap.add_argument("--read-fraction", type=float, default=0.7)
    ap.add_argument(
        "--cache-entries", type=int, default=0,
        help="per-router cache capacity (0 = unbounded)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--num-bins", type=int, default=128)
    ap.add_argument(
        "--replay-backend", choices=["jax", "pallas"], default="jax",
    )
    ap.add_argument(
        "--fail-on-regression", action="store_true",
        help="exit nonzero when any acceptance check fails (routing-off "
        "bit-exactness, P99/mean/mis-route monotonicity, finite crossover)",
    )
    args = ap.parse_args()
    main(
        num_requests=args.num_requests,
        num_keys=args.num_keys,
        lags=tuple(sorted(args.lags)),
        daemon_interval=args.daemon_interval,
        affinity=args.affinity,
        read_fraction=args.read_fraction,
        cache_entries=args.cache_entries,
        seed=args.seed,
        num_bins=args.num_bins,
        replay_backend=args.replay_backend,
        fail_on_regression=args.fail_on_regression,
    )
