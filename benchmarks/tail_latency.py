"""Tail-latency matrix: P50/P99/P99.9 per policy × topology.

The experiment the telemetry subsystem exists for: means hide exactly the
tail behaviour geo-distributed round-trips inflate, so this sweep races the
registered policies across the flat 3-node testbed, the 5-region WAN, and
the heterogeneous WAN-with-edge-node topology, reading interpolated
quantiles off the in-scan latency histograms (one fused program per policy
family — the trace is never re-walked). Emits per-(topology, policy) rows
and persists ``BENCH_tail_latency.json`` with the schema's top-level
``quantiles`` block.

The contention-on grid (``ServiceConfig``) re-races the size/cost policies
on wan5 with the M/M/1 queueing model enabled: lognormal object sizes load
the size-proportional service demand, and capacity_factor sets the load
level. Region weights are balanced there so the tail isolates size-driven
queueing (cost-per-KiB admission strands hot large objects on one owner
node) rather than regional traffic imbalance.
"""

from __future__ import annotations

import argparse
import time

from benchmarks.common import (
    WAN5_WORKLOAD_KWARGS,
    banner,
    dedupe_policies,
    emit,
    enable_compile_cache,
    write_bench_json,
)
from repro.kvsim import (
    ClusterConfig,
    ServiceConfig,
    TelemetryConfig,
    parse_policy,
    run_experiment,
    wan5_cluster,
    wan5_edge_cluster,
)

DEFAULT_POLICIES = (
    "remote",
    "replicated",
    "redynis",
    "redynis:h=0.05,decay=0.9",
    "topk:k=100",
    "costgreedy",
    "decaylfu:alpha=0.5",
)

# Contention-on grid: the size-aware sharding head-to-head. Light and
# moderate load (capacity_factor 2.0 / 1.0) keep the load factors below the
# stability clamp so the queueing mechanism — not the rho_max ceiling —
# separates the policies.
CONTENTION_POLICIES = (
    "sizeaware",
    "sizeaware:large_fanout=3",
    "costgreedy",
    "redynis",
)
CONTENTION_CAPACITY_FACTORS = (2.0, 1.0)
CONTENTION_SERVE_BYTES_PER_MS = 128.0
CONTENTION_SIGMA = 1.0
CONTENTION_WORKLOAD_KWARGS = dict(
    num_nodes=5,
    region_weights=(0.2, 0.2, 0.2, 0.2, 0.2),
    affinity=0.8,
)

# topology name -> (cluster, per-topology workload kwargs)
TOPOLOGIES = {
    "flat": (ClusterConfig(), dict(num_nodes=3, affinity=0.8)),
    "wan5": (wan5_cluster(), dict(WAN5_WORKLOAD_KWARGS)),
    "wan5_edge": (
        wan5_edge_cluster(edge_capacity_bytes=64 * 1024.0),
        dict(WAN5_WORKLOAD_KWARGS),
    ),
}


def main(
    num_requests: int = 30_000,
    iterations: int = 3,
    read_fraction: float = 0.9,
    policy_specs=DEFAULT_POLICIES,
    topologies=tuple(TOPOLOGIES),
    num_bins: int = 128,
    policy=None,
    replay_backend: str = "jax",
    contention: bool = True,
    contention_capacity_factors=CONTENTION_CAPACITY_FACTORS,
) -> dict:
    banner("tail_latency: P50/P99/P99.9 per policy x topology")
    telemetry = TelemetryConfig(num_bins=num_bins)
    rows, quantiles, out = [], {}, {}
    t_start = time.perf_counter()
    for topo in topologies:
        cluster, wl_kwargs = TOPOLOGIES[topo]
        candidates = [parse_policy(s) for s in policy_specs]
        if policy is not None:
            candidates.append(policy)
        policies = dedupe_policies(candidates, cluster.num_nodes)
        res = run_experiment(
            read_fractions=(read_fraction,),
            skewed=True,
            iterations=iterations,
            num_requests=num_requests,
            cluster=cluster,
            policies=policies,
            telemetry=telemetry,
            replay_backend=replay_backend,
            **wl_kwargs,
        )
        out[topo] = res
        for label, policy_rows in res["policies"].items():
            row = policy_rows[0]
            q = row["quantiles"]
            # The reported P99 is the mean of per-seed interpolated P99s —
            # the estimator row["p99_ci99"] is the CI band *of* — not the
            # pooled-histogram quantile (which lives in the quantiles
            # block); pairing the band with a different estimator could
            # print a point outside its own interval.
            p99 = row["p99_latency_ms"]
            emit(
                "tail_latency",
                round(p99, 2),
                "p99_ms",
                topology=topo,
                policy=label,
                p50=round(q["p50"], 2),
                p999=round(q["p999"], 2),
                p99_ci99=round(row["p99_ci99"], 2),
                hit_rate=round(row["hit_rate"], 4),
            )
            quantiles[f"{topo}/{label}"] = q
            rows.append(
                {
                    "topology": topo,
                    "policy": label,
                    "read_fraction": row["read_fraction"],
                    "hit_rate": row["hit_rate"],
                    "mean_latency_ms": row["mean_latency_ms"],
                    "throughput_ops_s": row["throughput"],
                    "p50_ms": q["p50"],
                    "p99_ms": p99,
                    "p999_ms": q["p999"],
                    "p99_ci99": row["p99_ci99"],
                    "convergence_chunk": row["trace"].convergence_chunk(),
                    # Per-seed average so the oscillation column is
                    # comparable across runs with different --iterations.
                    "post_convergence_moves_per_seed": row[
                        "trace"
                    ].post_convergence_moves() / iterations,
                }
            )
    contention_rows = []
    if contention:
        banner("tail_latency: contention-on grid (ServiceConfig, wan5)")
        for cf in contention_capacity_factors:
            svc = ServiceConfig(
                serve_bytes_per_ms=CONTENTION_SERVE_BYTES_PER_MS,
                capacity_factor=cf,
            )
            cluster = wan5_cluster()._replace(service=svc)
            policies = dedupe_policies(
                [parse_policy(s) for s in CONTENTION_POLICIES],
                cluster.num_nodes,
            )
            res = run_experiment(
                read_fractions=(1.0,),  # read-path contention, no broadcasts
                skewed=True,
                iterations=iterations,
                num_requests=num_requests,
                cluster=cluster,
                policies=policies,
                telemetry=telemetry,
                replay_backend=replay_backend,
                object_bytes_sigma=CONTENTION_SIGMA,
                **CONTENTION_WORKLOAD_KWARGS,
            )
            out[f"contention/cf{cf}"] = res
            for label, policy_rows in res["policies"].items():
                row = policy_rows[0]
                q = row["quantiles"]
                p99 = row["p99_latency_ms"]
                peak_rho = float(row["trace"].load_factor.max())
                emit(
                    "tail_latency_contention",
                    round(p99, 2),
                    "p99_ms",
                    capacity_factor=cf,
                    policy=label,
                    p50=round(q["p50"], 2),
                    p999=round(q["p999"], 2),
                    p99_ci99=round(row["p99_ci99"], 2),
                    hit_rate=round(row["hit_rate"], 4),
                    peak_load_factor=round(peak_rho, 4),
                )
                quantiles[f"contention/cf{cf}/{label}"] = q
                contention_rows.append(
                    {
                        "capacity_factor": cf,
                        "policy": label,
                        "hit_rate": row["hit_rate"],
                        "mean_latency_ms": row["mean_latency_ms"],
                        "p50_ms": q["p50"],
                        "p99_ms": p99,
                        "p999_ms": q["p999"],
                        "p99_ci99": row["p99_ci99"],
                        "peak_load_factor": peak_rho,
                    }
                )

    write_bench_json(
        "tail_latency",
        {
            "rows": rows,
            "contention": {
                "rows": contention_rows,
                "capacity_factors": list(contention_capacity_factors),
                "serve_bytes_per_ms": CONTENTION_SERVE_BYTES_PER_MS,
                "object_bytes_sigma": CONTENTION_SIGMA,
                "policies": list(CONTENTION_POLICIES),
            },
            "wall_time_s": time.perf_counter() - t_start,
        },
        quantiles=quantiles,
        num_requests=num_requests,
        iterations=iterations,
        read_fraction=read_fraction,
        num_bins=num_bins,
        topologies=list(topologies),
        replay_backend=replay_backend,
    )
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-requests", type=int, default=30_000)
    ap.add_argument("--iterations", type=int, default=3)
    ap.add_argument("--read-fraction", type=float, default=0.9)
    ap.add_argument("--num-bins", type=int, default=128)
    ap.add_argument(
        "--topologies", nargs="+", default=list(TOPOLOGIES),
        choices=list(TOPOLOGIES),
    )
    ap.add_argument(
        "--policies", nargs="+", default=list(DEFAULT_POLICIES),
        metavar="NAME[:k=v,...]",
        help="registry policy specs to race (default: the matrix built-ins)",
    )
    ap.add_argument(
        "--replay-backend", choices=["jax", "pallas"], default="jax",
        help="chunk-replay backend for the fused engine",
    )
    ap.add_argument(
        "--no-contention", action="store_true",
        help="skip the ServiceConfig contention-on grid",
    )
    ap.add_argument(
        "--contention-capacity-factors", nargs="+", type=float,
        default=list(CONTENTION_CAPACITY_FACTORS), metavar="CF",
        help="load levels for the contention grid (capacity_factor values)",
    )
    args = ap.parse_args()
    main(
        num_requests=args.num_requests,
        iterations=args.iterations,
        read_fraction=args.read_fraction,
        policy_specs=tuple(args.policies),
        topologies=tuple(args.topologies),
        num_bins=args.num_bins,
        replay_backend=args.replay_backend,
        contention=not args.no_contention,
        contention_capacity_factors=tuple(args.contention_capacity_factors),
    )
