"""Latency attribution: per-policy component breakdown on the wan5 WAN.

The provenance experiment the attribution layer exists for: with the
queueing model AND the routing/directory tier enabled, every request's
latency decomposes into the 8-way component taxonomy
(``repro.kernels.chunk_replay.ref.COMPONENTS``), and the per-policy story
becomes visible — replicated pays its write-broadcast legs, remote pays
read RTT, redynis trades a transient routing-detour/directory-fetch cost
for a collapsing read_rtt share. Emits one row per (policy, component),
persists ``BENCH_attribution.json`` (rows + the component-sum-reconstructs
-total checks the trend dashboard gates on), and — with ``--trace-out`` —
exports a sampled-request Chrome trace (Perfetto-loadable) from the flight
recorder.
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
    COMPONENTS,
    AttributionConfig,
    FlightRecorderConfig,
    RoutingConfig,
    ServiceConfig,
    TelemetryConfig,
    describe_policy,
    parse_policy,
    run_scenario,
    wan5_cluster,
    wan5_workload,
    write_chrome_trace,
)

DEFAULT_POLICIES = (
    "remote",
    "replicated",
    "redynis",
    "costgreedy",
)

# Both surcharge models on, so every component can be exercised: moderate
# queueing load, a 2-chunk-stale directory (active policies move keys, so
# their routers pay detours until the publish catches up), and a bounded
# router cache (cold keys miss and pay the home-node directory fetch).
SERVICE = ServiceConfig(serve_bytes_per_ms=128.0, capacity_factor=2.0)
ROUTING = RoutingConfig(publish_lag_chunks=2, cache_entries=256)


def main(
    num_requests: int = 30_000,
    read_fraction: float = 0.9,
    seed: int = 0,
    daemon_interval: int = 1000,
    policy_specs=DEFAULT_POLICIES,
    num_bins: int = 96,
    trace_out: str | None = None,
    trace_policy: str = "redynis",
    samples_per_chunk: int = 8,
) -> dict:
    banner("latency_attribution: component breakdown per policy (wan5)")
    cluster = wan5_cluster()._replace(service=SERVICE, routing=ROUTING)
    workload = wan5_workload(
        num_requests=num_requests,
        read_fraction=read_fraction,
        skewed=True,
        **{
            k: v
            for k, v in WAN5_WORKLOAD_KWARGS.items()
            if k != "num_nodes"
        },
    )
    telemetry = TelemetryConfig(
        num_bins=num_bins,
        attribution=AttributionConfig(num_bins=num_bins),
        flight=FlightRecorderConfig(samples_per_chunk=samples_per_chunk),
    )
    policies = dedupe_policies(
        [parse_policy(s) for s in policy_specs], cluster.num_nodes
    )
    trace_label = describe_policy(
        parse_policy(trace_policy).resolve(cluster.num_nodes)
    )
    t_start = time.perf_counter()
    rows, components, checks = [], {}, {}
    for policy in policies:
        label = describe_policy(policy.resolve(cluster.num_nodes))
        result, trace = run_scenario(
            workload,
            cluster,
            policy,
            seed=seed,
            daemon_interval=daemon_interval,
            telemetry=telemetry,
        )
        attr = trace.attribution
        comp_sum = sum(stats["mean_ms"] for stats in attr.values())
        # The headline invariant, gated by bench_trend: the per-request
        # component means must reconstruct the engine's mean latency.
        ok = abs(comp_sum - result.mean_latency_ms) <= 1e-3 * max(
            result.mean_latency_ms, 1.0
        )
        checks[f"component_sum_reconstructs_total/{label}"] = bool(ok)
        components[label] = {
            name: {
                "mean_ms": stats["mean_ms"],
                "share": stats["share"],
                "p50_ms": stats["p50"],
                "p99_ms": stats["p99"],
            }
            for name, stats in attr.items()
        }
        row = {
            "policy": label,
            "mean_latency_ms": result.mean_latency_ms,
            "hit_rate": result.hit_rate,
            "component_sum_ms": comp_sum,
        }
        for name in COMPONENTS:
            row[f"{name}_ms"] = attr[name]["mean_ms"]
        rows.append(row)
        top = max(
            (n for n in COMPONENTS if n != "service"),
            key=lambda n: attr[n]["mean_ms"],
        )
        emit(
            "latency_attribution",
            round(result.mean_latency_ms, 3),
            "mean_ms",
            policy=label,
            component_sum=round(comp_sum, 3),
            top_component=top,
            top_ms=round(attr[top]["mean_ms"], 3),
            detour_ms=round(attr["routing_detour"]["mean_ms"], 3),
            broadcast_ms=round(attr["write_broadcast"]["mean_ms"], 3),
        )
        if trace_out and label == trace_label:
            n_events = write_chrome_trace(trace.flight_records(), trace_out)
            print(f"WROTE,{trace_out} ({n_events} request events)")
    write_bench_json(
        "attribution",
        {
            "rows": rows,
            "components": components,
            "checks": checks,
            "wall_time_s": time.perf_counter() - t_start,
        },
        num_requests=num_requests,
        read_fraction=read_fraction,
        seed=seed,
        daemon_interval=daemon_interval,
        num_bins=num_bins,
        samples_per_chunk=samples_per_chunk,
        service=True,
        routing_publish_lag_chunks=ROUTING.publish_lag_chunks,
        routing_cache_entries=ROUTING.cache_entries,
    )
    return {"rows": rows, "components": components, "checks": checks}


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-requests", type=int, default=30_000)
    ap.add_argument("--read-fraction", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--daemon-interval", type=int, default=1000)
    ap.add_argument("--num-bins", type=int, default=96)
    ap.add_argument(
        "--policies", nargs="+", default=list(DEFAULT_POLICIES),
        metavar="NAME[:k=v,...]",
    )
    ap.add_argument(
        "--trace-out", metavar="PATH",
        help="write the flight-recorder Chrome trace (Perfetto-loadable) "
        "for --trace-policy here",
    )
    ap.add_argument("--trace-policy", default="redynis")
    ap.add_argument("--samples-per-chunk", type=int, default=8)
    args = ap.parse_args()
    main(
        num_requests=args.num_requests,
        read_fraction=args.read_fraction,
        seed=args.seed,
        daemon_interval=args.daemon_interval,
        policy_specs=tuple(args.policies),
        num_bins=args.num_bins,
        trace_out=args.trace_out,
        trace_policy=args.trace_policy,
        samples_per_chunk=args.samples_per_chunk,
    )
