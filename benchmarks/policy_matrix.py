"""Policy head-to-head matrix on the 5-region WAN — the experiment grid the
policy API exists for: every registered decision rule (Algorithm 3, the
static baselines, top-K replication, size-aware cost-greedy, decayed-LFU)
on the same skewed geo workload, seeds batched and same-family dynamic
params vmapped into one program per family (``run_experiment(policies=...)``).

Emits per-policy hit-rate / mean-latency / throughput rows and persists
``BENCH_policy_matrix.json``.
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
    TelemetryConfig,
    parse_policy,
    run_experiment,
    wan5_cluster,
)

# Spec strings (registry-parsed) so the matrix is CLI-overridable.
DEFAULT_POLICIES = (
    "local",
    "remote",
    "replicated",
    "redynis",
    "redynis:h=0.05,decay=0.9",
    "topk:k=100",
    "costgreedy",
    "decaylfu:alpha=0.5",
)


def main(
    num_requests: int = 30_000,
    iterations: int = 3,
    read_fraction: float = 0.9,
    policy_specs=DEFAULT_POLICIES,
    policy=None,
    replay_backend: str = "jax",
) -> dict:
    banner("policy_matrix: policy head-to-head on the wan5 geo cluster")
    candidates = [parse_policy(s) for s in policy_specs]
    if policy is not None:
        candidates.append(policy)
    policies = dedupe_policies(candidates, 5)
    t_start = time.perf_counter()
    res = run_experiment(
        read_fractions=(read_fraction,),
        skewed=True,
        iterations=iterations,
        num_requests=num_requests,
        cluster=wan5_cluster(),
        policies=policies,
        telemetry=TelemetryConfig(),
        replay_backend=replay_backend,
        **WAN5_WORKLOAD_KWARGS,
    )
    rows, quantiles = [], {}
    for label, policy_rows in res["policies"].items():
        row = policy_rows[0]
        emit(
            "policy_matrix",
            round(row["hit_rate"], 4),
            "hit_rate",
            policy=label,
            mean_latency_ms=round(row["mean_latency_ms"], 2),
            p99_latency_ms=round(row["p99_latency_ms"], 2),
            p99_ci99=round(row["p99_ci99"], 2),
            throughput=round(row["throughput"], 2),
            ci99=round(row["ci99"], 2),
        )
        quantiles[label] = row["quantiles"]
        rows.append(
            {
                "policy": label,
                "read_fraction": row["read_fraction"],
                "hit_rate": row["hit_rate"],
                "mean_latency_ms": row["mean_latency_ms"],
                "p99_latency_ms": row["p99_latency_ms"],
                "p99_ci99": row["p99_ci99"],
                "throughput_ops_s": row["throughput"],
                "ci99": row["ci99"],
            }
        )
    write_bench_json(
        "policy_matrix",
        {
            "rows": rows,
            "num_batched_calls": res["num_batched_calls"],
            "wall_time_s": time.perf_counter() - t_start,
        },
        quantiles=quantiles,
        num_requests=num_requests,
        iterations=iterations,
        read_fraction=read_fraction,
        cluster="wan5",
        replay_backend=replay_backend,
    )
    return res


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-requests", type=int, default=30_000)
    ap.add_argument("--iterations", type=int, default=3)
    ap.add_argument("--read-fraction", type=float, default=0.9)
    ap.add_argument(
        "--policies", nargs="+", default=list(DEFAULT_POLICIES),
        metavar="NAME[:k=v,...]",
        help="registry policy specs to race (default: all built-ins)",
    )
    ap.add_argument(
        "--replay-backend", choices=["jax", "pallas"], default="jax",
        help="chunk-replay backend for the fused engine",
    )
    args = ap.parse_args()
    main(
        num_requests=args.num_requests,
        iterations=args.iterations,
        read_fraction=args.read_fraction,
        policy_specs=tuple(args.policies),
        replay_backend=args.replay_backend,
    )
