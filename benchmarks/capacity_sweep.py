"""Hit-rate / throughput vs per-node replica budget — the scenario axis the
capacity projection opens (paper Algorithm 3 never models memory pressure;
size-aware sharding and DINOMO's elastic capacity management both show this
is where placement gets interesting).

Sweeps the OPTIMIZED scenario across shrinking ``capacity_bytes`` (inf =
the paper, then budgets above / around / well below the hot set, which is
hot_fraction × num_keys × object_bytes ≈ 100 KiB at the defaults) on the
skewed workload with a lognormal object-size distribution, and persists
``BENCH_capacity_sweep.json``."""

from __future__ import annotations

import argparse
import time

from benchmarks.common import (
    banner,
    emit,
    enable_compile_cache,
    write_bench_json,
)
from repro.kvsim import (
    ClusterConfig,
    RedynisPolicy,
    WorkloadConfig,
    describe_policy,
    parse_policy,
    run_scenario,
)

DEFAULT_CAPACITIES_KIB = (float("inf"), 256, 128, 64, 32, 16)


def main(
    num_requests: int = 50_000,
    capacities_kib=DEFAULT_CAPACITIES_KIB,
    object_bytes_sigma: float = 0.5,
    backend: str = "jax",
    seed: int = 0,
    policy=None,
) -> list[dict]:
    if policy is None:
        policy = RedynisPolicy(backend=backend)
    banner(
        f"capacity_sweep: hit-rate vs per-node replica budget "
        f"(policy={describe_policy(policy)})"
    )
    wl = WorkloadConfig(
        num_requests=num_requests,
        skewed=True,
        object_bytes_sigma=object_bytes_sigma,
    )
    rows: list[dict] = []
    t_start = time.perf_counter()
    for cap_kib in capacities_kib:
        cap = float("inf") if cap_kib == float("inf") else cap_kib * 1024.0
        cl = ClusterConfig(capacity_bytes=cap)
        t0 = time.perf_counter()
        r = run_scenario(wl, cl, policy, seed=seed)
        wall = time.perf_counter() - t0
        label = "inf" if cap == float("inf") else f"{cap_kib:g}"
        emit(
            "capacity_sweep",
            round(r.throughput_ops_s, 2),
            "ops/s",
            capacity_kib=label,
            hit_rate=round(r.hit_rate, 4),
            capacity_evictions=int(r.capacity_evictions),
            repl_moves=int(r.replication_moves),
            peak_occupancy_kib=round(float(r.peak_occupancy_bytes.max()) / 1024.0, 1),
        )
        rows.append(
            {
                "capacity_kib": None if cap == float("inf") else cap_kib,
                "throughput_ops_s": r.throughput_ops_s,
                "hit_rate": r.hit_rate,
                "mean_latency_ms": r.mean_latency_ms,
                "replication_moves": r.replication_moves,
                "capacity_evictions": r.capacity_evictions,
                "evictions": r.evictions,
                "peak_occupancy_bytes": r.peak_occupancy_bytes.tolist(),
                "wall_time_s": wall,
            }
        )
    write_bench_json(
        "capacity_sweep",
        {"rows": rows, "wall_time_s": time.perf_counter() - t_start},
        policy=describe_policy(policy),
        num_requests=num_requests,
        object_bytes_sigma=object_bytes_sigma,
    )
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-requests", type=int, default=50_000)
    ap.add_argument("--backend", choices=("jax", "pallas"), default="jax")
    ap.add_argument(
        "--policy", type=parse_policy, default=None, metavar="NAME[:k=v,...]",
        help="placement policy spec, e.g. redynis:h=0.2 or topk:k=50 "
        "(default: redynis with --backend)",
    )
    ap.add_argument(
        "--capacities-kib", type=float, nargs="+", default=None,
        help="per-node budgets in KiB (omit for the default ladder incl. inf)",
    )
    args = ap.parse_args()
    caps = (
        tuple(args.capacities_kib)
        if args.capacities_kib
        else DEFAULT_CAPACITIES_KIB
    )
    main(
        num_requests=args.num_requests,
        capacities_kib=caps,
        backend=args.backend,
        policy=args.policy,
    )
