"""Paper Figure 3 — Skewed (zipfian 90/10) Object Access Distribution.

Same grid as Figure 2 but with the paper's skewed workload: 10% of data
items receive 90% of traffic. Adds a beyond-paper affinity sweep showing how
the Optimized scenario degrades as request sources for a key spread across
nodes (the paper's DNS-affinity assumption weakening).
"""

from __future__ import annotations

import time

from benchmarks.common import (
    banner,
    emit,
    enable_compile_cache,
    json_rows,
    write_bench_json,
)
from repro.kvsim import (
    ClusterConfig,
    RedynisPolicy,
    StaticPolicy,
    WorkloadConfig,
    diurnal_workload,
    run_experiment,
    run_scenario,
    wan5_cluster,
    wan5_workload,
)

# The paper's four scenarios as policies, keyed by the figure's labels.
BASELINES = {
    "local": StaticPolicy(mode="local"),
    "remote": StaticPolicy(mode="remote"),
    "optimized": RedynisPolicy(),
    "replicated": StaticPolicy(mode="replicated"),
}


def main(
    iterations: int = 5,
    num_requests: int = 100_000,
    replay_backend: str = "jax",
) -> dict:
    banner("fig3: skewed (zipfian 90/10) object access (paper Figure 3)")
    t_start = time.perf_counter()
    res = run_experiment(
        policies=list(BASELINES.values()),
        read_fractions=(1.0, 0.9, 0.75, 0.5),
        skewed=True,
        iterations=iterations,
        num_requests=num_requests,
        replay_backend=replay_backend,
    )
    # run_experiment keys rows by resolved-policy label, in input order.
    by_name = dict(zip(BASELINES, res["policies"].values()))
    for scenario, rows in by_name.items():
        for row in rows:
            emit(
                "fig3_skewed",
                round(row["throughput"], 2),
                "ops/s",
                scenario=scenario,
                read_fraction=row["read_fraction"],
                ci99=round(row["ci99"], 2),
                hit_rate=round(row["hit_rate"], 4),
            )
    opt = {r["read_fraction"]: r["throughput"] for r in by_name["optimized"]}
    rem = {r["read_fraction"]: r["throughput"] for r in by_name["remote"]}
    loc = {r["read_fraction"]: r["throughput"] for r in by_name["local"]}
    for rf in opt:
        emit(
            "fig3_validation",
            round(opt[rf] / rem[rf], 2),
            "x_over_remote",
            read_fraction=rf,
            frac_of_local=round(opt[rf] / loc[rf], 3),
        )

    banner("fig3b: affinity sweep (beyond paper)")
    cluster = ClusterConfig()
    for affinity in (1.0, 0.95, 0.9, 0.8, 0.6, 1.0 / 3.0):
        wl = WorkloadConfig(
            num_requests=num_requests // 2, skewed=True, affinity=affinity
        )
        r = run_scenario(
            wl, cluster, RedynisPolicy(), seed=0,
            replay_backend=replay_backend,
        )
        emit(
            "fig3b_affinity",
            round(r.throughput_ops_s, 2),
            "ops/s",
            affinity=round(affinity, 3),
            hit_rate=round(r.hit_rate, 4),
            repl_moves=int(r.replication_moves),
        )

    banner("fig3c: 5-region WAN topology (beyond paper)")
    geo = wan5_cluster()
    wl5 = wan5_workload(num_requests=num_requests // 2)
    for label, pol in (
        ("local", StaticPolicy(mode="local")),
        ("remote", StaticPolicy(mode="remote")),
        ("optimized", RedynisPolicy()),
    ):
        r = run_scenario(wl5, geo, pol, seed=0, replay_backend=replay_backend)
        emit(
            "fig3c_wan5",
            round(r.throughput_ops_s, 2),
            "ops/s",
            scenario=label,
            hit_rate=round(r.hit_rate, 4),
            mean_latency_ms=round(r.mean_latency_ms, 2),
        )

    banner("fig3d: diurnal hot region — decay chases moving traffic")
    wld = diurnal_workload(num_requests=num_requests // 2)
    for decay in (1.0, 0.5):
        r = run_scenario(
            wld, geo, RedynisPolicy(decay=decay), seed=0,
            replay_backend=replay_backend,
        )
        emit(
            "fig3d_diurnal",
            round(r.throughput_ops_s, 2),
            "ops/s",
            decay=decay,
            hit_rate=round(r.hit_rate, 4),
            repl_moves=int(r.replication_moves),
        )
    write_bench_json(
        "fig3_skewed",
        {
            "scenarios": json_rows(by_name),
            "wall_time_s": time.perf_counter() - t_start,
        },
        iterations=iterations,
        num_requests=num_requests,
        replay_backend=replay_backend,
    )
    return res


if __name__ == "__main__":
    enable_compile_cache()
    main()
