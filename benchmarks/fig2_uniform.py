"""Paper Figure 2 — Uniform Object Access Distribution.

Local / Remote / Optimized (+ beyond-paper Replicated) throughput across
read ratios 100% -> 50%, 100k requests, 3 nodes, 100 ms simulated remote
RTT, with 99% confidence intervals over repeated iterations — the exact
experiment grid of paper §8.2/§9.

``engine="scan"`` (default) runs the fused lax.scan engine with the seed
dimension vmapped; ``compare_engines=True`` additionally times the retained
per-chunk reference loop on the same grid and reports the fusion speedup
(warm timings — each engine runs once to compile, then is timed).
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
from repro.kvsim import RedynisPolicy, StaticPolicy, run_experiment

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
    engine: str = "scan",
    compare_engines: bool = False,
    replay_backend: str = "jax",
) -> dict:
    banner("fig2: uniform object access distribution (paper Figure 2)")
    t_start = time.perf_counter()
    res = run_experiment(
        policies=list(BASELINES.values()),
        read_fractions=(1.0, 0.9, 0.75, 0.5),
        skewed=False,
        iterations=iterations,
        num_requests=num_requests,
        engine=engine,
        replay_backend=replay_backend,
    )
    wall_s = time.perf_counter() - t_start
    # run_experiment keys rows by resolved-policy label, in input order.
    by_name = dict(zip(BASELINES, res["policies"].values()))
    for scenario, rows in by_name.items():
        for row in rows:
            emit(
                "fig2_uniform",
                round(row["throughput"], 2),
                "ops/s",
                scenario=scenario,
                read_fraction=row["read_fraction"],
                ci99=round(row["ci99"], 2),
                hit_rate=round(row["hit_rate"], 4),
            )
    # Paper §10 validation: Optimized ~10x Remote, near Local.
    opt = {r["read_fraction"]: r["throughput"] for r in by_name["optimized"]}
    rem = {r["read_fraction"]: r["throughput"] for r in by_name["remote"]}
    loc = {r["read_fraction"]: r["throughput"] for r in by_name["local"]}
    for rf in opt:
        emit(
            "fig2_validation",
            round(opt[rf] / rem[rf], 2),
            "x_over_remote",
            read_fraction=rf,
            frac_of_local=round(opt[rf] / loc[rf], 3),
        )

    write_bench_json(
        "fig2_uniform",
        {"scenarios": json_rows(by_name), "wall_time_s": wall_s},
        engine=engine,
        iterations=iterations,
        num_requests=num_requests,
        replay_backend=replay_backend,
    )

    if compare_engines:
        banner("fig2b: scan-fusion speedup over the reference chunk loop")
        timings = {}
        kw = dict(
            policies=list(BASELINES.values()),
            iterations=iterations,
            num_requests=num_requests,
        )
        for eng in ("scan", "reference"):
            run_experiment(engine=eng, **kw)  # compile / warm caches
            t0 = time.perf_counter()
            run_experiment(engine=eng, **kw)
            timings[eng] = time.perf_counter() - t0
            emit("fig2b_engine_s", round(timings[eng], 3), "s", engine=eng)
        emit(
            "fig2b_fusion_speedup",
            round(timings["reference"] / timings["scan"], 2),
            "x",
            num_requests=num_requests,
            iterations=iterations,
        )
    return res


if __name__ == "__main__":
    enable_compile_cache()
    main()
