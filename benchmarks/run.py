"""Benchmark entry point: ``python -m benchmarks.run [names...]``.

One module per paper table/figure + the beyond-paper integration benches:

  fig2_uniform      paper Figure 2 (uniform access, Local/Remote/Optimized)
  fig3_skewed       paper Figure 3 (zipfian 90/10) + affinity sweep
  daemon_sweep      Algorithm 3 analysis throughput (pure JAX vs Pallas)
  capacity_sweep    hit-rate vs per-node replica budget (beyond paper)
  policy_matrix     registered-policy head-to-head on the wan5 geo cluster
  tail_latency      P50/P99/P99.9 per policy x topology (in-scan telemetry)
  moe_placement     hot-expert replica cache on the reduced MoE
  hot_embedding     hot-row cache hit rates + HBM bytes saved
  serving_sessions  session-cache migration vs static placement
  roofline          aggregate the dry-run sweep into the §Roofline table

``--policy NAME[:k=v,...]`` selects a placement policy from the
``repro.core.policy`` registry (e.g. ``--policy redynis:h=0.05`` or
``--policy topk:k=50``) and is forwarded to every selected bench whose
``main`` accepts a ``policy`` kwarg (daemon_sweep, capacity_sweep,
policy_matrix, tail_latency). ``--replay-backend jax|pallas`` selects the
fused engine's chunk-replay backend the same way (fig2_uniform,
fig3_skewed, policy_matrix, tail_latency, engine_throughput).

Every line of output in ``RESULT,name,value,unit,k=v`` form is machine
collectable; EXPERIMENTS.md quotes them directly. The figure / sweep
benches additionally persist ``BENCH_<name>.json`` (throughput, hit-rate,
wall-time) — the perf-trajectory files CI uploads as artifacts; set
``$BENCH_DIR`` to redirect them.
"""

from __future__ import annotations

import inspect
import sys
import time

from benchmarks.common import enable_compile_cache

MODULES = [
    "fig2_uniform",
    "fig3_skewed",
    "daemon_sweep",
    "capacity_sweep",
    "policy_matrix",
    "tail_latency",
    "engine_throughput",
    "moe_placement",
    "hot_embedding",
    "serving_sessions",
    "roofline",
]

# CPU-friendly iteration counts for the figure benches (full fidelity is
# iterations=5, num_requests=100_000 — the EXPERIMENTS.md numbers).
FAST_KWARGS = {
    "fig2_uniform": {"iterations": 3, "num_requests": 50_000},
    "fig3_skewed": {"iterations": 3, "num_requests": 50_000},
    "capacity_sweep": {"num_requests": 20_000},
    "policy_matrix": {"num_requests": 10_000},
    "tail_latency": {"num_requests": 10_000, "iterations": 2},
    "engine_throughput": {"num_requests": 50_000, "repeats": 3},
}


def main() -> None:
    args = sys.argv[1:]
    policy = None
    if "--policy" in args:
        from repro.core.policy import parse_policy

        at = args.index("--policy")
        if at + 1 >= len(args):
            raise SystemExit("--policy requires a spec, e.g. redynis:h=0.2")
        policy = parse_policy(args[at + 1])
        del args[at : at + 2]
    replay_backend = None
    if "--replay-backend" in args:
        at = args.index("--replay-backend")
        if at + 1 >= len(args):
            raise SystemExit("--replay-backend requires jax or pallas")
        replay_backend = args[at + 1]
        del args[at : at + 2]
    full = "--full" in args
    names = [n for n in args if not n.startswith("--")]
    if not names:
        names = MODULES
    t0 = time.time()
    for name in names:
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        kwargs = {} if full else dict(FAST_KWARGS.get(name, {}))
        sig = inspect.signature(mod.main).parameters
        if policy is not None and "policy" in sig:
            kwargs["policy"] = policy
        if replay_backend is not None and "replay_backend" in sig:
            kwargs["replay_backend"] = replay_backend
        mod.main(**kwargs)
    print(f"\nall benchmarks done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
