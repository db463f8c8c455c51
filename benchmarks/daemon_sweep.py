"""Placement-daemon analysis throughput — the paper's "constant time per
key" claim, measured: keys/second for Algorithm 3 sweeps at growing key
counts, through the scored pipeline's pluggable backends (``--backend
jax|pallas|both``; Pallas runs in interpret mode on CPU, so its numbers
here validate semantics — MXU-free VPU tiling is what the kernel buys on
real TPU). Also times the scan-compatible masked step and the capacity
projection stage, and persists ``BENCH_daemon_sweep.json``."""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from benchmarks.common import (
    banner,
    emit,
    enable_compile_cache,
    time_fn,
    write_bench_json,
)
from repro.core.metadata import create_store
from repro.core.placement import masked_step, sweep
from repro.core.policy import (
    PolicyContext,
    describe_policy,
    parse_policy,
    policy_masked_step,
    split_policy,
)


def main(
    sizes=(1_000, 10_000, 100_000, 1_000_000),
    n_nodes: int = 16,
    backend: str = "both",
    policy=None,
) -> list[dict]:
    banner(f"daemon_sweep: Algorithm 3 analysis throughput (backend={backend})")
    backends = ("jax", "pallas") if backend == "both" else (backend,)
    if policy is not None:
        policy = policy.resolve(n_nodes)
        policy.validate(n_nodes)
    rows: list[dict] = []
    t_start = time.perf_counter()
    for k in sizes:
        ks = jax.random.split(jax.random.PRNGKey(k % 2**31), 3)
        counts = jax.random.randint(ks[0], (k, n_nodes), 0, 100).astype(jnp.int32)
        hosts = jax.random.uniform(ks[1], (k, n_nodes)) > 0.8
        store = create_store(k, n_nodes)._replace(
            access_counts=counts,
            hosts=hosts,
            live=jnp.ones((k,), bool),
        )
        h = 1.0 / n_nodes
        obj = jax.random.uniform(ks[2], (k,), minval=64.0, maxval=4096.0)
        cap = jnp.full((n_nodes,), 0.3 * float(jnp.sum(obj)) / n_nodes)

        for bk in backends:
            t_sweep = time_fn(
                lambda: sweep(store, h, 0, backend=bk)[0].owners, iters=3
            )
            emit(
                f"daemon_sweep_{bk}",
                round(k / t_sweep / 1e6, 3),
                "Mkeys/s",
                keys=k,
                note="interpret-mode-on-CPU" if bk == "pallas" else "",
            )
            rows.append(
                {"name": f"sweep_{bk}", "keys": k, "mkeys_per_s": k / t_sweep / 1e6}
            )

            # Capacity-projected sweep: the full scored pipeline with a
            # finite per-node byte budget (projection = 3 sorts + cumsum).
            t_capped = time_fn(
                lambda: sweep(
                    store, h, 0, object_bytes=obj, capacity_bytes=cap,
                    backend=bk,
                )[0].owners,
                iters=3,
            )
            emit(
                f"daemon_sweep_{bk}_capacity",
                round(k / t_capped / 1e6, 3),
                "Mkeys/s",
                keys=k,
            )
            rows.append(
                {
                    "name": f"sweep_{bk}_capacity",
                    "keys": k,
                    "mkeys_per_s": k / t_capped / 1e6,
                }
            )

            # Scan-compatible (due-masked) step: the form the fused
            # simulation engine runs inside lax.scan — masking must not
            # cost throughput (measured per backend, like the sweep).
            masked = jax.jit(
                lambda s, due: masked_step(s, 0, due, h=h, backend=bk)[1].hosts
            )
            t_masked = time_fn(
                lambda: masked(store, jnp.bool_(True)), iters=5
            )
            emit(
                f"daemon_sweep_masked_step_{bk}",
                round(k / t_masked / 1e6, 3),
                "Mkeys/s",
                keys=k,
            )
            rows.append(
                {
                    "name": f"masked_step_{bk}",
                    "keys": k,
                    "mkeys_per_s": k / t_masked / 1e6,
                }
            )

        if policy is not None:
            # Generic policy engine: decide + shared expiry/capacity stages
            # through `core.policy.policy_masked_step` (the form the fused
            # simulator runs for any registered policy).
            label = describe_policy(policy)
            static, params = split_policy(policy)
            rtt = jnp.where(
                jnp.eye(n_nodes, dtype=bool), 0.0,
                jnp.full((n_nodes, n_nodes), 100.0),
            )
            ctx = PolicyContext(
                rtt=rtt, object_bytes=obj, capacity_bytes=cap, params=params
            )
            pstate = static.init(store, ctx)
            stepped = jax.jit(
                lambda s, ps, due: policy_masked_step(static, ps, s, 0, due, ctx)[
                    2
                ].hosts
            )
            t_policy = time_fn(
                lambda: stepped(store, pstate, jnp.bool_(True)), iters=5
            )
            emit(
                "daemon_sweep_policy",
                round(k / t_policy / 1e6, 3),
                "Mkeys/s",
                keys=k,
                policy=label,
            )
            rows.append(
                {
                    "name": "policy_masked_step",
                    "policy": label,
                    "keys": k,
                    "mkeys_per_s": k / t_policy / 1e6,
                }
            )

    write_bench_json(
        "daemon_sweep",
        {"rows": rows, "wall_time_s": time.perf_counter() - t_start},
        backend=backend,
        n_nodes=n_nodes,
    )
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--backend", choices=("jax", "pallas", "both"), default="both",
        help="sweep backend(s) to measure",
    )
    ap.add_argument(
        "--sizes", type=int, nargs="+",
        default=[1_000, 10_000, 100_000, 1_000_000],
    )
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument(
        "--policy", type=parse_policy, default=None, metavar="NAME[:k=v,...]",
        help="additionally time core.policy.policy_masked_step for this "
        "registry spec (e.g. redynis:h=0.05, topk:k=500, decaylfu)",
    )
    args = ap.parse_args()
    main(
        sizes=tuple(args.sizes), n_nodes=args.nodes, backend=args.backend,
        policy=args.policy,
    )
