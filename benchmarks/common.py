"""Shared benchmark utilities: timing, CSV/report emission, and persisted
``BENCH_<name>.json`` result files (the perf trajectory CI archives)."""

from __future__ import annotations

import json
import os
import time
from typing import Callable

import jax
import numpy as np

__all__ = [
    "enable_compile_cache",
    "time_fn",
    "emit",
    "banner",
    "git_commit",
    "write_bench_json",
    "json_rows",
    "dedupe_policies",
    "BENCH_SCHEMA_VERSION",
    "WAN5_WORKLOAD_KWARGS",
]

# Version stamp for the BENCH_*.json payload shape; bench_trend.py uses it
# (with the git commit) to align and order trajectory points. Bump when a
# top-level payload key changes meaning.
BENCH_SCHEMA_VERSION = 1

# The wan5 geo-traffic preset the policy benchmarks share (policy_matrix,
# tail_latency): skewed sources concentrated in two hot regions. Kept here
# so the cross-benchmark numbers stay comparable; run_experiment builds its
# own WorkloadConfig per read fraction from these kwargs.
WAN5_WORKLOAD_KWARGS = dict(
    num_nodes=5,
    region_weights=(0.35, 0.25, 0.20, 0.12, 0.08),
    affinity=0.8,
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and nothing is set here. Otherwise the cache lives at
    the fixed ``<repo>/.jax_cache``: the path is part of the cache key, so
    a path that moved between runs would never hit. The key takes in the
    op metadata too, so a program is never loaded with another tree's scope
    names, which a profile would show. Entry points call this; importing
    the library never does."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def dedupe_policies(candidates, num_nodes: int) -> list:
    """Drop policies whose *resolved* label (at this cluster size) repeats —
    a forwarded ``--policy`` that coincides with a default entry must not
    trip ``run_experiment``'s duplicate-label check."""
    from repro.kvsim import describe_policy

    seen, out = set(), []
    for p in candidates:
        label = describe_policy(p.resolve(num_nodes))
        if label not in seen:
            seen.add(label)
            out.append(p)
    return out


def json_rows(table: dict) -> dict:
    """``run_experiment`` rows minus the non-JSON leaves (the per-seed
    ``SimResult`` list, the merged ``SimTrace``) — the shape the
    ``BENCH_*.json`` artifacts persist."""
    skip = ("results", "trace")
    return {
        label: [{k: v for k, v in row.items() if k not in skip} for row in rows]
        for label, rows in table.items()
    }


def time_fn(fn: Callable, *args, iters: int = 10, warmup: int = 2) -> float:
    """Median wall-time (seconds) of ``fn(*args)`` with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def emit(name: str, value, unit: str = "", **extra) -> None:
    """One CSV-ish result line: ``name,value,unit,k=v,...``"""
    tail = "".join(f",{k}={v}" for k, v in extra.items())
    print(f"RESULT,{name},{value},{unit}{tail}", flush=True)


def banner(title: str) -> None:
    print(f"\n=== {title} ===", flush=True)


def git_commit() -> str | None:
    """The repo's HEAD commit hash, or ``None`` outside a git checkout (the
    bench files must stay writable from exported tarballs)."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True,
            text=True,
            timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except (OSError, subprocess.SubprocessError):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def write_bench_json(
    name: str, metrics: dict, quantiles: dict | None = None, **meta
) -> str:
    """Persist one benchmark's results as ``BENCH_<name>.json``.

    metrics: the measured values (throughput, hit-rate, wall-time, ... —
        anything JSON-serialisable; numpy scalars are coerced via float).
    quantiles: optional top-level tail-latency block — per-entry
        P50/P90/P95/P99/P99.9 dicts in ms (``SimTrace.tail_summary()``
        shape), keyed however the benchmark groups them (policy label,
        topology, ...). Kept out of ``metrics`` so trajectory scrapers can
        diff the distribution summaries without parsing benchmark-specific
        row schemas.
    meta: run parameters worth keeping next to the numbers (backend,
        num_requests, ...).
    Output directory: ``$BENCH_DIR`` if set, else the current directory.
    Returns the written path (also printed as a ``WROTE,`` line so log
    scrapers can find the artifacts).
    """
    out_dir = os.environ.get("BENCH_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    payload = {
        "bench": name,
        "schema_version": BENCH_SCHEMA_VERSION,
        "git_commit": git_commit(),
        "unix_time": time.time(),
        **meta,
        "metrics": metrics,
    }
    if quantiles is not None:
        payload["quantiles"] = quantiles
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True, default=float)
        fh.write("\n")
    print(f"WROTE,{path}", flush=True)
    return path
