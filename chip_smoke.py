"""Chip smoke test: drive the simulator's main path on a TPU and check it.

Runs ``repro.kvsim.run_scenario`` on one TPU chip at a geo deployment of
10^7 keys: the 5-region ``wan5`` cluster under the YCSB-B read/update mix
with 1 KB records and Redynis placement. Then it holds the chip's results
to the repo's own references: streamed against materialised traces, the
fused engine against ``run_scenario_reference``, and the compiled Pallas
kernels against the jax path. There is no CPU fallback: without a TPU the
script fails and prints no result.

    python3 chip_smoke.py            # one chip: phases 1-5
    python3 chip_smoke.py --chips 4  # four chips: sharded vs unsharded only

Every phase raises on a mismatch, so a non-zero exit means a phase failed.
The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Printed speeds are information, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import enable_compile_cache  # noqa: E402
from repro.kernels.chunk_replay.ops import chunk_replay  # noqa: E402
from repro.kernels.common import interpret_default  # noqa: E402
from repro.kernels.latency_histogram.ops import latency_histogram  # noqa: E402
from repro.kernels.ownership_sweep.ops import ownership_sweep  # noqa: E402
from repro.kvsim import (  # noqa: E402
    RedynisPolicy,
    SimResult,
    TelemetryConfig,
    run_scenario,
    run_scenario_reference,
    wan5_cluster,
    wan5_workload,
)

# The deployment: 10^7 keys is the paper's premise; 10^8 requests at one
# daemon sweep per 10^6 requests gives 100 sweeps of the whole keyspace.
DEPLOY_KEYS = 10_000_000
DEPLOY_REQUESTS = 100_000_000
DEPLOY_INTERVAL = 1_000_000

# Tolerances of the repo's tests: engine vs reference and pallas vs jax
# (tests/test_simulate_equivalence.py, tests/test_chunk_replay.py), and
# sharded vs unsharded f32 reductions (PR 7's documented bound).
RTOL = 1e-4

# SimResult fields that count events: exact on every path.
COUNTERS = (
    "hit_rate", "replication_moves", "deletion_moves", "evictions",
    "capacity_evictions",
)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    tail = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"PHASE {phase} {tail}", flush=True)


def deployment(num_requests: int = DEPLOY_REQUESTS,
               num_keys: int = DEPLOY_KEYS):
    """wan5 (5 regions, WAN5 RTTs, 10 ms service) under YCSB-B: 95% reads,
    1 KB records (CoreWorkload fieldcount=10 x fieldlength=100), with the
    repo's 90/10 hot-set skew."""
    return wan5_workload(
        num_keys=num_keys, num_requests=num_requests, read_fraction=0.95,
        object_bytes=1024.0,
    )


def assert_results(a: SimResult, b: SimResult, ctx: str, *,
                   exact=SimResult._fields, rtol: float = 0.0) -> None:
    """Fields in ``exact`` must match bit for bit, the rest to ``rtol``."""
    for field, x, y in zip(SimResult._fields, a, b):
        x, y = np.asarray(x), np.asarray(y)
        if field in exact:
            check(np.array_equal(x, y), f"{ctx}: {field} {x} != {y}")
        else:
            check(np.allclose(x, y, rtol=rtol, atol=0.0),
                  f"{ctx}: {field} {x} vs {y} beyond rtol={rtol}")


def check_finite(res: SimResult, ctx: str) -> None:
    for field, v in zip(SimResult._fields, res):
        check(bool(np.all(np.isfinite(np.asarray(v)))),
              f"{ctx}: {field} is not finite: {v}")


def phase_device() -> jax.Device:
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {dev.platform!r}")
    check(not interpret_default(),
          "interpret_default() is True on a TPU: kernels would not compile")
    say("1 device", kind=repr(dev.device_kind), count=len(jax.devices()))
    return dev


def phase_deployment(dev: jax.Device) -> None:
    wl, cl = deployment(), wan5_cluster()
    kw = dict(seed=0, daemon_interval=DEPLOY_INTERVAL,
              telemetry=TelemetryConfig(), trace_mode="streamed")
    t0 = time.perf_counter()
    cold = run_scenario(wl, cl, RedynisPolicy(), **kw)
    t1 = time.perf_counter()
    res, trace = run_scenario(wl, cl, RedynisPolicy(), **kw)
    t2 = time.perf_counter()
    cold_s, warm_s = t1 - t0, t2 - t1
    check_finite(res, "deployment")
    check(0.0 <= res.hit_rate <= 1.0, f"hit rate {res.hit_rate}")
    served = float(trace.hist_group.sum())
    check(served == wl.num_requests,
          f"histogram holds {served} requests, served {wl.num_requests}")
    assert_results(cold[0], res, "deployment cold vs warm")
    p99 = trace.quantile(0.99)
    check(bool(np.isfinite(p99)), f"P99 {p99}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    say("2 deployment", keys=wl.num_keys, requests=wl.num_requests,
        daemon_interval=DEPLOY_INTERVAL, compile_s=cold_s - warm_s,
        cold_s=cold_s, warm_s=warm_s,
        sim_requests_per_s=wl.num_requests / warm_s,
        hit_rate=res.hit_rate, mean_latency_ms=res.mean_latency_ms,
        p99_latency_ms=p99, replication_moves=res.replication_moves,
        peak_bytes_in_use=peak)


def phase_stream_equivalence() -> None:
    wl, cl = deployment(num_requests=10_000_000), wan5_cluster()
    kw = dict(seed=0, daemon_interval=DEPLOY_INTERVAL,
              telemetry=TelemetryConfig())
    mat, tm = run_scenario(wl, cl, RedynisPolicy(),
                           trace_mode="materialized", **kw)
    st, ts = run_scenario(wl, cl, RedynisPolicy(), trace_mode="streamed",
                          **kw)
    assert_results(mat, st, "streamed vs materialized")
    check(np.array_equal(tm.hist_group, ts.hist_group),
          "streamed vs materialized: histograms differ")
    say("3 streamed==materialized", keys=wl.num_keys,
        requests=wl.num_requests, hit_rate=st.hit_rate,
        mean_latency_ms=st.mean_latency_ms)


def phase_reference() -> None:
    wl, cl = deployment(num_requests=100_000, num_keys=1_000), wan5_cluster()
    kw = dict(seed=0, daemon_interval=1_000)
    fused = run_scenario(wl, cl, RedynisPolicy(), **kw)
    ref = run_scenario_reference(wl, cl, RedynisPolicy(), **kw)
    assert_results(fused, ref, "engine vs reference", exact=(), rtol=RTOL)
    say("4 engine==reference", keys=wl.num_keys, requests=wl.num_requests,
        rtol=RTOL, hit_rate=fused.hit_rate,
        mean_latency_ms=fused.mean_latency_ms)


def lowered_kernels(k: int, b: int, n: int):
    """The three main-path kernels' jitted wrappers, lowered for this
    platform with the interpret flag left to auto-selection."""
    s = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    yield "chunk_replay", chunk_replay.lower(
        s((k, n), bool), s((b,), i32), s((b,), i32), s((b,), bool),
        s((b,), bool), s((n, n), f32), service_ms=10.0, master=0,
        xfer_read_ms=0.0, xfer_write_ms=0.0, read_mode="map", num_bins=128,
        backend="pallas",
    )
    yield "ownership_sweep", ownership_sweep.lower(
        s((k, n), i32), s((k, n), bool), s((k,), bool), s((k,), i32),
        s((), i32), h=s((), f32),
    )
    yield "latency_histogram", latency_histogram.lower(
        s((b,), f32), s((b,), i32), s((b,), f32), num_groups=2 * n,
    )


def phase_pallas() -> None:
    wl, cl = deployment(num_requests=1_000_000, num_keys=100_000), \
        wan5_cluster()
    say("5 pallas", note="keys cut to 1e5: the chunk_replay gather costs "
        "O(requests x keys), ROADMAP Speed item 3")
    interval = 10_000
    for name, lowered in lowered_kernels(wl.num_keys, interval, 5):
        check("tpu_custom_call" in lowered.as_text(),
              f"{name}: no tpu_custom_call in the lowered program")
    kw = dict(seed=0, daemon_interval=interval)
    ref, tr = run_scenario(wl, cl, RedynisPolicy(),
                           telemetry=TelemetryConfig(), **kw)
    ker, tk = run_scenario(
        wl, cl, RedynisPolicy(backend="pallas"),
        telemetry=TelemetryConfig(backend="pallas"), replay_backend="pallas",
        **kw,
    )
    assert_results(ref, ker, "pallas vs jax", exact=COUNTERS, rtol=RTOL)
    check(np.array_equal(tr.hist_group, tk.hist_group),
          "pallas vs jax: histograms differ")
    say("5 pallas==jax", keys=wl.num_keys, requests=wl.num_requests,
        daemon_interval=interval, rtol=RTOL, hit_rate=ker.hit_rate,
        mean_latency_ms=ker.mean_latency_ms)


def phase_sharded(num_shards: int) -> None:
    devices = jax.devices()
    check(len(devices) >= num_shards,
          f"--chips {num_shards} needs {num_shards} devices, "
          f"have {len(devices)}")
    wl, cl = deployment(), wan5_cluster()
    kw = dict(seed=0, daemon_interval=DEPLOY_INTERVAL,
              telemetry=TelemetryConfig(), trace_mode="streamed")
    t0 = time.perf_counter()
    sharded, ts = run_scenario(wl, cl, RedynisPolicy(),
                               num_shards=num_shards, **kw)
    t1 = time.perf_counter()
    # The mesh must span every chip: each one held at least the replicated
    # [K] natural-source vector while the sharded run was live.
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices[:num_shards]]
    check(all(p >= 4 * wl.num_keys for p in peaks),
          f"not every chip held state: peak_bytes_in_use {peaks}")
    single, t1s = run_scenario(wl, cl, RedynisPolicy(), **kw)
    assert_results(single, sharded, f"{num_shards} shards vs 1",
                   exact=COUNTERS, rtol=RTOL)
    check(np.array_equal(t1s.hist_group, ts.hist_group),
          f"{num_shards} shards vs 1: histograms differ")
    check(np.allclose(t1s.occupancy_bytes, ts.occupancy_bytes, rtol=RTOL,
                      atol=0.0),
          f"{num_shards} shards vs 1: occupancy beyond rtol={RTOL}")
    say("6 sharded==single", shards=num_shards, keys=wl.num_keys,
        requests=wl.num_requests, sharded_cold_s=t1 - t0,
        hit_rate=sharded.hit_rate, mean_latency_ms=sharded.mean_latency_ms,
        peak_bytes_in_use=peaks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the key-sharded engine against the unsharded one",
    )
    args = ap.parse_args(argv)
    say("0 compile-cache", dir=enable_compile_cache())
    dev = phase_device()
    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_deployment(dev)
        phase_stream_equivalence()
        phase_reference()
        phase_pallas()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
