"""Chip benchmark of the Redynis simulator: run one cell once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
deployment (``configs/``) and a traffic mix (``traffic/``). Set-up loads
the program from the compile cache and makes one full warm-up call, since
the request count and the daemon period are static in the compiled scan
and a smaller call would warm another program. The window then makes
back-to-back ``run_scenario`` calls, each with its own seed drawn from
``--seed`` (a closed loop: one study, one scenario after another), and
ends at the first call that completes at or after ``--seconds``.

``--trace 0`` reports the end-to-end metrics: simulated requests per
second over the window, and the set-up seconds. ``--trace 1`` profiles
the second and third calls and reports the per-layer metrics, each read
by ``metrics/<name>.py`` from the stretch that holds the second call and
the host gap after it.

After the window one call, drawn from the seed, is replayed by the plain
reference (``reference.py``) and compared (``compare.py``). The last line
of standard output is one JSON object; the numbers compared, each beside
its limit, close both it and standard error. Without a TPU, or with fewer
chips than the cell asks for, the run exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import NamedTuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import compare, spec  # noqa: E402


class NoChip(RuntimeError):
    pass


class Context(NamedTuple):
    """What a per-layer metric reader is given."""

    reduced: object  # trace_reduce.Reduced of the traced stretch
    requests: int  # simulated requests in the stretch (one call)
    sweeps: int  # daemon sweeps in the stretch
    num_keys: int
    num_nodes: int
    device_kind: str
    chips: int  # devices that ran the stretch; they share its work


def call_seed(seed: int, index) -> int:
    """The seed of one call: a 31-bit draw from ``--seed`` and the call's
    index, so any ``--seed`` gives the same calls and fits the program's
    int32 seed."""
    digest = hashlib.sha256(f"{seed}/{index}".encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def require_chip(chips: int):
    """The cell's devices; raises ``NoChip`` without enough TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform is "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the cache key), or where
    ``JAX_COMPILATION_CACHE_DIR`` says. Every program is cached, however
    quickly it compiles, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations (compile-cache misses) while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if self.active and event == self.EVENT:
            self.count += 1


def _traced_stretch(trace_dir: str, cell: spec.Cell, prog, device_kind: str):
    from chipbench import trace_reduce

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    devices, host = trace_reduce.read_xplane(max(paths, key=os.path.getmtime))
    calls = sorted(s.start_ns for s in host if s.name == "chipbench.call")
    reduced = trace_reduce.reduce(devices, host, calls[0], calls[1])
    ctx = Context(
        reduced=reduced,
        requests=prog.requests_per_call,
        sweeps=-(-prog.requests_per_call // prog.interval),
        num_keys=prog.workload.num_keys,
        num_nodes=prog.workload.num_nodes,
        device_kind=device_kind,
        chips=reduced.chips,
    )
    metrics = {}
    for m in cell.per_layer:
        value = spec.load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return reduced, metrics


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t0: float, devices) -> dict:
    """Set up, measure, check: the result line of one run of ``cell``."""
    import jax

    from chipbench import program, reference

    prog = program.Program(cell.config, cell.traffic)
    counter = CompileCounter()
    prog.call(call_seed(seed, "warm-up"))
    setup_s = time.perf_counter() - t0

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    outs, ends = [], []
    counter.active = True
    start = time.perf_counter()
    while True:
        index = len(outs)
        if trace and index == 1:
            jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("chipbench.call"):
            outs.append(prog.call(call_seed(seed, index)))
        ends.append(time.perf_counter() - start)
        if trace and index == 2:
            jax.profiler.stop_trace()
        elapsed = time.perf_counter() - start
        if elapsed >= seconds and (not trace or len(outs) >= 3):
            break
    counter.active = False
    # The TPU runtime reserves a program's temporaries apart from its
    # buffers: `peak_bytes_in_use` alone misses them, and with
    # `peak_bytes_reserved` it matches the compiled memory analysis.
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)

    result = {
        "correct": False,
        "attempted": len(outs),
        "failed": 0,
        "metrics": {},
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak,
        },
    }
    call_s = [b - a for a, b in zip([0.0] + ends, ends)]
    print(f"window calls={len(outs)} seconds={elapsed} setup_s={setup_s} "
          f"call_s={call_s} compiles_in_window={counter.count} "
          f"memory_stats={stats}", file=sys.stderr, flush=True)
    if trace:
        t_reduce = time.perf_counter()
        reduced, result["metrics"] = _traced_stretch(
            trace_dir, cell, prog, devices[0].device_kind
        )
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace reduced seconds={time.perf_counter() - t_reduce}",
              file=sys.stderr, flush=True)
        result["device"]["busy_s"] = reduced.busy_ns / 1e9
        result["device"]["window_s"] = reduced.window_ns / 1e9
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    else:
        requests = len(outs) * prog.requests_per_call
        result["metrics"] = {
            "sim_requests_per_s": {"value": requests / elapsed,
                                   "unit": "requests/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    result["metrics"] = {m["name"]: result["metrics"][m["name"]]
                         for m in cell.end_to_end + cell.per_layer
                         if m["name"] in result["metrics"]}

    # The window is closed and its memory read: free the program's state,
    # then replay one call, drawn from the seed, through the reference.
    sample = call_seed(seed, "sample") % len(outs)
    sim, sim_trace = outs[sample]
    del outs, prog
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = reference.simulate(reference.deployment(cell.config, cell.traffic),
                             call_seed(seed, sample))
    values = compare.numbers(sim, sim_trace, ref,
                             int(cell.traffic["requests_per_call"]))
    print(f"reference call={sample} seconds={time.perf_counter() - t_ref}",
          file=sys.stderr, flush=True)
    checked = compare.checks(values, cell.limits)
    result["correct"] = compare.passed(checked)
    result["failed"] = 0 if result["correct"] else 1
    result["checks"] = checked
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    try:
        devices = require_chip(cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T0,
                      devices)
    print(f"run seconds={time.perf_counter() - T0}", file=sys.stderr,
          flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
