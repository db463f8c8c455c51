"""Readings that the correctness limits are set from (not part of a run).

    python3 chipbench/readings.py --workload <cell> --seeds 1 2 ... --control-seeds 1 2 3

In one process, at the cell's own size: for each seed (drawn into the
program's int32 seed as a run draws its calls' seeds), one program call
against the plain reference (the lower readings: what sound runs give),
and for each control seed, the control (the reference serving every chunk
from the map one sweep older) against the reference (the upper readings).
The control needs no program, so with control seeds alone it runs on one
chip whatever the cell asks for. Each reading is one JSON line; the last
line holds, per number, the largest sound reading and the smallest control
reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import compare, program, reference, run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=())
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    try:
        run.require_chip(cell.chips if args.seeds else 1)
    except run.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    dep = reference.deployment(cell.config, cell.traffic)
    r = dep.num_requests
    prog = program.Program(cell.config, cell.traffic) if args.seeds else None
    sound, control, refs = [], [], {}
    seed_of = lambda seed: run.call_seed(seed, "reading")
    for seed in args.seeds:
        t = time.perf_counter()
        res, trace = prog.call(seed_of(seed))
        t_call = time.perf_counter() - t
        refs[seed] = reference.simulate(dep, seed_of(seed))
        values = compare.numbers(res, trace, refs[seed], r)
        sound.append(values)
        print(json.dumps({"kind": "program", "seed": seed, "call_s": t_call,
                          **values}), flush=True)
    for seed in args.control_seeds:
        ref = refs.get(seed) or reference.simulate(dep, seed_of(seed))
        ctl = reference.simulate(dep, seed_of(seed), control=True)
        values = compare.numbers(*compare.reference_as_program(ctl, r), ref, r)
        control.append(values)
        print(json.dumps({"kind": "control", "seed": seed, **values}),
              flush=True)
    print(json.dumps({
        "workload": cell.name,
        "lower": {k: max(v[k] for v in sound) for k in compare.NUMBERS}
        if sound else None,
        "upper": {k: min(v[k] for v in control) for k in compare.NUMBERS}
        if control else None,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
