"""Digest of each chip benchmark cell's compiled program, names left out.

    python3 benchmarks/hlo_digest.py [--described] [--root DIR] [--out DIR] [CELL ...]

Compiles the program of each one-chip cell of ``BENCHMARK.json`` (default:
all of them) from the tree at ``--root`` (default: this checkout) for one
TPU v5e: the attached chip, or with ``--described`` a described ``v5e:2x2``
topology and no chip, as ``tests/test_tpu_compile.py`` does. The persistent
compile cache is left off, so each program is compiled here. Prints one line
a cell, ``<cell> sha256=<hex> bytes=<n>``: the digest and length of the
optimised HLO text with every ``metadata={...}`` and the module's
stack-frame tables (file names, function names, source lines) taken out.
Two trees whose lines agree compile to the same program; they differ at
most in the scope names and source lines a profile shows. ``--out`` also
writes each stripped text to ``<DIR>/<cell>.hlo``, for a diff.
"""

from __future__ import annotations

import argparse
import hashlib
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# A metadata attribute; its quoted strings may hold any character.
METADATA = re.compile(r', metadata=\{(?:[^}"]|"[^"]*")*\}')


def strip(text: str) -> str:
    """The HLO module's text without what only names and locates its
    instructions: each ``metadata={...}``, and the stack-frame tables that
    lie between the ``HloModule`` line and the first computation."""
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    return METADATA.sub("", "\n".join(lines[:1] + lines[first:]))


def compiled_text(prog, sharding) -> str:
    """The optimised HLO of ``prog``'s scan engine (``chipbench.program``),
    lowered from argument shapes alone as ``run_scenario`` calls it."""
    import jax
    import jax.numpy as jnp

    from repro.kvsim.simulate import ShardSpec, _prepare, _simulate_jit
    from repro.kvsim.telemetry import normalize_telemetry

    wl = prog.workload
    static, params = _prepare(wl, prog.cluster, "hlo_digest", prog.policy)

    def shape(x):
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                    sharding=sharding)

    lowered = _simulate_jit().lower(
        None, None, None,
        jax.ShapeDtypeStruct((wl.num_keys,), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((wl.num_keys,), jnp.float32, sharding=sharding),
        jax.tree_util.tree_map(shape, params),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding),
        cluster=prog.cluster, policy=static, daemon_interval=prog.interval,
        telemetry=normalize_telemetry(prog.telemetry),
        workload=wl, shard=ShardSpec(), **prog.engine,
    )
    return lowered.compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*",
                    help="cells of BENCHMARK.json (default: every one-chip cell)")
    ap.add_argument("--root", default=ROOT,
                    help="checkout whose program is compiled")
    ap.add_argument("--described", action="store_true",
                    help="compile for a described v5e, with no chip")
    ap.add_argument("--out", help="directory for the stripped texts")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path[:0] = [os.path.join(root, "src"), root]

    import jax
    from jax.sharding import SingleDeviceSharding

    from chipbench import program, spec

    jax.config.update("jax_enable_compilation_cache", False)
    if args.described:
        from jax.experimental import topologies

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    else:
        device = jax.devices()[0]
        if device.platform != "tpu":
            print(f"no TPU attached ({device.platform}); pass --described",
                  file=sys.stderr)
            return 1
    bench = spec.load_benchmark(root)
    names = args.cells or [w["name"] for w in bench["workloads"]
                           if int(w["chips"]) == 1]
    for name in names:
        cell = spec.resolve(bench, name, root)
        if cell.chips != 1 or int(cell.config["num_shards"]) != 1:
            print(f"{name}: only one-chip cells are compiled", file=sys.stderr)
            return 1
        text = strip(compiled_text(program.Program(cell.config, cell.traffic),
                                   SingleDeviceSharding(device)))
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, name + ".hlo"), "w") as f:
                f.write(text)
        digest = hashlib.sha256(text.encode()).hexdigest()
        print(f"{name} sha256={digest} bytes={len(text)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
