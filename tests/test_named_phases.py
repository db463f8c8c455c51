"""Every phase of the streamed engine's scan body carries a named scope.

The chip benchmark attributes device time to the scan body's phases by the
``jax.named_scope`` path in each operation's ``op_name`` metadata; work that
enters the body outside every scope is time no metric can name. This test
lowers the streamed engine at a small size, with the benchmark cells'
settings (Redynis, telemetry on, the jax replay path), and reads the
lowered HLO: each computing instruction of the scan body must carry one of
the program's scope names, and the access log and the daemon sweep must
lie inside the policy step, whose metric sums the two.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro.kvsim import RedynisPolicy, TelemetryConfig, wan5_cluster, wan5_workload
from repro.kvsim.simulate import ShardSpec, _prepare, _simulate_jit
from repro.kvsim.telemetry import normalize_telemetry

# The scopes the scan body opens (kvsim/simulate.py), nested ones included.
SCOPES = frozenset({
    "trace_window", "fault_prepass", "routing_prepass", "contention_prepass",
    "attribution_components", "chunk_replay", "replay_fold",
    "fault_counters", "occupancy", "policy_step", "access_log",
    "daemon_sweep", "repair_accounting", "telemetry_fold",
    "attribution_fold", "flight_recorder",
})
# The phases the benchmark cells run, each of which must hold work.
RUN_BY_CELLS = ("trace_window", "chunk_replay", "replay_fold", "occupancy",
                "policy_step", "access_log", "daemon_sweep", "telemetry_fold")
NESTED = {"access_log": "policy_step", "daemon_sweep": "policy_step"}
# Instructions that compute nothing: arguments, literals and tuple plumbing.
INERT = {"parameter", "constant", "tuple", "get-tuple-element"}

HEADER = re.compile(r"^(ENTRY )?([\w.\-]+) \{$")
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?([\w.\-]+) = (.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side, past its shape (a
    tuple shape is parenthesised and may hold spaces)."""
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rhs[i + 1:].lstrip()
    else:
        rest = rhs.split(" ", 1)[1]
    return rest.split("(", 1)[0]


def _computations(text: str):
    """``({name: [(instruction, opcode, rhs, op_name)]}, entry name)`` of an
    HLO module's text."""
    comps, entry, current = {}, None, None
    for line in text.splitlines():
        head = HEADER.match(line)
        if head:
            current = comps.setdefault(head.group(2), [])
            if head.group(1):
                entry = head.group(2)
            continue
        ins = INSTRUCTION.match(line)
        if ins and current is not None:
            name = OP_NAME.search(ins.group(2))
            current.append((ins.group(1), _opcode(ins.group(2)),
                            ins.group(2), name.group(1) if name else ""))
    return comps, entry


def _callee(rhs: str, attr: str) -> str:
    return re.search(attr + r"=%?([\w.\-]+)", rhs).group(1)


@pytest.fixture(scope="module")
def lowered_hlo():
    wl = wan5_workload(
        num_keys=1_000, num_requests=10_000, read_fraction=0.95,
        object_bytes=1024.0,
    )
    cluster = wan5_cluster()
    static, params = _prepare(wl, cluster, "named-phases", RedynisPolicy())
    shape = lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype)
    lowered = _simulate_jit().lower(
        None, None, None,
        jax.ShapeDtypeStruct((wl.num_keys,), jnp.int32),
        jax.ShapeDtypeStruct((wl.num_keys,), jnp.float32),
        jax.tree_util.tree_map(shape, params),
        jax.ShapeDtypeStruct((), jnp.int32),
        cluster=cluster, policy=static, daemon_interval=1_000,
        telemetry=normalize_telemetry(
            TelemetryConfig(num_bins=128, lo_ms=1.0, hi_ms=10_000.0)
        ),
        replay_backend="jax", trace_mode="streamed", workload=wl,
        shard=ShardSpec(),
    )
    return lowered.as_text(dialect="hlo", debug_info=True)


def _scan_body(comps, entry):
    """The scan's body function: the one computation that the body of the
    entry's ``while`` calls."""
    (loop,) = [rhs for _, op, rhs, _ in comps[entry] if op == "while"]
    (call,) = [rhs for _, op, rhs, _ in comps[_callee(loop, "body")]
               if op == "call"]
    return comps[_callee(call, "to_apply")]


def test_every_computing_instruction_of_the_scan_body_is_named(lowered_hlo):
    comps, entry = _computations(lowered_hlo)
    body = _scan_body(comps, entry)
    kinds = {op for _, op, _, _ in body}
    by_name = {name: op for name, op, _, _ in body}
    unnamed = [
        (name, op, path) for name, op, rhs, path in body
        if op not in INERT
        # A splat literal lowers to a broadcast of a scalar constant.
        and not (op == "broadcast"
                 and by_name.get(re.search(r"\(([\w.\-]+)\)", rhs).group(1))
                 == "constant")
        and not SCOPES.intersection(path.split("/"))
    ]
    assert len(body) > 100 and {"call", "scatter", "reduce"} <= kinds
    assert unnamed == []
    seen = {part for _, _, _, path in body for part in path.split("/")}
    assert set(RUN_BY_CELLS) <= seen


def test_access_log_and_daemon_sweep_lie_inside_the_policy_step(lowered_hlo):
    comps, _ = _computations(lowered_hlo)
    nested = [path for ins in comps.values() for _, _, _, path in ins
              if set(NESTED).intersection(path.split("/"))]
    assert {p for p in nested if "access_log" in p.split("/")}
    assert {p for p in nested if "daemon_sweep" in p.split("/")}
    for path in nested:
        parts = path.split("/")
        for scope, parent in NESTED.items():
            if scope in parts:
                assert parent in parts[:parts.index(scope)], path


def test_the_compilation_cache_keys_on_the_scope_names():
    """A program compiled from a tree with other scopes compiles to the
    same instructions; loaded from a shared persistent cache in place of
    this one, its profile would carry that tree's names."""
    assert jax.config.jax_compilation_cache_include_metadata_in_key
