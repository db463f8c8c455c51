"""The trace reduction: on a hand-made stretch, and on a small trace
recorded on a TPU v5e (two ``run_scenario`` calls at 4,096 keys, each in a
``chipbench.call`` span)."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import trace_reduce as tr  # noqa: E402

CHIP_TRACE = os.path.join(os.path.dirname(__file__), "data",
                          "v5e_two_calls.xplane.pb")

SWEEP = "jit(_simulate)/while/body/policy_step/add"
REPLAY = "jit(_simulate)/while/body/chunk_replay/jit(chunk_latency)/min"


def test_hand_made_stretch():
    chip = [
        tr.Op("fusion.1", 100, 50, SWEEP),
        tr.Op("fusion.2", 140, 40, REPLAY),  # overlaps the first
        tr.Op("all-reduce.3", 300, 10, ""),
        tr.Op("rng.4", 400, 100, "jit(_simulate)/while/body"),
        tr.Op("fusion.5", 1_000_000, 10, SWEEP),  # next call: ends stretch
    ]
    host = [tr.HostSpan("chipbench.call", 0, 2_000_000),
            tr.HostSpan("build_trace", 550, 600_000)]
    r = tr.reduce([chip], host, start_ns=50, end_ns=900_000)
    assert r.window_ns == 1_000_000 - 100
    assert r.busy_ns == 80 + 10 + 100
    assert r.time_ns(tr.under("policy_step")) == 50
    assert r.time_ns(tr.under("chunk_replay")) == 40
    assert r.time_ns(tr.unscoped) == 100
    assert r.time_ns(tr.is_collective) == 10
    gaps = dict(r.idle_gaps)
    assert gaps["build_trace"] == pytest.approx((1_000_000 - 500) / 1e9)
    assert gaps["between operations"] == pytest.approx((120 + 90) / 1e9)
    assert r.device_ops[0][0] == "unscoped:rng.4"


def test_ops_without_metadata_take_a_neighbours_path():
    """XLA's own instructions carry no ``op_name``: a relayout copy counts
    under the instruction that consumes it, else under the one that
    produces its operand, through other instructions without one."""
    paths = tr.inherited_paths([
        (1, "fusion.9", REPLAY, []),
        (2, "copy.76", "", [1]),
        (3, "reshape.255", SWEEP, [2]),
        (4, "copy-start.1", "", [1]),
        (5, "copy-done.1", "", [4]),
        (6, "fusion.3", SWEEP, [5]),
        (7, "get-tuple-element.7", "", []),
        (8, "copy.2", "", [7]),
        (9, "copy.5", "", [1]),
    ])
    assert paths == {
        "copy.76": SWEEP,  # its user, reshape.255
        "copy-start.1": SWEEP,  # via copy-done.1 to fusion.3
        "copy-done.1": SWEEP,
        "get-tuple-element.7": "",  # no neighbour with a path
        "copy.2": "",
        "copy.5": REPLAY,  # no user: its operand, fusion.9
    }
    # The profiler may give such an operation a path of its own (an
    # enclosing while's); the instruction's inherited path replaces it.
    ops = [tr.Op("copy.76", 10, 1, "jit(_simulate)/while"),
           tr.Op("copy.76", 110, 1, "jit(other)/while"),
           tr.Op("reshape.255", 12, 1, SWEEP)]
    out = tr._with_inherited(ops, [(0, 50, "jit__simulate(1)"),
                                   (100, 150, "jit_other(2)")],
                             {"jit__simulate(1)": paths})
    assert [op.path for op in out] == [SWEEP, "jit(other)/while", SWEEP]


@pytest.mark.skipif(not os.path.exists(CHIP_TRACE),
                    reason="no chip trace committed")
def test_chip_trace():
    devices, host = tr.read_xplane(CHIP_TRACE)
    calls = sorted(s.start_ns for s in host if s.name == "chipbench.call")
    assert len(devices) == 1 and len(calls) == 2
    r = tr.reduce(devices, host, calls[0], calls[1])
    assert 0 < r.busy_ns <= r.window_ns
    sweep = r.time_ns(tr.under("policy_step"))
    replay = r.time_ns(tr.under("chunk_replay"))
    rest = r.time_ns(tr.unscoped)
    assert sweep > 0 and replay > 0 and rest > 0
    assert r.time_ns(tr.is_collective) == 0
    # Scopes partition the device time: busy never exceeds their sum.
    assert r.busy_ns <= sweep + replay + rest + 1
    assert 0 < len(r.device_ops) <= 10 and 0 < len(r.idle_gaps) <= 10
    # The trace carries each program's HLO: its instructions without
    # metadata (async copies the profiler files under the scan's while)
    # take their neighbours' paths.
    xspace, hlo = tr._proto_classes()
    space = xspace()
    with open(CHIP_TRACE, "rb") as f:
        space.ParseFromString(f.read())
    programs = tr._module_paths(space, hlo)
    paths = next(v for k, v in programs.items()
                 if k.startswith("jit__simulate"))
    elsewhere = {n for k, v in programs.items() if v is not paths for n in v}
    moved = [op for op in devices[0]
             if op.name in paths and op.name not in elsewhere]
    assert moved and all(op.path == paths[op.name] for op in moved)
    assert any(op.path for op in moved)
