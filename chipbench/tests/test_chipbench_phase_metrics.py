"""The readers of the scan body's named phases: on hand-made operations,
against a hand count, on a trace recorded before the phases were named,
and on a small trace recorded on a TPU v5e with them (two ``run_scenario``
calls of the ``wan5_10m`` deployment cut to 10^5 keys, 10^6 requests a
call, one sweep per 10^5, each call in a ``chipbench.call`` span).

The ``telemetry_fold`` scope has no reader: the histogram fold's fusion
carries no ``op_name`` and inherits the path of the scan's stacking, so the
scope holds only the fold's input conversion."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import roofline, run, spec  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
BODY = "jit(_simulate)/while/body/closed_call/"
# Each new reader and the scope path it reads.
PHASES = {
    "access_log_ms": "policy_step/access_log",
    "daemon_sweep_ms": "policy_step/daemon_sweep",
    "trace_window_ms": "trace_window",
    "replay_fold_ms": "replay_fold",
    "occupancy_ms": "occupancy",
}
NEW = tuple(PHASES) + ("daemon_sweep_roofline",)
UNSCOPED_PHASES = ("trace_window_ms", "replay_fold_ms", "occupancy_ms")


def _read(name, ops, requests=10**6, sweeps=10, num_keys=10**5, chips=1):
    reduced = tr.Reduced(chips=chips, window_ns=1e9, busy_ns=1e9, ops=ops,
                         device_ops=[], idle_gaps=[])
    return spec.load_reader(name)(run.Context(
        reduced=reduced, requests=requests, sweeps=sweeps, num_keys=num_keys,
        num_nodes=5, device_kind="TPU v5 lite", chips=chips,
    ))


def _hand_made():
    """One operation under each phase, each with its own duration (ms),
    beside operations under the older scopes, under ``telemetry_fold`` and
    under none."""
    ops = [tr.Op(f"fusion.{i}", 1e6 * i, 1e6 * 2**i, BODY + path + "/add")
           for i, path in enumerate(PHASES.values())]
    return ops + [
        tr.Op("fusion.90", 0, 3e6, BODY + "chunk_replay/jit(chunk_latency)/min"),
        tr.Op("rng.91", 0, 5e6, BODY + "iota"),
        tr.Op("scatter.93", 0, 11e6, BODY + "telemetry_fold/convert"),
        tr.Op("all-reduce.92", 0, 7e6, BODY + "occupancy/psum"),
    ]


@pytest.mark.parametrize("name", PHASES)
def test_each_reader_selects_its_own_scope(name):
    i = list(PHASES).index(name)
    # 10^6 requests: milliseconds per 10^6 requests are the phase's own.
    assert _read(name, _hand_made()) == pytest.approx(2**i)
    others = [op for op in _hand_made() if op.name != f"fusion.{i}"]
    assert _read(name, others) is None


def test_access_log_and_daemon_sweep_sum_to_the_policy_step():
    ops = _hand_made()
    parts = _read("access_log_ms", ops) + _read("daemon_sweep_ms", ops)
    assert parts == pytest.approx(_read("policy_step_ms", ops))
    # The new top-level phases are still unscoped to the older reader.
    phases = sum(_read(m, ops) for m in UNSCOPED_PHASES)
    assert _read("unscoped_ms", ops) == pytest.approx(phases + 5 + 11)


def test_daemon_sweep_roofline_by_hand():
    # 10^7 keys x 5 nodes, 100 sweeps, no request counted: each sweep reads
    # 5 * 10^7 int32 counts (2 * 10^8 B), reads and writes 5 * 10^7 map
    # flags (10^8 B) and reads 10^7 liveness flags (10^7 B) = 3.1 * 10^8 B.
    least = 100 * 3.1e8
    assert roofline.policy_step_bytes(10**7, 5, 100, 0) == least
    ops = [tr.Op("fusion.1", 0, 2e9, BODY + "policy_step/daemon_sweep/and"),
           tr.Op("fusion.2", 0, 9e9, BODY + "policy_step/access_log/add")]
    share = _read("daemon_sweep_roofline", ops, requests=10**8, sweeps=100,
                  num_keys=10**7)
    assert share == pytest.approx(100 * least / 819e9 / 2.0)
    # On four chips each holds a quarter of the sweep's bytes.
    four = _read("daemon_sweep_roofline", ops * 4, requests=10**8,
                 sweeps=100, num_keys=10**7, chips=4)
    assert four == pytest.approx(share / 4)


def _chip_stretch(name):
    devices, host = tr.read_xplane(os.path.join(DATA, name))
    calls = sorted(s.start_ns for s in host if s.name == "chipbench.call")
    return tr.reduce(devices, host, calls[0], calls[1])


def test_readers_read_nothing_where_the_program_has_no_such_scope():
    """A program that opens none of the new scopes (the trace of
    ``test_chipbench_trace_reduce``) leaves every new metric out."""
    ops = _chip_stretch("v5e_two_calls.xplane.pb").ops
    assert _read("policy_step_ms", ops) is not None
    assert {name: _read(name, ops) for name in NEW} == dict.fromkeys(NEW)


def test_chip_trace_every_phase_holds_device_time():
    reduced = _chip_stretch("v5e_phases.xplane.pb")
    ops = reduced.ops
    values = {name: _read(name, ops) for name in NEW}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert 0 < values["daemon_sweep_roofline"] < 100
    parts = values["access_log_ms"] + values["daemon_sweep_ms"]
    assert parts == pytest.approx(_read("policy_step_ms", ops), rel=1e-9)
    phases = sum(values[m] for m in UNSCOPED_PHASES)
    assert phases <= _read("unscoped_ms", ops) * (1 + 1e-9)
    # The program opens telemetry_fold too; no metric reads it yet.
    assert reduced.time_ns(tr.under("telemetry_fold")) > 0
