"""The least-bytes counts behind the roofline shares, against hand counts,
and the peaks table."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import roofline  # noqa: E402


def test_policy_step_bytes_by_hand():
    # 3 keys x 2 nodes, 4 sweeps, 10 requests: each sweep reads 6 int32
    # counts (24 B), reads 6 map flags and writes 6 (12 B), reads 3
    # liveness flags (3 B) = 39 B; each request updates one count (8 B).
    assert roofline.policy_step_bytes(3, 2, 4, 10) == 4 * 39 + 10 * 8


def test_chunk_replay_bytes_by_hand():
    # 7 requests on 5 nodes: key 4 B + node 4 B + op 1 B + row 5 B = 14 B.
    assert roofline.chunk_replay_bytes(5, 7) == 7 * 14


def test_share_at_peak_is_100_percent():
    kind = "TPU v5 lite"
    bw = roofline.peaks(kind)["hbm_bytes_per_s"]
    assert bw == 819e9
    assert roofline.share(bw * 0.5, 0.5, kind) == pytest.approx(100.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_roofline_readers_split_least_bytes_over_chips():
    """On four chips each chip holds a quarter of the work: with the same
    device time per chip, the share is a quarter of one chip's."""
    from chipbench import run, spec
    from chipbench import trace_reduce as tr

    ops = [tr.Op("fusion.1", 0, 1e9, "jit(_simulate)/while/body/policy_step"),
           tr.Op("fusion.2", 0, 1e9, "jit(_simulate)/while/body/chunk_replay")]
    for name in ("policy_step_roofline", "chunk_replay_roofline"):
        shares = []
        for chips in (1, 4):
            reduced = tr.Reduced(chips=chips, window_ns=1e9, busy_ns=1e9,
                                 ops=ops * chips, device_ops=[], idle_gaps=[])
            shares.append(spec.load_reader(name)(run.Context(
                reduced=reduced, requests=10**8, sweeps=100,
                num_keys=4 * 10**7, num_nodes=5, device_kind="TPU v5 lite",
                chips=chips,
            )))
        assert shares[1] == pytest.approx(shares[0] / 4)
