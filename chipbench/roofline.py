"""Least bytes each layer must move, and the chip's peaks.

The counts are taken from the unpadded data the algorithms touch, whatever
implements them, so a layout that pads or re-reads shows as a low share of
the roofline. Both layers are bound by memory traffic (they do no
arithmetic worth counting against the chip's FLOP/s), so a share is the
least bytes over the peak HBM bandwidth, divided by the measured time.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def policy_step_bytes(num_keys: int, num_nodes: int, sweeps: int,
                      requests: int) -> int:
    """Algorithm 1's access log and Algorithm 3's sweeps.

    Per request, the read-modify-write of one int32 access count (8 bytes).
    Per sweep, a pass over the store: the ``[K, N]`` int32 counts read
    (4 bytes each), the ``[K, N]`` replica map read and the new map written
    (1 byte each), and the ``[K]`` liveness flags read (1 byte each)."""
    return requests * 8 + sweeps * (num_keys * num_nodes * 6 + num_keys)


def chunk_replay_bytes(num_nodes: int, requests: int) -> int:
    """Algorithms 1-2 per request: the request's key, node and operation
    (4 + 4 + 1 bytes) and the key's replica row (one byte per node)."""
    return requests * (9 + num_nodes)


def peaks(device_kind: str) -> dict:
    """The peak row for ``device_kind``; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; have {sorted(table['devices'])}")
    return table["devices"][device_kind]


def share(least_bytes: float, seconds: float, device_kind: str) -> float:
    """Percent of the roofline: least time at peak bandwidth over time."""
    return 100.0 * least_bytes / peaks(device_kind)["hbm_bytes_per_s"] / seconds
