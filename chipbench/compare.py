"""The comparison that decides ``correct``: one call of the program against
the plain reference over the same seed.

Three numbers, each held to the cell's limit (``limits/<cell>.json``):

``counts_off``
    How many of the quantities that are exact integers differ: per chunk
    the read hit rate (a ratio of two exact counts), the requests replayed,
    the replicas the sweep added and dropped, expiry and memory evictions,
    and the call's move and eviction totals.
``hist_off``
    Requests counted in another latency bin, summed over the grouped
    whole-call histogram (node x read/write) and the per-chunk histograms.
``value_gap``
    The widest relative gap of a float result: the call's mean latency,
    hit rate, throughput, busy time and peak replica bytes per node, and
    per chunk the mean latency and the replica bytes per node. The program
    sums in float32; the reference sums chunks in float64.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

NUMBERS = ("counts_off", "hist_off", "value_gap")


def _gap(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    scale = np.maximum(np.abs(b), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(a - b) / scale))


def _off(a, b) -> int:
    return int(np.sum(np.asarray(a, np.float64) != np.asarray(b, np.float64)))


# Fields of run_scenario's (SimResult, SimTrace) that the comparison reads.
EXACT_RESULT = ("replication_moves", "deletion_moves", "evictions",
                "capacity_evictions")
EXACT_TRACE = ("hit_rate", "requests", "moves", "drops", "evictions",
               "capacity_evictions")
HISTOGRAMS = ("hist_group", "chunk_hist")
FLOAT_RESULT = ("mean_latency_ms", "hit_rate", "throughput_ops_s",
                "node_busy_ms", "peak_occupancy_bytes")
FLOAT_TRACE = ("mean_latency_ms", "occupancy_bytes")


def reference_as_program(ref: dict, num_requests: int):
    """``reference.simulate``'s output shaped as the program's
    ``(result, trace)``: the call's aggregates in float64 from per-chunk
    sums, per-chunk series, and the histograms."""
    reads = ref["reads"]
    busy = ref["busy"].sum(axis=0)
    result = SimpleNamespace(
        mean_latency_ms=ref["lat_sum"].sum() / num_requests,
        hit_rate=ref["hits"].sum() / max(reads.sum(), 1.0),
        throughput_ops_s=num_requests / (busy.max() / 1000.0),
        node_busy_ms=busy,
        peak_occupancy_bytes=ref["replica_bytes"].max(axis=0),
        replication_moves=ref["adds"].sum(),
        deletion_moves=ref["drops"].sum(),
        evictions=0.0,
        capacity_evictions=ref["evicted"].sum(),
    )
    trace = SimpleNamespace(
        hit_rate=ref["hits"] / np.maximum(reads, 1.0),
        requests=ref["count"],
        moves=ref["adds"],
        drops=ref["drops"],
        evictions=np.zeros_like(ref["adds"]),
        capacity_evictions=ref["evicted"],
        hist_group=ref["hist"].sum(axis=0),
        chunk_hist=ref["hist"].sum(axis=1),
        mean_latency_ms=ref["lat_sum"] / np.maximum(ref["count"], 1.0),
        occupancy_bytes=ref["replica_bytes"],
    )
    return result, trace


def numbers(result, trace, ref: dict, num_requests: int) -> dict:
    """The compared numbers for a call ``(result, trace)``, as
    ``run_scenario`` returns it, against ``reference.simulate``'s output."""
    want, want_trace = reference_as_program(ref, num_requests)
    pairs = lambda fields, a, b: ((getattr(a, f), getattr(b, f))
                                  for f in fields)
    counts_off = sum(
        _off(x, y) for x, y in (*pairs(EXACT_RESULT, result, want),
                                *pairs(EXACT_TRACE, trace, want_trace))
    )
    hist_off = sum(
        int(np.abs(np.asarray(x) - y).sum())
        for x, y in pairs(HISTOGRAMS, trace, want_trace)
    )
    value_gap = max(
        _gap(x, y) for x, y in (*pairs(FLOAT_RESULT, result, want),
                                *pairs(FLOAT_TRACE, trace, want_trace))
    )
    return {"counts_off": counts_off, "hist_off": hist_off,
            "value_gap": value_gap}


def checks(values: dict, limits: dict) -> dict:
    """Each number beside its limit, in the order of ``NUMBERS``."""
    return {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS}


def passed(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())
