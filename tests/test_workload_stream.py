"""Streamed trace generation ⇄ materialised trace equivalence.

``generate_trace_chunk`` must be BIT-identical to slicing the materialised
``generate_trace`` output — same ``fold_in`` stream, every workload knob,
chunk sizes that do and don't divide ``num_requests``. This is the contract
that lets ``trace_mode="streamed"`` reuse the seed goldens unchanged: if any
draw shifts by one counter position the engine equivalence tests downstream
all fail, so this file is the first place to look.

Positions ``>= num_requests`` are explicitly unspecified (the engine masks
them), so every comparison here clips the final partial chunk to ``R``.
"""

import numpy as np
import pytest

from repro.kvsim import WorkloadConfig, diurnal_workload, wan5_workload
from repro.kvsim.workload import (
    generate_key_state,
    generate_trace,
    generate_trace_chunk,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


# Every preset family named in the issue: uniform, region-skewed (wan5),
# diurnal rotation, lognormal sizes — plus affinity + read mix stressors.
PRESETS = {
    "uniform": WorkloadConfig(num_requests=777, num_keys=64),
    "skewed": WorkloadConfig(
        num_requests=777, num_keys=64, skewed=True, read_fraction=0.7
    ),
    "wan5": wan5_workload(num_requests=777, num_keys=64, affinity=0.8),
    "diurnal": diurnal_workload(num_requests=777, num_keys=64, affinity=0.8),
    "lognormal": wan5_workload(
        num_requests=777,
        num_keys=64,
        affinity=0.8,
        object_bytes_sigma=0.5,
        read_fraction=0.6,
    ),
    # Cold-key span 180_000 > 2**16: jax's randint multiplier wraps there.
    "large_keyspace": wan5_workload(
        num_requests=777, num_keys=200_000, affinity=0.8
    ),
}


def _concat_chunks(cfg, seed, chunk_size):
    """Concatenate streamed chunks, clipped to num_requests."""
    num_chunks = -(-cfg.num_requests // chunk_size)
    ks, ns, rs = [], [], []
    for c in range(num_chunks):
        ch = generate_trace_chunk(cfg, seed, c, chunk_size)
        ks.append(np.asarray(ch.keys))
        ns.append(np.asarray(ch.nodes))
        rs.append(np.asarray(ch.is_read))
    r = cfg.num_requests
    return (
        np.concatenate(ks)[:r],
        np.concatenate(ns)[:r],
        np.concatenate(rs)[:r],
    )


@pytest.mark.parametrize("name", sorted(PRESETS))
# 777 = 3 * 7 * 37: 100 and 256 leave partial final chunks, 111 divides.
@pytest.mark.parametrize("chunk_size", [100, 111, 256])
def test_chunked_equals_materialized(name, chunk_size):
    cfg = PRESETS[name]
    trace = generate_trace(cfg, seed=5)
    keys, nodes, is_read = _concat_chunks(cfg, 5, chunk_size)
    np.testing.assert_array_equal(keys, np.asarray(trace.keys))
    np.testing.assert_array_equal(nodes, np.asarray(trace.nodes))
    np.testing.assert_array_equal(is_read, np.asarray(trace.is_read))


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_key_state_equals_materialized(name):
    """natural_node and object_bytes from the O(K) generator match the
    fields inside the full trace bit-for-bit (same fold_in draws)."""
    cfg = PRESETS[name]
    trace = generate_trace(cfg, seed=5)
    natural, obj = generate_key_state(cfg, seed=5)
    np.testing.assert_array_equal(
        np.asarray(natural), np.asarray(trace.natural_node)
    )
    np.testing.assert_array_equal(
        np.asarray(obj).view(np.uint32),
        np.asarray(trace.object_bytes).view(np.uint32),
    )


def test_single_chunk_is_whole_trace():
    """chunk_size == num_requests: one window IS the trace."""
    cfg = PRESETS["wan5"]
    trace = generate_trace(cfg, seed=9)
    ch = generate_trace_chunk(cfg, 9, 0, cfg.num_requests)
    np.testing.assert_array_equal(np.asarray(ch.keys), np.asarray(trace.keys))
    np.testing.assert_array_equal(
        np.asarray(ch.nodes), np.asarray(trace.nodes)
    )
    np.testing.assert_array_equal(
        np.asarray(ch.is_read), np.asarray(trace.is_read)
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        name=st.sampled_from(sorted(PRESETS)),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
        # Odd sizes rarely divide 777 — the partial-final-chunk case
        # dominates, which is exactly the boundary worth fuzzing.
        chunk_size=st.integers(min_value=1, max_value=900),
    )
    def test_stream_equivalence_property(name, seed, chunk_size):
        cfg = PRESETS[name]
        trace = generate_trace(cfg, seed=seed)
        keys, nodes, is_read = _concat_chunks(cfg, seed, chunk_size)
        np.testing.assert_array_equal(keys, np.asarray(trace.keys))
        np.testing.assert_array_equal(nodes, np.asarray(trace.nodes))
        np.testing.assert_array_equal(is_read, np.asarray(trace.is_read))
