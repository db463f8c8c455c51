"""Compile rehearsal for the TPU v5e: the main-path Pallas kernels and the
streamed engine, compiled for a described (not attached) v5e chip.

Nothing here runs on a chip. Each test lowers with ``interpret=False`` and
compiles with the TPU compiler against a ``v5e:2x2`` topology description,
which refuses what a chip would refuse — tiling, VMEM, alignment, device
memory — at no chip time. The topology is described inside a fixture and
never at import, so every pytest-xdist worker collects the same tests and
only the worker that runs this file loads the TPU library. Keep these
compiles in this one file: a second file could land on another worker,
whose fixture would then skip its tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunk_replay.kernel import chunk_replay_call
from repro.kernels.latency_histogram.kernel import latency_histogram_call
from repro.kernels.ownership_sweep.kernel import ownership_sweep_call
from repro.kvsim import RedynisPolicy, TelemetryConfig, wan5_cluster, wan5_workload
from repro.kvsim.simulate import ShardSpec, _prepare, _simulate_jit

# Kernel widths of the deployment: 5 wan5 nodes, 128 histogram bins, a
# 2**20-key replica map and a 2**16-request chunk.
K, B, N, BINS = 1 << 20, 1 << 16, 5, 128
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent cache
    # but never read back without one; keep these out of it.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    mp.undo()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("num_bins", [0, BINS])
def test_chunk_replay_compiles_for_v5e(one_chip, num_bins):
    compiled = _compile(
        one_chip,
        lambda hosts, keys, nodes, is_read, valid, rtt: chunk_replay_call(
            hosts, keys, nodes, is_read, valid, rtt, service_ms=10.0,
            xfer_read_ms=1.0, xfer_write_ms=1.0, lo=1.0, hi=1e4, master=0,
            read_mode="map", num_bins=num_bins, interpret=False,
        ),
        ((K, N), jnp.float32), ((B,), jnp.int32), ((B,), jnp.int32),
        ((B,), jnp.int32), ((B,), jnp.int32), ((N, N), jnp.float32),
    )
    _assert_kernel(compiled)


def test_ownership_sweep_compiles_for_v5e(one_chip):
    compiled = _compile(
        one_chip,
        lambda counts, hosts, live, last, now, h: ownership_sweep_call(
            counts, hosts, live, last, now, h=h, expiry=8, interpret=False,
        ),
        ((K, N), jnp.float32), ((K, N), jnp.int8), ((K,), jnp.int8),
        ((K,), jnp.int32), ((), jnp.int32), ((), jnp.float32),
    )
    _assert_kernel(compiled)


def test_latency_histogram_compiles_for_v5e(one_chip):
    compiled = _compile(
        one_chip,
        lambda lat, group, w: latency_histogram_call(
            lat, group, w, num_groups=2 * N, num_bins=BINS, lo=1.0, hi=1e4,
            interpret=False,
        ),
        ((B,), jnp.float32), ((B,), jnp.int32), ((B,), jnp.float32),
    )
    _assert_kernel(compiled)


def test_streamed_engine_at_ten_million_keys_fits_one_v5e(one_chip):
    """The deployment chip_smoke.py runs: wan5, 10**7 keys, 10**8 requests
    streamed with one sweep per 10**6, Redynis, telemetry on."""
    wl = wan5_workload(
        num_keys=10_000_000, num_requests=100_000_000, read_fraction=0.95,
        object_bytes=1024.0,
    )
    cluster = wan5_cluster()
    static, params = _prepare(wl, cluster, "rehearsal", RedynisPolicy())
    on_chip = lambda x: jax.ShapeDtypeStruct(
        jnp.shape(x), jnp.asarray(x).dtype, sharding=one_chip
    )
    compiled = _simulate_jit().lower(
        None, None, None,
        jax.ShapeDtypeStruct((wl.num_keys,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((wl.num_keys,), jnp.float32, sharding=one_chip),
        jax.tree_util.tree_map(on_chip, params),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        cluster=cluster, policy=static, daemon_interval=1_000_000,
        telemetry=TelemetryConfig(), replay_backend="jax",
        trace_mode="streamed", workload=wl, shard=ShardSpec(),
    ).compile()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert total < V5E_HBM_BYTES, mem
