"""Pallas chunk-replay kernel (TPU): the simulator's whole per-chunk
request path fused into ONE pass over request tiles.

The pre-fusion engine materialised ``[B, N]`` HBM intermediates
(``replicas``, ``read_replicas``, owner masks) and walked them in four
separate passes (read path, write path, hit flags, busy scatter) before a
fifth pass folded the telemetry histogram. Here one grid step ingests a
``[TR]`` request tile and never leaves VMEM:

  replica gather  — ``hosts[keys]`` recast as a one-hot matmul the MXU eats
                    natively: ``onehot_k [TR, TKEY] ∙ hosts [TKEY, N]``,
                    accumulated across key tiles in a VMEM scratch (each key
                    lands in exactly one tile, so the sum IS the gather).
  read path       — RTT-row gather (again a one-hot matmul), masked
                    nearest-replica min, orphan worst-RTT guard, and the
                    size-aware remote transfer charge.
  write path      — Algorithm 2 over the RTT row: master relay + the
                    broadcast completing at the farthest owner (a masked
                    max over the owner plane).
  hit flags       — the requesting node's own column of the replica plane.
  busy fold       — per-node latency totals as ``lat [1, TR] ∙ onehot_n
                    [TR, N]`` instead of a VPU-hostile scatter.
  histogram fold  — the telemetry layer's grouped ``[2N, B]`` log-bin
                    histogram (``latency_histogram``'s one-hot matmul),
                    fused in so telemetry-on runs stop paying a separate
                    dispatch over the chunk.

Latency expressions replicate ``ref.chunk_latency_ref`` op-for-op (same
f32 sequence ⇒ same bits ⇒ identical histogram buckets); only the
*reductions* (busy, lat_sum) re-associate across tiles, so those are
allclose-vs-oracle while hit/read/count/histogram stay bit-exact for the
0/1 weights the engine uses — pinned by tests/test_chunk_replay.py.

Scalars (service/transfer charges, histogram bin range) arrive as scalar
*inputs* (the trio convention, like the ownership sweep's H), so jitted
pipelines can retune the latency model without recompiling; ``read_mode``
/ ``master`` / ``num_bins`` / tile sizes stay static.

VMEM budget per step: the two one-hot planes dominate — ``TR·TKEY`` for
the gather (512·1024·4B = 2 MB) + ``TR·(N + G + B)`` for the folds
(≈ 0.6 MB at N ≤ 64, B = 128) + the [TR, N] scratch; comfortably inside
16 MB with room to double-buffer.

Failure injection (PR 10) required NO kernel change: degraded-mode
serving arrives entirely through the operands — the engine hands this
kernel the availability-masked replica map (so the nearest-replica min
only sees LIVE copies), a ``valid`` mask with refused requests already
dropped (weight-0 rows), and the write-failover delta pre-folded into
``extra_ms`` by ``ref.fault_extra_ms_ref``. See ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import compiler_params, interpret_default, pl, vmem_scratch
from repro.kernels.latency_histogram.ref import bin_index

__all__ = ["chunk_replay_kernel", "chunk_replay_call"]

DEFAULT_TR = 512
DEFAULT_TKEY = 1024

# stats lane order in the [1, 4] output block.
STAT_FIELDS = ("lat_sum", "hits", "reads", "count")


def chunk_replay_kernel(
    keys_ref,  # [TR, 1] i32
    nodes_ref,  # [TR, 1] i32
    read_ref,  # [TR, 1] i32 (is_read)
    valid_ref,  # [TR, 1] i32 (0 masks padded rows)
    hosts_ref,  # [TKEY, N] f32 (0/1 replica map tile)
    rtt_ref,  # [N, N] f32
    service_ref,  # [1, 1] f32 — per-op service cost
    xfer_r_ref,  # [1, 1] f32 — remote read transfer charge
    xfer_w_ref,  # [1, 1] f32 — write transfer charge
    lo_ref,  # [1, 1] f32 — lowest interior histogram edge
    hi_ref,  # [1, 1] f32 — histogram overflow threshold
    *refs,  # [extra_ms input], outputs (busy, stats[, hist]), replica scratch
    read_mode: str,
    master: int,
    num_bins: int,
    n: int,
    tr: int,
    tkey: int,
    num_key_tiles: int,
    with_extra: bool = False,
):
    if with_extra:
        # [TR, 1] f32 per-request contention wait (ServiceConfig pre-pass).
        extra_ref, *refs = refs
    else:
        extra_ref = None
    with_hist = num_bins > 0
    if with_hist:
        busy_ref, stats_ref, hist_ref, replicas_ref = refs
    else:
        busy_ref, stats_ref, replicas_ref = refs
        hist_ref = None
    i = pl.program_id(0)  # request tile
    j = pl.program_id(1)  # key tile (inner loop)

    @pl.when((i == 0) & (j == 0))
    def _init():
        busy_ref[...] = jnp.zeros_like(busy_ref)
        stats_ref[...] = jnp.zeros_like(stats_ref)
        if with_hist:
            hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(j == 0)
    def _reset_gather():
        replicas_ref[...] = jnp.zeros_like(replicas_ref)

    # --- 1. replica-row gather as a one-hot matmul, one key tile at a time.
    local = keys_ref[...] - j * tkey  # [TR, 1]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (tr, tkey), 1)
    onehot_k = (iota_k == local).astype(jnp.float32)
    replicas_ref[...] += jax.lax.dot_general(
        onehot_k,
        hosts_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == num_key_tiles - 1)
    def _replay():
        nodes = nodes_ref[...]  # [TR, 1]
        is_read = read_ref[...] != 0
        valid = valid_ref[...] != 0
        service = service_ref[0, 0]
        rtt = rtt_ref[...]

        iota_n = jax.lax.broadcasted_iota(jnp.int32, (tr, n), 1)
        onehot_n = (iota_n == nodes).astype(jnp.float32)

        if read_mode == "ideal":
            # The paper's theoretically-ideal scenario: pure service cost.
            lat = jnp.zeros((tr, 1), jnp.float32) + service
            hit = jnp.ones((tr, 1), dtype=bool)
        else:
            replicas_f = replicas_ref[...]  # [TR, N] exact 0/1
            replicas = replicas_f > 0.5
            # Own-node column of the replica plane (the hit flag).
            own = jnp.sum(
                replicas_f * onehot_n, axis=1, keepdims=True
            )  # exact 0/1
            hit = own > 0.5
            if read_mode == "no_local":
                read_replicas = replicas & (iota_n != nodes)
                hit = jnp.zeros_like(hit)
                has_local = jnp.zeros_like(hit)
            else:
                read_replicas = replicas
                has_local = hit

            # --- 2. read path: nearest visible replica over the RTT row.
            # HIGHEST: a TPU matmul otherwise rounds f32 operands to
            # bf16, and this gather must return the RTTs exactly.
            row = jax.lax.dot_general(
                onehot_n, rtt, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )  # [TR, N] — exact gather (one nonzero term per sum)
            masked = jnp.where(read_replicas, row, jnp.inf)
            nearest = jnp.min(masked, axis=1, keepdims=True)
            nearest = jnp.where(
                jnp.isfinite(nearest), nearest, jnp.max(rtt)
            )
            r_lat = service + nearest + jnp.where(
                has_local, 0.0, xfer_r_ref[0, 0]
            )

            # --- 3. write path: master relay + farthest-owner broadcast.
            owner_count = jnp.sum(replicas_f, axis=1, keepdims=True)
            sole_local = hit & (owner_count == 1.0)
            if read_mode == "no_local":
                sole_local = jnp.zeros_like(sole_local)
            relay = jnp.where(
                nodes == master, 0.0, row[:, master : master + 1]
            )
            non_master = replicas & (iota_n != master)
            post = jnp.max(
                jnp.where(non_master, rtt[master : master + 1, :], 0.0),
                axis=1,
                keepdims=True,
            )
            cost = relay + post
            cost = cost + jnp.where(cost > 0, xfer_w_ref[0, 0], 0.0)
            w_lat = service + jnp.where(sole_local, 0.0, cost)

            lat = jnp.where(is_read, r_lat, w_lat)

        # --- 4/5. hit flags + per-node busy fold (MXU, not a scatter).
        if extra_ref is not None:
            # Same elementwise add, same position as the oracle's, so the
            # histogram bucket of every request stays bit-identical.
            lat = lat + extra_ref[...]
        lat = jnp.where(valid, lat, 0.0)
        read_hits = hit & is_read & valid
        busy_ref[...] += jax.lax.dot_general(
            lat, onehot_n, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,  # full-f32 latencies
            preferred_element_type=jnp.float32,
        )  # [1, N]
        w = valid.astype(jnp.float32)
        stats_ref[...] += jnp.concatenate(
            [
                jnp.sum(lat).reshape(1, 1),
                jnp.sum(read_hits.astype(jnp.float32)).reshape(1, 1),
                jnp.sum((is_read & valid).astype(jnp.float32)).reshape(1, 1),
                jnp.sum(w).reshape(1, 1),
            ],
            axis=1,
        )

        # --- 6. grouped latency-histogram fold (telemetry on only).
        if with_hist:
            idx = bin_index(lat, lo_ref[0, 0], hi_ref[0, 0], num_bins)
            iota_b = jax.lax.broadcasted_iota(jnp.int32, (tr, num_bins), 1)
            onehot_b = (iota_b == idx).astype(jnp.float32)
            group = nodes * 2 + read_ref[...]
            iota_g = jax.lax.broadcasted_iota(jnp.int32, (tr, 2 * n), 1)
            onehot_g = (iota_g == group).astype(jnp.float32) * w
            hist_ref[...] += jax.lax.dot_general(
                onehot_g, onehot_b, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )


def chunk_replay_call(
    hosts: jax.Array,  # [K, N] f32 0/1 (K padded to tkey)
    keys: jax.Array,  # [B] i32 (B padded to tr)
    nodes: jax.Array,  # [B] i32
    is_read: jax.Array,  # [B] i32
    valid: jax.Array,  # [B] i32
    rtt: jax.Array,  # [N, N] f32
    *,
    service_ms,
    xfer_read_ms,
    xfer_write_ms,
    lo,
    hi,
    master: int,
    read_mode: str,
    num_bins: int,
    tr: int = DEFAULT_TR,
    tkey: int = DEFAULT_TKEY,
    interpret: bool | None = None,
    extra_ms: jax.Array | None = None,  # [B] f32 contention wait per request
):
    if interpret is None:
        interpret = interpret_default()
    b = keys.shape[0]
    k, n = hosts.shape
    tr = min(tr, b)
    tkey = min(tkey, k)
    assert b % tr == 0, (b, tr)
    assert k % tkey == 0, (k, tkey)
    num_key_tiles = k // tkey
    grid = (b // tr, num_key_tiles)
    kernel = functools.partial(
        chunk_replay_kernel,
        read_mode=read_mode,
        master=master,
        num_bins=num_bins,
        n=n,
        tr=tr,
        tkey=tkey,
        num_key_tiles=num_key_tiles,
        with_extra=extra_ms is not None,
    )
    req = lambda i, j: (i, 0)
    acc = lambda i, j: (0, 0)
    scalar = pl.BlockSpec((1, 1), acc)
    out_specs = [
        pl.BlockSpec((1, n), acc),  # busy
        pl.BlockSpec((1, 4), acc),  # stats
    ]
    out_shape = [
        jax.ShapeDtypeStruct((1, n), jnp.float32),
        jax.ShapeDtypeStruct((1, 4), jnp.float32),
    ]
    if num_bins > 0:
        out_specs.append(pl.BlockSpec((2 * n, num_bins), acc))
        out_shape.append(jax.ShapeDtypeStruct((2 * n, num_bins), jnp.float32))
    in_specs = [
        pl.BlockSpec((tr, 1), req),
        pl.BlockSpec((tr, 1), req),
        pl.BlockSpec((tr, 1), req),
        pl.BlockSpec((tr, 1), req),
        pl.BlockSpec((tkey, n), lambda i, j: (j, 0)),
        pl.BlockSpec((n, n), acc),
        scalar,
        scalar,
        scalar,
        scalar,
        scalar,
    ]
    inputs = [
        keys.astype(jnp.int32).reshape(b, 1),
        nodes.astype(jnp.int32).reshape(b, 1),
        is_read.astype(jnp.int32).reshape(b, 1),
        valid.astype(jnp.int32).reshape(b, 1),
        hosts.astype(jnp.float32),
        rtt.astype(jnp.float32),
        jnp.asarray(service_ms, jnp.float32).reshape(1, 1),
        jnp.asarray(xfer_read_ms, jnp.float32).reshape(1, 1),
        jnp.asarray(xfer_write_ms, jnp.float32).reshape(1, 1),
        jnp.asarray(lo, jnp.float32).reshape(1, 1),
        jnp.asarray(hi, jnp.float32).reshape(1, 1),
    ]
    if extra_ms is not None:
        in_specs.append(pl.BlockSpec((tr, 1), req))
        inputs.append(extra_ms.astype(jnp.float32).reshape(b, 1))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[vmem_scratch((tr, n), jnp.float32)],
        # Every grid step accumulates into the SAME output blocks, so both
        # grid dimensions are sequential ("arbitrary"), not parallel.
        compiler_params=compiler_params(("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*inputs)
