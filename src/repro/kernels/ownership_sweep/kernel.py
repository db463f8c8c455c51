"""Pallas ownership sweep (TPU): the paper's Algorithm 3 analysis loop.

One grid step processes a [TK, N] tile of the metadata cluster entirely in
VMEM: ownership fractions (eq. 1), eligibility vs H (eq. 2) with the
argmax-fallback starvation guard (eq. 3's intent), expiry, and the
owner/add/drop deltas. All VPU work — no matmuls — so the kernel is
memory-bound by design and the tile size just has to keep the six [TK, N]
planes (~6·TK·N·4B) under VMEM; TK = 2048 at N ≤ 64 is ≈ 3 MB.

The ownership coefficient H arrives as a scalar *input* (like ``now``)
rather than a compile-time constant, so jitted pipelines can trace it —
``repro.core.placement.sweep(backend="pallas")`` routes through here with a
traced H. ``expiry`` stays static (``<= 0`` disables — the unified
convention; the branch compiles away when unused). ``interpret`` defaults to
auto-detection: interpret mode off-TPU, compiled Mosaic on TPU.

The daemon sweeps millions of keys per pass; this kernel is why the paper's
"constant time per key, no graph traversal" claim survives contact with a
TPU: one HBM read + one write per metadata byte. The ``f`` output plane
feeds the cost model's capacity projection directly (scored placement
pipeline), avoiding a second [K, N] pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import compiler_params, interpret_default, pl

__all__ = ["ownership_sweep_kernel", "ownership_sweep_call"]

DEFAULT_TK = 2048


def ownership_sweep_kernel(
    counts_ref,  # [TK, N] f32
    hosts_ref,  # [TK, N] i8
    live_ref,  # [TK, 1] i8
    last_ref,  # [TK, 1] i32
    now_ref,  # [1, 1] i32
    h_ref,  # [1, 1] f32 — ownership coefficient H
    owners_ref,  # out [TK, N] i8
    add_ref,  # out [TK, N] i8
    drop_ref,  # out [TK, N] i8
    expired_ref,  # out [TK, 1] i8
    f_ref,  # out [TK, N] f32 — ownership fractions (cost-model scoring)
    *,
    expiry: int,
    n: int,
    tk: int,
):
    # Mosaic lowers i1 planes poorly (no i8 -> i1 truncation, no lane
    # broadcast of an i1 column), so every [TK, 1] column is broadcast to
    # [TK, N] as a number first and compared there.
    plane = lambda col: jnp.broadcast_to(col, (tk, n))
    counts = counts_ref[...]
    hosts = hosts_ref[...].astype(jnp.int32) != 0
    live = live_ref[...].astype(jnp.int32)  # [TK, 1] 0/1
    h = h_ref[0, 0]

    total = plane(jnp.sum(counts, axis=-1, keepdims=True))
    touched = total > 0
    f = jnp.where(touched, counts / jnp.maximum(total, 1.0), 0.0)
    elig = f >= h
    # Starvation guard: traffic but nobody qualifies -> hottest node keeps it.
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (tk, n), 1)
    am = plane(jnp.argmax(counts, axis=-1)[:, None].astype(jnp.int32))
    n_elig = plane(jnp.sum(elig.astype(jnp.int32), axis=-1, keepdims=True))
    none_q = touched & (n_elig == 0)
    elig = (none_q & (iota_n == am)) | (~none_q & elig)

    owners = (touched & elig) | (~touched & hosts)  # silence = no churn
    if expiry > 0:
        now = now_ref[0, 0]
        expired = live * ((now - last_ref[...]) > expiry).astype(jnp.int32)
    else:
        expired = jnp.zeros_like(live)
    owners = owners & (plane(live) != 0) & (plane(expired) == 0)

    owners_ref[...] = owners.astype(jnp.int8)
    add_ref[...] = (owners & ~hosts).astype(jnp.int8)
    drop_ref[...] = (hosts & ~owners).astype(jnp.int8)
    expired_ref[...] = expired.astype(jnp.int8)
    f_ref[...] = f


def ownership_sweep_call(
    counts: jax.Array,  # [K, N] f32
    hosts: jax.Array,  # [K, N] bool/i8
    live: jax.Array,  # [K] bool/i8
    last_access: jax.Array,  # [K] i32
    now: jax.Array,  # [] or [1] i32
    *,
    h: jax.Array | float,
    expiry: int = 0,
    tk: int = DEFAULT_TK,
    interpret: bool | None = None,
):
    if interpret is None:
        interpret = interpret_default()
    k, n = counts.shape
    tk = min(tk, k)
    assert k % tk == 0, (k, tk)
    grid = (k // tk,)
    kernel = functools.partial(ownership_sweep_kernel, expiry=expiry, n=n, tk=tk)
    row = lambda i: (i, 0)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tk, n), row),
            pl.BlockSpec((tk, n), row),
            pl.BlockSpec((tk, 1), row),
            pl.BlockSpec((tk, 1), row),
            scalar,
            scalar,
        ],
        out_specs=[
            pl.BlockSpec((tk, n), row),
            pl.BlockSpec((tk, n), row),
            pl.BlockSpec((tk, n), row),
            pl.BlockSpec((tk, 1), row),
            pl.BlockSpec((tk, n), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), jnp.int8),
            jax.ShapeDtypeStruct((k, n), jnp.int8),
            jax.ShapeDtypeStruct((k, n), jnp.int8),
            jax.ShapeDtypeStruct((k, 1), jnp.int8),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
        ],
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(
        counts.astype(jnp.float32),
        hosts.astype(jnp.int8),
        live.astype(jnp.int8).reshape(k, 1),
        last_access.astype(jnp.int32).reshape(k, 1),
        jnp.asarray(now, jnp.int32).reshape(1, 1),
        jnp.asarray(h, jnp.float32).reshape(1, 1),
    )
    return out
