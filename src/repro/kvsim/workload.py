"""YCSB-style workload generation (paper §8.2) + geo workload presets.

The paper's workloads are permutations of:
  * read ratio: 100% (all reads) → 50% (write-heavy)
  * uniform vs skewed key access — skew = zipfian approximated as
    "10% of the data items requested 90% of the time" (paper's own wording,
    which we implement literally as a two-tier distribution)
  * 100,000 total requests

Geo-distribution model: each key has a *natural request source* (the node
closest to most of its clients — the paper's DNS-routing assumption, §4);
requests for a key arrive at that node with probability ``affinity`` and at a
uniformly random other node otherwise. ``affinity = 1/n`` reduces to fully
uniform sources. This is the knob that makes "bring data closer to the
frequent source" meaningful, and it is an *assumption the paper leaves
implicit* (documented in EXPERIMENTS.md §Repro-assumptions).

Beyond the paper's 3-node testbed, two geo workload classes pair with the
``[N, N]`` RTT topologies in ``cluster.py``:

  * **region-skewed** (``region_weights``): keys' natural sources are drawn
    from a non-uniform distribution over regions — most traffic originates
    in a couple of hot regions, as in real WAN deployments.
  * **diurnal** (``diurnal_shifts``): the hot region *rotates* across the
    trace ("follow the sun") — at phase p every request source is shifted p
    nodes around the ring, so placement must chase moving traffic. This is
    the workload the daemon's beyond-paper count decay exists for.

``generate_trace`` is pure JAX and accepts a traced seed, so the simulator
can ``vmap`` trace generation across CI iterations.

Streamed trace generation (scale-out fabric)
--------------------------------------------
``generate_trace`` materialises the whole ``[R]`` trace — O(R) HBM that caps
studies around ~1M requests. The streamed spelling splits the same PRNG
stream positionally instead of temporally:

  * :func:`generate_key_state` draws the per-key state (natural sources,
    object sizes) — O(K), drawn once per run; bit-identical to the
    corresponding ``Trace`` fields.
  * :func:`generate_trace_chunk` draws any window of request positions
    on demand — O(chunk) — and is **bit-identical to slicing the
    materialised ``generate_trace`` output** at those positions.

The equivalence works because jax's partitionable threefry scheme (the
default since jax 0.5) is counter-based and positional: element ``p`` of
``random_bits(key, 32, (n,))`` is the XOR of the two output lanes of
threefry2x32 on the counter pair ``(0, p)``, whatever ``n`` is.
``_sliced_bits`` binds the threefry primitive on exactly those counters for
an arbitrary *position* vector, so any slice of the stream costs O(slice),
not O(n). ``_sliced_randint`` / ``_sliced_bernoulli`` then replicate
``jax.random``'s bit-to-value transforms op-for-op on top. Positions
``>= num_requests`` produce well-typed garbage (in-range keys/nodes) that
callers must mask — the simulation engine's padded-row ``valid`` mask
already does.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.extend.random import threefry2x32_p

__all__ = [
    "WorkloadConfig",
    "Trace",
    "TraceChunk",
    "generate_trace",
    "generate_key_state",
    "generate_trace_chunk",
    "wan5_workload",
    "diurnal_workload",
]


class WorkloadConfig(NamedTuple):
    num_requests: int = 100_000  # paper: uniform set of 100k requests
    # The paper does not state the key count; 1000 gives 100 accesses/key
    # under uniform traffic, enough for placement to converge within the
    # trace (calibration constant, see EXPERIMENTS.md §Repro-assumptions).
    num_keys: int = 1_000
    num_nodes: int = 3  # paper testbed: 3 nodes
    read_fraction: float = 1.0  # 1.0 .. 0.5
    skewed: bool = False  # False=uniform, True=zipfian 90/10
    hot_fraction: float = 0.10  # "10% of the data items ..."
    hot_traffic: float = 0.90  # "... 90% of the time"
    # P(request arrives at the key's natural node). The paper's DNS
    # assumption (§4) pins each client to its nearest server and a key's
    # clients are geo-clustered, so the faithful default is 1.0; the
    # affinity-sweep benchmark explores degradation below that.
    affinity: float = 1.0
    # P(natural node = i) per region; None = uniform over nodes. Length must
    # equal num_nodes (hashable tuple so the config stays a jit static).
    region_weights: tuple[float, ...] | None = None
    # >0: request sources rotate `diurnal_shifts` times across the trace —
    # requests in phase p originate (natural + p) % n, so the hot region
    # moves and stale placements decay in value.
    diurnal_shifts: int = 0
    # Per-key payload size distribution, consumed by the placement daemon's
    # capacity projection (per-node replica-byte budgets). Sizes are
    # lognormal: object_bytes × exp(sigma · N(0,1)), drawn once per key from
    # the trace seed; sigma = 0 (default) keeps every object at exactly
    # `object_bytes` — and with an infinite budget the sizes are inert, so
    # the paper's experiments are unchanged.
    object_bytes: float = 1024.0
    object_bytes_sigma: float = 0.0


class Trace(NamedTuple):
    keys: Array  # [R] int32
    nodes: Array  # [R] int32 requesting node
    is_read: Array  # [R] bool
    natural_node: Array  # [K] int32 per-key natural source (ground truth)
    object_bytes: Array  # [K] f32 per-key payload size


class TraceChunk(NamedTuple):
    """The per-request fields of one streamed window (per-key state lives in
    :func:`generate_key_state`; positions ``>= num_requests`` are garbage the
    caller must mask)."""

    keys: Array  # [B] int32
    nodes: Array  # [B] int32
    is_read: Array  # [B] bool


def _check_region_weights(cfg: WorkloadConfig) -> None:
    if cfg.region_weights is not None and len(cfg.region_weights) != cfg.num_nodes:
        raise ValueError(
            f"region_weights has {len(cfg.region_weights)} entries "
            f"for {cfg.num_nodes} nodes"
        )


def _workload_keys(seed: int | Array) -> tuple[Array, ...]:
    """The six per-field subkeys every trace spelling shares — splitting is
    O(1), so the streamed path re-derives them rather than threading key
    state around."""
    return tuple(jax.random.split(jax.random.PRNGKey(seed), 6))


def _natural_nodes(cfg: WorkloadConfig, k_nat: Array) -> Array:
    """Per-key natural request source ``[K] i32`` (the geo ground truth)."""
    k, n = cfg.num_keys, cfg.num_nodes
    if cfg.region_weights is not None:
        w = jnp.asarray(cfg.region_weights, jnp.float32)
        return jax.random.choice(k_nat, n, (k,), p=w / jnp.sum(w)).astype(
            jnp.int32
        )
    return jax.random.randint(k_nat, (k,), 0, n).astype(jnp.int32)


def _key_sizes(cfg: WorkloadConfig, k_other: Array) -> Array:
    """Per-key payload sizes ``[K] f32`` (lognormal when sigma > 0)."""
    k = cfg.num_keys
    if cfg.object_bytes_sigma > 0:
        # fold_in (not an extra split) so keys/nodes/reads are byte-identical
        # to traces generated before sizes existed (pinned seed goldens).
        k_size = jax.random.fold_in(k_other, 2)
        sizes = cfg.object_bytes * jnp.exp(
            cfg.object_bytes_sigma * jax.random.normal(k_size, (k,))
        )
    else:
        sizes = jnp.full((k,), cfg.object_bytes, jnp.float32)
    return sizes.astype(jnp.float32)


def generate_trace(cfg: WorkloadConfig, seed: int | Array = 0) -> Trace:
    _check_region_weights(cfg)
    k_hot, k_key, k_node, k_rw, k_nat, k_other = _workload_keys(seed)
    r, k, n = cfg.num_requests, cfg.num_keys, cfg.num_nodes

    if cfg.skewed:
        # Two-tier zipf approximation, exactly as the paper describes it:
        # hot 10% of keys serve 90% of requests.
        n_hot = max(1, int(k * cfg.hot_fraction))
        pick_hot = jax.random.bernoulli(k_hot, cfg.hot_traffic, (r,))
        hot_ids = jax.random.randint(k_key, (r,), 0, n_hot)
        cold_ids = jax.random.randint(
            jax.random.fold_in(k_key, 1), (r,), n_hot, k
        )
        keys = jnp.where(pick_hot, hot_ids, cold_ids).astype(jnp.int32)
    else:
        keys = jax.random.randint(k_key, (r,), 0, k).astype(jnp.int32)

    natural = _natural_nodes(cfg, k_nat)
    stay = jax.random.bernoulli(k_node, cfg.affinity, (r,))
    # A non-natural request lands uniformly on one of the other n-1 nodes.
    shift = jax.random.randint(k_other, (r,), 1, n)
    nat_of_key = natural[keys]
    nodes = jnp.where(stay, nat_of_key, (nat_of_key + shift) % n).astype(jnp.int32)

    if cfg.diurnal_shifts > 0:
        # "Follow the sun": phase p (p = 0..shifts-1) rotates every request
        # source p nodes around the ring.
        phase = (jnp.arange(r, dtype=jnp.int32) * cfg.diurnal_shifts) // r
        nodes = ((nodes + phase) % n).astype(jnp.int32)

    sizes = _key_sizes(cfg, k_other)
    is_read = jax.random.bernoulli(k_rw, cfg.read_fraction, (r,))
    return Trace(
        keys=keys,
        nodes=nodes,
        is_read=is_read,
        natural_node=natural,
        object_bytes=sizes,
    )


# ---------------------------------------------------------------------------
# Streamed trace generation: positional slices of the identical PRNG stream.
# ---------------------------------------------------------------------------


def _sliced_bits(key: Array, pos: Array) -> Array:
    """``jax.random.bits(key, (n,), uint32)[pos]`` for any ``n > pos``, in
    O(|pos|): the partitionable layout encrypts the counter pair ``(0, p)``
    for position ``p`` and XORs the two output lanes. Positions past the
    trace's end are counters the full draw never used — callers mask those
    rows."""
    p = pos.astype(jnp.uint32)
    k0 = jnp.broadcast_to(key[0], pos.shape).astype(jnp.uint32)
    k1 = jnp.broadcast_to(key[1], pos.shape).astype(jnp.uint32)
    out0, out1 = threefry2x32_p.bind(k0, k1, jnp.zeros_like(p), p)
    return out0 ^ out1


def _sliced_randint(key: Array, pos: Array, minval: int, maxval: int) -> Array:
    """``jax.random.randint(key, (n,), minval, maxval)[pos]`` — the
    double-draw modular-reduction transform of ``jax._src.random._randint``
    replicated op-for-op on sliced bits (int32, static python bounds)."""
    k1, k2 = jax.random.split(key)
    higher = _sliced_bits(k1, pos)
    lower = _sliced_bits(k2, pos)
    span_i = maxval - minval if maxval > minval else 1
    span = np.uint32(span_i)
    # jax squares 2**16 % span in uint32, which wraps once span > 2**16.
    multiplier = np.uint32(((2**16 % span_i) ** 2 % 2**32) % span_i)
    offset = ((higher % span) * multiplier + (lower % span)) % span
    return (minval + offset.astype(jnp.int32)).astype(jnp.int32)


def _sliced_uniform(key: Array, pos: Array) -> Array:
    """``jax.random.uniform(key, (n,))[pos]``: randomise the mantissa at
    exponent 1, shift to [0, 1) — bit-for-bit the ``_uniform`` transform."""
    bits = _sliced_bits(key, pos)
    float_bits = (bits >> np.uint32(9)) | np.float32(1.0).view(np.uint32)
    floats = jax.lax.bitcast_convert_type(float_bits, jnp.float32) - np.float32(1.0)
    return jax.lax.max(
        np.float32(0.0), floats * np.float32(1.0) + np.float32(0.0)
    )


def _sliced_bernoulli(key: Array, p, pos: Array) -> Array:
    """``jax.random.bernoulli(key, p, (n,))[pos]``."""
    return _sliced_uniform(key, pos) < jnp.float32(p)


def generate_key_state(
    cfg: WorkloadConfig, seed: int | Array = 0
) -> tuple[Array, Array]:
    """The per-key state of a trace — ``(natural_node [K] i32,
    object_bytes [K] f32)`` — bit-identical to the corresponding
    :func:`generate_trace` fields, without drawing any request. O(K), drawn
    once per streamed run."""
    _check_region_weights(cfg)
    _, _, _, _, k_nat, k_other = _workload_keys(seed)
    return _natural_nodes(cfg, k_nat), _key_sizes(cfg, k_other)


def _request_window(
    cfg: WorkloadConfig, keys6: tuple[Array, ...], pos: Array, natural: Array
) -> TraceChunk:
    """Per-request fields at arbitrary positions ``pos`` — the streamed
    engine's in-scan spelling (``keys6`` from :func:`_workload_keys`,
    ``natural`` the full ``[K]`` map from :func:`generate_key_state`)."""
    k_hot, k_key, k_node, k_rw, _, k_other = keys6
    r, k, n = cfg.num_requests, cfg.num_keys, cfg.num_nodes

    if cfg.skewed:
        n_hot = max(1, int(k * cfg.hot_fraction))
        pick_hot = _sliced_bernoulli(k_hot, cfg.hot_traffic, pos)
        hot_ids = _sliced_randint(k_key, pos, 0, n_hot)
        cold_ids = _sliced_randint(jax.random.fold_in(k_key, 1), pos, n_hot, k)
        keys = jnp.where(pick_hot, hot_ids, cold_ids).astype(jnp.int32)
    else:
        keys = _sliced_randint(k_key, pos, 0, k).astype(jnp.int32)

    stay = _sliced_bernoulli(k_node, cfg.affinity, pos)
    shift = _sliced_randint(k_other, pos, 1, n)
    nat_of_key = natural[keys]
    nodes = jnp.where(stay, nat_of_key, (nat_of_key + shift) % n).astype(jnp.int32)

    if cfg.diurnal_shifts > 0:
        phase = (pos.astype(jnp.int32) * cfg.diurnal_shifts) // r
        nodes = ((nodes + phase) % n).astype(jnp.int32)

    is_read = _sliced_bernoulli(k_rw, cfg.read_fraction, pos)
    return TraceChunk(keys=keys, nodes=nodes, is_read=is_read)


def generate_trace_chunk(
    cfg: WorkloadConfig,
    seed: int | Array,
    chunk_idx: int | Array,
    chunk_size: int,
    natural: Array | None = None,
) -> TraceChunk:
    """Request positions ``[chunk_idx*chunk_size, (chunk_idx+1)*chunk_size)``
    of the trace ``generate_trace(cfg, seed)`` would materialise —
    **bit-identical to slicing its output** (same ``fold_in`` stream), in
    O(chunk_size) memory.

    ``chunk_idx`` may be traced (the engine calls this inside ``lax.scan``).
    ``natural`` is the full ``[K]`` natural-source map from
    :func:`generate_key_state`; pass it to amortise the O(K) per-key draw
    across chunks (recomputed from ``seed`` when ``None``). Rows whose
    position is ``>= cfg.num_requests`` (a final chunk that overruns the
    trace) carry in-range garbage the caller must mask.
    """
    _check_region_weights(cfg)
    keys6 = _workload_keys(seed)
    if natural is None:
        natural = _natural_nodes(cfg, keys6[4])
    start = jnp.asarray(chunk_idx, jnp.int32) * chunk_size
    pos = start + jnp.arange(chunk_size, dtype=jnp.int32)
    return _request_window(cfg, keys6, pos, natural)


def wan5_workload(**kwargs) -> WorkloadConfig:
    """5-region WAN preset: skewed traffic whose natural sources concentrate
    in two hot regions (pairs with ``cluster.wan5_cluster``)."""
    kwargs.setdefault("num_nodes", 5)
    kwargs.setdefault("skewed", True)
    kwargs.setdefault("region_weights", (0.35, 0.25, 0.20, 0.12, 0.08))
    return WorkloadConfig(**kwargs)


def diurnal_workload(**kwargs) -> WorkloadConfig:
    """Diurnal hot-region preset: traffic concentrated in one region whose
    identity rotates across the trace (pairs with ``cluster.wan5_cluster``
    and a decaying placement daemon)."""
    kwargs.setdefault("num_nodes", 5)
    kwargs.setdefault("skewed", True)
    kwargs.setdefault("region_weights", (0.60, 0.10, 0.10, 0.10, 0.10))
    kwargs.setdefault("diurnal_shifts", 4)
    return WorkloadConfig(**kwargs)
