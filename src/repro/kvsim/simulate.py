"""Trace-driven simulation of the paper's experiment (§8–§9).

Reproduces the paper's Figure 2/3 experiment on YCSB-style traces
(``workload.py``) with the latency model generalised to an ``[N, N]`` RTT
topology (``cluster.py``) — under any *placement policy* from
``repro.core.policy``. The decision rule is a first-class value::

    run_scenario(workload, cluster, RedynisPolicy(h=0.2))
    run_scenario(workload, cluster, StaticPolicy(mode="remote"))

The legacy ``Scenario`` enum spelling and its kwarg sprawl
(``ownership_coefficient`` / ``expiry_ticks`` / ``decay`` /
``daemon_period`` / ``backend``) were removed once their one-release
deprecation window closed; passing a ``Scenario`` where a policy belongs
now raises with the replacement spelled out.

An *active* policy (``policy.is_active``) runs the actual core engine —
requests fold accesses into a :class:`repro.core.MetadataStore` and the
policy decides between request chunks through the shared pipeline
(fractions → decide → expiry → capacity projection), exactly like the
paper's offline RedynisDaemon. With finite per-node replica budgets
(``ClusterConfig.capacity_bytes``) the projection stage trims adds and
evicts cold replicas uniformly for every policy; at the default infinite
budget it compiles away and ``RedynisPolicy`` is bit-identical to the
paper's Algorithm 3. Static policies freeze the replica map and the whole
decision machinery compiles away.

Execution model
---------------
The trace is processed in chunks of ``daemon_interval`` requests. Within a
chunk every request sees the replica map *frozen at chunk start* — this is
the paper's non-blocking property: in-flight requests are never stalled by
the daemon; they observe the previous placement until the sweep commits.
Metadata updates (access logging) fold in continuously, as in Algorithm 1.
Per-node occupancy (replica bytes) is sampled on the same frozen map for
*every* policy, and ``peak_occupancy_bytes`` is its running elementwise max
(static policies never mutate the map, so their per-chunk peak equals the
initial-map occupancy the seed engine reported).

Two engines with identical semantics:

  * ``run_scenario`` — the fused fast path: ONE ``jax.lax.scan`` over
    fixed-shape chunks with the policy step ``due``-masked inside the scan
    body (``repro.core.policy.policy_masked_step``), so a whole scenario is
    a single compiled program. The policy's *static key* is the jit static
    while its dynamic hyperparameters (H, decay, K, thresholds) are traced
    — re-running with new knob values never recompiles. ``run_experiment``
    additionally ``vmap``s the seed (CI-iteration) dimension, and its
    ``policies=[...]`` axis stacks same-family dynamic params and vmaps the
    *policy* dimension alongside seeds — a head-to-head grid as one batched
    program.
  * ``run_scenario_reference`` — the retained slow path: the original
    per-chunk Python loop. It exists as the regression oracle for the fused
    engine (see tests/test_simulate_equivalence.py) and accumulates in
    float64; equivalence is allclose, not bit-identical.

Chunk-replay backends
---------------------
The per-chunk request path (replica gather → read/write latency → hit
flags → busy accumulation → telemetry histogram fold) is the hot loop of
every experiment, and lives in the ``repro.kernels.chunk_replay`` trio.
``replay_backend`` selects its implementation, mirroring the ownership
sweep's backend plumbing:

  * ``"jax"`` (default) — the pure-jnp composition, kept op-for-op
    identical to the pre-fusion engine so every aggregate stays bit-exact
    with the seed goldens. The engine additionally hoists the O(K·N)
    per-chunk occupancy sample out of the scan body for *inactive*
    policies (a static map never changes, so its occupancy is a loop
    constant) — this is where static baselines win big.
  * ``"pallas"`` — the fused one-pass Mosaic kernel: one grid step per
    request tile, gathers and folds recast as MXU matmuls, and — with
    telemetry on — the grouped latency histogram folded in the same pass
    (subsuming the separate ``latency_histogram`` dispatch). Histogram
    counts stay bit-exact; busy/latency reductions re-associate across
    tiles, so engine-level results are allclose to the jax backend
    (pinned by tests/test_chunk_replay.py).

``run_scenario_reference`` always replays through the jnp path — it *is*
the oracle the kernel is pinned against.

Throughput model
----------------
Nodes serve their request streams concurrently (the paper's three
application servers). Per-node busy time = Σ latency of requests arriving at
that node; makespan = max over nodes; throughput = R / makespan. The paper
does not state the YCSB per-op service cost; ``ClusterConfig.service_ms`` is
the calibration constant (documented in EXPERIMENTS.md §Repro-assumptions).

Queueing model
--------------
With ``ClusterConfig.service`` set to an enabled
:class:`~repro.kvsim.cluster.ServiceConfig`, every request additionally
pays an M/M/1-style contention wait: each chunk's per-request service
demand ``d = service_ms + object_bytes / serve_bytes_per_ms`` folds at the
request's *serving* node into a load factor
``rho = min(demand_fold / capacity_ms, rho_max)``, and the request waits
``d * rho / (1 - rho)`` on top of its RTT-model latency. The pre-pass
(``kernels.chunk_replay.ref.contention_extra_ms_ref``) is canonical for
both engines, the static fast path, AND the Pallas replay backend — the
fused kernel consumes the per-request ``extra_ms`` it produces, so
contention can no more drift between backends than the base latency model
can. ``service=None`` (the default) compiles the exact pre-contention
program, so every seed golden holds bit-exact (pinned by
tests/test_service_time.py).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.metadata import create_store, record_accesses
from repro.kernels.chunk_replay.ops import (
    REPLAY_BACKENDS,
    chunk_latency,
    chunk_replay,
)
from repro.kernels.chunk_replay.ref import (
    chunk_components_ref,
    contention_extra_ms_ref,
    fault_extra_ms_ref,
    routing_extra_split_ref,
)
from repro.kernels.latency_histogram.ref import bin_index
from repro.core.policy import (
    PolicyContext,
    describe_policy,
    policy_masked_step,
    policy_sweep,
    publish_mask,
    split_policy,
)
from repro.kvsim.cluster import ClusterConfig, Scenario, normalize_service
from repro.kvsim.faults import compile_schedule, normalize_faults
from repro.kvsim.routing import (
    STALE_AGE_BINS,
    consult_probe,
    init_router_state,
    normalize_routing,
    publish_commit,
    published_view,
    router_cache_update,
    router_of,
    stale_age_fold,
)
from repro.kvsim.telemetry import (
    NUM_COMPONENTS,
    SimTrace,
    TelemetryConfig,
    TelemetryLeaves,
    attribution_chunk_hist,
    attribution_trace_hist,
    build_trace,
    chunk_histogram,
    leaves_quantile,
    merge_leaves,
    normalize_telemetry,
    psum_leaves,
    trace_histogram,
)
from repro.kvsim.workload import (
    Trace,
    WorkloadConfig,
    _request_window,
    _workload_keys,
    generate_key_state,
    generate_trace,
)

__all__ = [
    "REPLAY_BACKENDS",
    "TRACE_MODES",
    "ShardSpec",
    "SimResult",
    "SimTrace",
    "TelemetryConfig",
    "run_scenario",
    "run_scenario_reference",
    "run_experiment",
    "confidence_interval_99",
]

TRACE_MODES = ("materialized", "streamed")

# The scan body's phases reach the compiled program only as the op_name
# metadata of its instructions, which is how a profile attributes device
# time to them. JAX's persistent compilation cache leaves metadata out of
# its key, so a program compiled from a tree with other scopes (sharing
# the cache directory) would be loaded in place of this one, with that
# tree's names: key the cache on the metadata too. This belongs where an
# entry point turns the cache on, as benchmarks.common.enable_compile_cache
# does; it stays here until chipbench/run.py's enable_compile_cache sets
# it, and then goes, with its test in tests/test_named_phases.py.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


class ShardSpec(NamedTuple):
    """Keyspace sharding of the fused engine, following the
    ``publish_and_fill`` convention from ``core/repartition.py``:
    ``axis_name=None`` (the default) is the degenerate single-shard program
    — no collectives, no request masking, op-for-op the unsharded engine,
    so every seed golden holds bit-exact. With an axis name the engine runs
    inside a ``shard_map`` over a ``Mesh`` whose ``axis_name`` dimension
    splits the key axis into ``num_shards`` contiguous blocks: per-key
    state (metadata counts, replica map, sizes, policy EMA/decay state)
    lives shard-local, each shard replays only its own keys' requests, and
    ``psum`` assembles the global aggregates (busy fold, histograms, move
    counters, occupancy, the contention demand fold) exactly where the
    daemon needs cluster-wide values.

    ``pad`` lifts the historical ``K % S == 0`` restriction: when the key
    axis does not divide evenly, ``run_scenario`` pads ``natural`` /
    ``object_bytes`` with ``pad`` trailing dummy keys so every shard holds
    ``ceil(K / S)`` rows, and the engine masks the padded rows out of all
    per-key state (never live, never owned, zero bytes). Requests are drawn
    from the REAL keyspace, so no padded key is ever requested. ``pad == 0``
    (every dividing K, and the whole unsharded world) compiles the exact
    historical program — the field is a jit static, so it only splits the
    compile cache, never the math.
    """

    axis_name: str | None = None
    num_shards: int = 1
    pad: int = 0

    @property
    def active(self) -> bool:
        return self.axis_name is not None and self.num_shards > 1


class SimResult(NamedTuple):
    """Aggregate metrics for one scenario run (one seed)."""

    throughput_ops_s: float
    hit_rate: float
    mean_latency_ms: float
    node_busy_ms: np.ndarray  # [N]
    replication_moves: float  # replicas created by the daemon
    deletion_moves: float  # replicas dropped by the daemon (all causes)
    evictions: float  # subset of deletions caused by key expiry
    capacity_evictions: float  # held replicas evicted by the budget projection
    peak_occupancy_bytes: np.ndarray  # [N] peak replica bytes per node
    # Routing/directory-tier counters (all zero when ClusterConfig.routing
    # is off — the fields default so the pre-routing result shape is a
    # strict prefix and existing consumers are untouched).
    router_consults: float = 0.0  # directory consults
    directory_fetches: float = 0.0  # cache misses (home-node round trips)
    mis_routes: float = 0.0  # consults detoured by a stale ownership view
    stale_consults: float = 0.0  # consults that hit a stale cache entry
    # Failure-injection counters (all zero when ClusterConfig.faults is off
    # — same strict-prefix convention as the routing block above). With
    # faults on, hit_rate/mean_latency_ms cover SERVED requests only; the
    # unavailable_* counts are the excluded remainder.
    unavailable_reads: float = 0.0  # reads refused (origin down / no live copy)
    unavailable_writes: float = 0.0  # writes refused (origin node down)
    failovers: float = 0.0  # writes relayed through a stand-in master
    repair_moves: float = 0.0  # re-replications of copies lost to failures


def _busy_fold(lat: Array, nodes: Array, n: int) -> Array:
    """Per-node latency totals ``[N]`` as a masked reduction over the
    requests. A scatter-add (or an f32 matmul on CPU) accumulates each
    node's total in request order, so once the total is large every later
    latency loses its low bits — 1% of a node's busy time at
    10**6-request chunks; XLA's reductions accumulate pairwise."""
    own = nodes[:, None] == jnp.arange(n)[None, :]
    return jnp.sum(jnp.where(own, lat[:, None], 0.0), axis=0)


def _mean_latency(lat_sum: Array, r: int, served: Array | None = None):
    """Mean latency over the served requests (all ``r`` when ``served`` is
    ``None``). When nothing was refused the faults-on program takes the
    very expression the faults-off program uses, ``lat_sum / r``, so an
    all-up fault schedule agrees with faults off bit for bit whatever the
    compiler makes of a division by a constant."""
    if served is None:
        return lat_sum / r
    return jnp.where(
        served == r, lat_sum / r, lat_sum / jnp.maximum(served, 1.0)
    )


def _initial_hosts(
    natural_node: Array, num_keys: int, num_nodes: int, placement: str
) -> Array:
    """Starting replica map (paper §9 scenario definitions): ``"full"`` is
    every-key-everywhere (the idealised baselines); ``"offsite"`` starts
    each key on a single node that is *not* its natural request source
    ("requests ... served not available on the local key-value store") —
    the worst-case placement adaptive policies must dig out of."""
    if placement == "full":
        return jnp.ones((num_keys, num_nodes), dtype=bool)
    home = (natural_node + 1) % num_nodes
    return jax.nn.one_hot(home, num_nodes, dtype=bool)


def _replay_scalars(cluster: ClusterConfig) -> dict:
    """The latency-model scalars the chunk-replay trio consumes (host-side
    floats — traced by the jit'd wrappers, so retuned clusters never
    recompile)."""
    return dict(
        service_ms=cluster.service_ms,
        master=cluster.master,
        xfer_read_ms=cluster.transfer_ms(cluster.value_bytes),
        xfer_write_ms=cluster.transfer_ms(cluster.value_bytes + cluster.key_bytes),
    )


def _flight_positions(fcfg, chunk_idx, chunk_size: int) -> Array:
    """In-chunk sample offsets ``[S] i32`` for the flight recorder.

    ``"stride"`` picks fixed equally-spaced offsets (chunk-independent, so
    the sample plan is a loop constant); ``"reservoir"`` draws uniform
    offsets from a counter-derived key (``fold_in(chunk)``) — deterministic
    per chunk, identical between the scan engine and the reference loop,
    and independent of the workload's request stream."""
    s = fcfg.samples_per_chunk
    if fcfg.mode == "stride":
        stride = max(chunk_size // s, 1)
        return (jnp.arange(s, dtype=jnp.int32) * stride) % chunk_size
    key = jax.random.fold_in(jax.random.PRNGKey(0x9E37), chunk_idx)
    return jax.random.randint(key, (s,), 0, chunk_size, dtype=jnp.int32)


def _chunk_latency(
    hosts: Array,  # [K, N] frozen replica map
    keys: Array,  # [B]
    nodes: Array,  # [B]
    is_read: Array,  # [B]
    rtt: Array,  # [N, N]
    cluster: ClusterConfig,
    read_mode: str,  # "ideal" | "no_local" | "map"
) -> tuple[Array, Array]:
    """Per-request latency + hit flags for one chunk under a frozen map —
    a thin dispatch onto ``repro.kernels.chunk_replay`` (the canonical
    implementation both engines and the Pallas kernel share)."""
    return chunk_latency(
        hosts, keys, nodes, is_read, rtt,
        read_mode=read_mode, **_replay_scalars(cluster),
    )


def _node_occupancy(hosts: Array, object_bytes: Array) -> Array:
    """Per-node replica bytes ``[N]`` under a replica map (both engines use
    this exact expression so their peaks agree bit-for-bit)."""
    return jnp.sum(jnp.where(hosts, object_bytes[:, None], 0.0), axis=0)


def _check_topology(workload: WorkloadConfig, cluster: ClusterConfig) -> None:
    if workload.num_nodes != cluster.num_nodes:
        raise ValueError(
            f"workload has {workload.num_nodes} nodes but cluster topology "
            f"has {cluster.num_nodes}"
        )
    if cluster.rtt is not None and len(cluster.rtt) != cluster.num_nodes:
        raise ValueError(
            f"rtt matrix is {len(cluster.rtt)}x{len(cluster.rtt[0])} but "
            f"num_nodes={cluster.num_nodes}"
        )
    if (
        isinstance(cluster.capacity_bytes, tuple)
        and len(cluster.capacity_bytes) != cluster.num_nodes
    ):
        raise ValueError(
            f"capacity_bytes has {len(cluster.capacity_bytes)} entries for "
            f"num_nodes={cluster.num_nodes}"
        )
    for name in ("zone_of", "region_of"):
        labels = getattr(cluster, name)
        if labels is not None and len(labels) != cluster.num_nodes:
            raise ValueError(
                f"{name} labels {len(labels)} nodes but "
                f"num_nodes={cluster.num_nodes}"
            )


def _seed_store(hosts: Array, num_keys: int, num_nodes: int):
    """Metadata layer seeded with the initial placement (Algorithm 1's
    "metadata == null -> generate metadata object" happened at load time)."""
    return create_store(num_keys, num_nodes)._replace(
        hosts=hosts,
        live=jnp.ones((num_keys,), dtype=bool),
        home=jnp.argmax(hosts, axis=-1).astype(jnp.int32),
    )


def _reject_scenario(caller: str, policy) -> None:
    """The PR-3 ``scenario=`` deprecation shim is gone (its one-release
    grace period ended with this release); keep the failure mode helpful by
    spelling out the exact policy replacement instead of an attribute
    error deep inside ``resolve``."""
    if isinstance(policy, Scenario):
        repl = (
            "RedynisPolicy()" if policy is Scenario.OPTIMIZED
            else f"StaticPolicy(mode={policy.value!r})"
        )
        raise ValueError(
            f"{caller}: the legacy scenario= spelling was removed (its "
            f"deprecation window is over); pass policy={repl} instead"
        )


def _prepare(workload, cluster, caller, policy):
    _check_topology(workload, cluster)
    _reject_scenario(caller, policy)
    if policy is None:
        raise ValueError(
            f"{caller}: a policy is required — e.g. RedynisPolicy() or "
            f"StaticPolicy(mode='local')"
        )
    policy = policy.resolve(workload.num_nodes)
    policy.validate(workload.num_nodes)
    return split_policy(policy)


def _contention_kwargs(
    cluster: ClusterConfig, read_mode: str, daemon_interval: int
) -> dict | None:
    """Host-side resolution of the queueing model: the kwargs
    ``contention_extra_ms_ref`` needs, or ``None`` when the cluster has no
    enabled :class:`ServiceConfig` (the bit-exact pre-contention path)."""
    service = normalize_service(cluster.service)
    if service is None:
        return None
    return dict(
        read_mode=read_mode,
        service_ms=cluster.service_ms,
        serve_bytes_per_ms=service.serve_bytes_per_ms,
        capacity_ms=service.capacity_ms(daemon_interval, cluster.service_ms),
        rho_max=service.rho_max,
    )


def _routing_kwargs(cluster: ClusterConfig, num_keys: int) -> dict | None:
    """Host-side resolution of the routing tier: the resolved knobs the
    engines consume, or ``None`` when the cluster has no enabled
    :class:`RoutingConfig` (the bit-exact pre-routing path — the same
    contract as :func:`_contention_kwargs`).

    ``num_routers = 0`` resolves to one router per cluster node, and a
    ``cache_entries`` at or beyond the keyspace collapses to 0 (the
    unbounded warm cache) so the admission ranking compiles away when it
    could never evict anything.
    """
    routing = normalize_routing(cluster.routing)
    if routing is None:
        return None
    if routing.home_node >= cluster.num_nodes:
        raise ValueError(
            f"routing.home_node={routing.home_node} is not a node index "
            f"(num_nodes={cluster.num_nodes})"
        )
    if routing.num_routers > cluster.num_nodes:
        raise ValueError(
            f"routing.num_routers={routing.num_routers} exceeds "
            f"num_nodes={cluster.num_nodes} (routers are consulted per "
            f"requesting node, node x -> router x % R)"
        )
    cache_entries = routing.cache_entries
    if cache_entries >= num_keys:
        cache_entries = 0
    return dict(
        num_routers=routing.num_routers or cluster.num_nodes,
        cache_entries=cache_entries,
        publish_lag_chunks=routing.publish_lag_chunks,
        home_node=routing.home_node,
        decay=routing.decay,
    )


def _fault_kwargs(cluster: ClusterConfig, num_chunks: int) -> dict | None:
    """Host-side resolution of the fault schedule: the per-chunk
    availability/crash timelines as device constants, or ``None`` when the
    cluster has no enabled :class:`FaultConfig` (the bit-exact no-fault
    path — the same contract as :func:`_contention_kwargs` /
    :func:`_routing_kwargs`).

    ``compile_schedule`` validates the declarative events against the
    cluster's failure-domain labelling (``zone_of``/``region_of``) and
    rejects any chunk in which every node would be down — the simulator
    models degraded service, not a total blackout."""
    faults = normalize_faults(cluster.faults)
    if faults is None:
        return None
    avail, crash = compile_schedule(
        faults,
        num_nodes=cluster.num_nodes,
        num_chunks=num_chunks,
        zone_of=cluster.zone_of,
        region_of=cluster.region_of,
    )
    return dict(avail=jnp.asarray(avail), crash=jnp.asarray(crash))


# ---------------------------------------------------------------------------
# Fused engine: one lax.scan over chunks, policy due-masked inside the body.
# ---------------------------------------------------------------------------

_SIM_STATICS = (
    "cluster", "policy", "daemon_interval", "telemetry", "replay_backend",
    "trace_mode", "workload", "shard",
)


def _check_replay_backend(caller: str, replay_backend: str) -> None:
    if replay_backend not in REPLAY_BACKENDS:
        raise ValueError(
            f"{caller}: unknown replay_backend {replay_backend!r}; expected "
            f"one of {REPLAY_BACKENDS}"
        )


def _simulate(
    keys: Array | None,  # [R] (None in streamed mode)
    nodes: Array | None,  # [R]
    is_read: Array | None,  # [R]
    natural: Array,  # [K] (always the FULL key axis; shards slice locally)
    object_bytes: Array,  # [K]
    params: dict,  # the policy's dynamic hyperparameters (traced)
    seed: Array | None = None,  # traced trace seed (streamed mode only)
    *,
    cluster: ClusterConfig,
    policy,  # static key from split_policy (hashable jit static)
    daemon_interval: int,
    telemetry: TelemetryConfig | None = None,
    replay_backend: str = "jax",
    trace_mode: str = "materialized",
    workload: WorkloadConfig | None = None,
    shard: ShardSpec | None = None,
):
    """Whole-scenario simulation as a single fixed-shape scan program.

    The trace is padded to ``num_chunks * daemon_interval`` with ``valid``-
    masked rows (zero latency, zero metadata weight), so every chunk has one
    shape and the Python loop collapses into ``jax.lax.scan``.

    Returns ``(aggregate leaves, telemetry leaves | None)``. With
    ``telemetry`` (a normalised :class:`TelemetryConfig` static) the scan
    body additionally folds each chunk's latencies into a grouped log-bin
    histogram and emits per-chunk series as the scan's ``ys``; the carry —
    and therefore every aggregate result — is untouched, which is what
    keeps the telemetry-off AND telemetry-on aggregates bit-exact with the
    pre-telemetry engine (pinned by tests/test_telemetry.py).

    ``trace_mode="streamed"`` drops the materialised ``[R]`` trace buffers
    entirely: each scan iteration regenerates its own chunk of requests
    in-scan from ``seed`` via ``workload._request_window`` — bit-identical
    to the slices the materialised path would have consumed (the sliced
    threefry emulation in ``workload.py``), so every aggregate and
    histogram matches the materialised engine exactly. Peak live memory
    falls from O(R + K) to O(daemon_interval + K).

    ``shard`` (a :class:`ShardSpec` static) runs the body per key-shard
    inside a caller-supplied ``shard_map`` — see the class docstring. The
    degenerate default compiles the identical unsharded program. Sharded
    f32 reductions (busy, latency sums, occupancy, contention folds)
    re-associate across shards and are allclose to single-device values;
    histogram counts and hit/read/move counters are integer sums and stay
    bit-exact.
    """
    shard = shard or ShardSpec()
    if trace_mode == "streamed":
        if workload is None:
            raise ValueError("trace_mode='streamed' requires workload=")
        r = workload.num_requests
        stream_keys = _workload_keys(seed)
    else:
        r = keys.shape[0]
        stream_keys = None
    num_keys = natural.shape[0]
    n = cluster.num_nodes
    rtt = cluster.rtt_matrix()
    obj = jnp.asarray(object_bytes, jnp.float32)
    if shard.active:
        # Contiguous block sharding of the key axis: shard i owns global
        # keys [i*kps, (i+1)*kps). natural/obj arrive replicated (requests
        # reference any key when generating/localising the trace); the
        # per-key STATE below is built from the local slice only.
        kps = num_keys // shard.num_shards
        shard_idx = jax.lax.axis_index(shard.axis_name)
        shard_base = shard_idx * kps
        nat_local = jax.lax.dynamic_slice(natural, (shard_base,), (kps,))
        obj_local = jax.lax.dynamic_slice(obj, (shard_base,), (kps,))
        local_keys = kps
    else:
        kps = num_keys
        nat_local, obj_local, local_keys = natural, obj, num_keys
    # Host-side static: at the default infinite budget the projection stage
    # is skipped entirely (capacity=None), keeping Algorithm 3 bit-exact.
    capacity = (
        cluster.capacity_vector() if cluster.has_finite_capacity else None
    )
    ctx = PolicyContext(
        rtt=rtt, object_bytes=obj_local, capacity_bytes=capacity, params=params
    )
    # Host-side static: with no enabled ServiceConfig the contention
    # pre-pass is absent from the compiled program entirely — the exact
    # pre-contention bits (goldens pinned by tests/test_service_time.py).
    contention = _contention_kwargs(cluster, policy.read_mode, daemon_interval)
    # Host-side static: with no enabled RoutingConfig the directory tier is
    # absent from the compiled program entirely — the exact pre-routing
    # bits (goldens pinned by tests/test_routing.py).
    routing = _routing_kwargs(cluster, num_keys - shard.pad)
    # Host-side statics: with attribution/flight off (the defaults —
    # normalize_telemetry collapses disabled sub-configs to None) their
    # leaves stay None, the scan emits NO extra ys, and the compiled
    # program is structurally identical to the pre-provenance engine.
    acfg = None if telemetry is None else telemetry.attribution
    fcfg = None if telemetry is None else telemetry.flight

    num_chunks = -(-r // daemon_interval)
    pad = num_chunks * daemon_interval - r
    # Host-side static: with no enabled FaultConfig the membership timeline,
    # degraded-mode pricing, and repair bookkeeping are absent from the
    # compiled program entirely — the exact no-fault bits (goldens pinned by
    # tests/test_faults.py). The [C, N] schedule constants embed in the
    # program and each scan iteration dynamic-indexes its own chunk row.
    fault = _fault_kwargs(cluster, num_chunks)

    if trace_mode == "streamed":
        # No materialised trace: the scan consumes only chunk indices and
        # each body iteration regenerates its own request window in-scan.
        pk = pn = pr = pv = None
        chunked = None
        xs = jnp.arange(num_chunks, dtype=jnp.int32)
    else:
        def padded(x: Array) -> Array:
            if pad:
                x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
            return x

        pk, pn, pr = padded(keys), padded(nodes), padded(is_read)
        pv = jnp.arange(num_chunks * daemon_interval) < r
        chunked = lambda x: x.reshape(num_chunks, daemon_interval)
        xs = (
            jnp.arange(num_chunks, dtype=jnp.int32),
            chunked(pk),
            chunked(pn),
            chunked(pr),
            chunked(pv),
        )

    hosts0 = _initial_hosts(nat_local, local_keys, n, policy.initial_placement)
    if shard.active and shard.pad:
        # Padded tail keys (ceil-division sharding, satellite of PR 8) are
        # dead weight: never live, never hosted, zero bytes — so no policy
        # sweep, occupancy sample, or counter ever sees them and the
        # non-dividing-K run stays bit-exact with the unsharded engine.
        real = (shard_base + jnp.arange(kps, dtype=jnp.int32)) < (
            num_keys - shard.pad
        )
        hosts0 = hosts0 & real[:, None]
    store = _seed_store(hosts0, local_keys, n)
    if shard.active and shard.pad:
        store = store._replace(live=real)
    pstate = policy.init(store, ctx)
    zero = jnp.float32(0.0)
    # The O(K·N) occupancy sample is a loop constant for inactive policies
    # (a static map never changes) — hoisted out of the scan body; active
    # policies re-sample it per chunk on the frozen-at-chunk-start map.
    # Sharded: occupancy is a cluster property, psum'd at the sample point
    # so the running peak is taken over the GLOBAL per-node vector.
    occ0 = _node_occupancy(store.hosts, obj_local)
    if shard.active:
        occ0 = jax.lax.psum(occ0, shard.axis_name)
    # Whole-trace replay materialises O(R·N) planes (one-hot busy fold,
    # replica/RTT rows); past this element budget (~256 MB of f32) the
    # per-chunk scan's bounded O(B·N) footprint is the safer trade. It
    # needs the materialised trace and an unsharded map by construction.
    static_fast = (
        r * n <= 64 * 1024 * 1024
        and trace_mode == "materialized"
        and not shard.active
        # A frozen map does NOT freeze the routing tier: router caches and
        # consult counters evolve per chunk, so routing always scans.
        and routing is None
        # Faults evolve the availability mask (and crashes mutate even a
        # static policy's map) per chunk, so fault runs always scan too.
        and fault is None
    )
    if not policy.is_active and replay_backend == "jax" and static_fast:
        # Static fast path: a frozen map makes the ENTIRE request path
        # loop-invariant, so the scan collapses into one vectorized pass
        # over the whole trace — no per-chunk program iterations at all
        # (the strong form of the occupancy hoist: the O(K·N) sample AND
        # the [B, N] latency passes leave the loop together). Latencies
        # come from the exact same _chunk_latency expressions (identical
        # f32 bits); the reductions below (matmul busy fold, whole-trace
        # sums) re-associate relative to the scan's per-chunk
        # accumulation, so aggregates are exact for integer-ms latency
        # sums below 2**24 (every golden config) and allclose otherwise
        # (pinned by the seed goldens and tests/test_chunk_replay.py).
        slot_idx = None
        if num_keys * n * 2 <= r:
            # A frozen map also makes latency a pure function of the
            # (key, node, is_read) triple — when that grid is smaller
            # than the trace, evaluate _chunk_latency ONCE per distinct
            # triple and gather per request (elementwise ops on the grid
            # produce the identical f32 bits the direct evaluation would).
            grid = jnp.arange(num_keys * n * 2, dtype=jnp.int32)
            tlat, thit = _chunk_latency(
                store.hosts,
                grid // (n * 2),
                (grid // 2) % n,
                (grid % 2).astype(bool),
                rtt, cluster, policy.read_mode,
            )
            slot_idx = pk * (n * 2) + pn * 2 + pr.astype(jnp.int32)
            lat, read_hits = tlat[slot_idx], thit[slot_idx]
        else:
            lat, read_hits = _chunk_latency(
                store.hosts, pk, pn, pr, rtt, cluster, policy.read_mode
            )
        rho_c = None
        if contention is not None:
            # Contention is NOT loop-invariant even under a frozen map —
            # rho folds over each chunk's own demand — so vmap the
            # canonical pre-pass over the chunk axis and fold the waits
            # into the whole-trace latencies (the grid shortcut above only
            # ever supplies the base RTT-model latency).
            extra_c, rho_c = jax.vmap(
                lambda ck, cn, cr, cv: contention_extra_ms_ref(
                    store.hosts, ck, cn, cr, cv, rtt, obj, **contention
                )
            )(chunked(pk), chunked(pn), chunked(pr), chunked(pv))
            lat = lat + extra_c.reshape(-1)
        if pad:
            # Padding exists only when the trace doesn't divide into
            # chunks; with none, the validity masks are static no-ops.
            lat = jnp.where(pv, lat, 0.0)
            read_hits = read_hits & pv
            read_flags = pr & pv
        else:
            read_flags = pr
        busy = _busy_fold(lat, pn, n)
        lat_sum = jnp.sum(lat)
        hits = jnp.sum(read_hits.astype(jnp.int32))
        reads = jnp.sum(read_flags.astype(jnp.int32))
        leaves = (
            r / (jnp.max(busy) / 1000.0),
            hits.astype(jnp.float32)
            / jnp.maximum(reads.astype(jnp.float32), 1.0),
            _mean_latency(lat_sum, r),
            busy,
            zero,  # repl
            zero,  # drop
            zero,  # evic
            zero,  # cap_evic
            occ0,  # a static map's peak IS the initial-map occupancy
        )
        if telemetry is None:
            return leaves, None
        w = pv.astype(jnp.float32)
        zeros_c = jnp.zeros((num_chunks,), jnp.float32)
        # Latency provenance on the fast path: price the WHOLE padded trace
        # through the component oracle in one pass (the frozen map makes
        # every component loop-invariant too; contention waits, the only
        # chunk-varying term, fold in from the vmapped pre-pass above).
        sa_hist = sa_sum = sf_meta = sf_vals = None
        if acfg is not None or fcfg is not None:
            with jax.named_scope("attribution_components"):
                comps = chunk_components_ref(
                    store.hosts, pk, pn, pr, rtt,
                    read_mode=policy.read_mode,
                    contention_ms=(
                        None if rho_c is None else extra_c.reshape(-1)
                    ),
                    **_replay_scalars(cluster),
                )
                if pad:
                    comps = jnp.where(pv[None, :], comps, 0.0)
        if acfg is not None:
            with jax.named_scope("attribution_fold"):
                sa_hist = attribution_trace_hist(
                    comps, pn * 2 + pr.astype(jnp.int32), w, acfg, n,
                    num_chunks,
                )
                sa_sum = jnp.sum(
                    comps.reshape(
                        NUM_COMPONENTS, num_chunks, daemon_interval
                    ),
                    axis=2,
                ).T
        if fcfg is not None:
            # Same sample plan as the scan body (gathered whole-trace here);
            # the routing column is -1: routing always forces the scan path.
            with jax.named_scope("flight_recorder"):
                cidx = jnp.arange(num_chunks, dtype=jnp.int32)
                jpos = jax.vmap(
                    lambda cc: _flight_positions(fcfg, cc, daemon_interval)
                )(cidx)
                gpos = cidx[:, None] * daemon_interval + jpos
                own = pv[gpos]
                mi32 = lambda v: jnp.where(own, v, 0).astype(jnp.int32)
                sf_meta = jnp.stack(
                    [
                        mi32(gpos),
                        mi32(pk[gpos]),
                        mi32(pn[gpos]),
                        mi32(jnp.full_like(gpos, -1)),
                        mi32(pr[gpos].astype(jnp.int32) | 2),
                    ],
                    axis=2,
                )
                scomps = comps[:, gpos]
                sf_vals = jnp.concatenate(
                    [jnp.sum(scomps, axis=0, keepdims=True), scomps],
                    axis=0,
                ).transpose(1, 2, 0)
        if (
            slot_idx is not None
            and telemetry.backend != "pallas"
            and contention is None
        ):
            # Bin indices are a pure function of the triple too: bucketize
            # the grid once, gather per request (saves R log evals). With
            # contention on, the per-chunk wait breaks the pure-function
            # property, so the full latencies are bucketized directly.
            bin_idx = bin_index(
                tlat, telemetry.lo_ms, telemetry.hi_ms, telemetry.num_bins
            )[slot_idx]
        else:
            bin_idx = None
        ys = TelemetryLeaves(
            # All C per-chunk histograms in ONE flat bincount pass (or the
            # vmapped Pallas kernel under backend="pallas").
            hist=trace_histogram(
                lat, pn * 2 + pr.astype(jnp.int32), w, telemetry, n,
                num_chunks, bin_idx=bin_idx,
            ),
            hits=jnp.sum(chunked(read_hits.astype(jnp.float32)), axis=1),
            reads=jnp.sum(chunked(read_flags.astype(jnp.float32)), axis=1),
            lat_sum=jnp.sum(chunked(lat), axis=1),
            count=jnp.sum(chunked(w), axis=1),
            adds=zeros_c,
            drops=zeros_c,
            expiry_evictions=zeros_c,
            capacity_evictions=zeros_c,
            occupancy=jnp.broadcast_to(occ0, (num_chunks, n)),
            load_factor=(
                jnp.zeros((num_chunks, n), jnp.float32)
                if rho_c is None else rho_c
            ),
            # Routing forces the scan path, so the fast path's routing
            # series are structurally zero (kept [C]-shaped for SimTrace).
            router_consults=zeros_c,
            directory_fetches=zeros_c,
            mis_routes=zeros_c,
            stale_consults=zeros_c,
            stale_age_hist=jnp.zeros(
                (num_chunks, STALE_AGE_BINS), jnp.float32
            ),
            attr_hist=sa_hist,
            attr_sum=sa_sum,
            flight_meta=sf_meta,
            flight_vals=sf_vals,
        )
        return leaves, ys

    if routing is None:
        # None is a legal (empty) pytree carry leaf: with routing off the
        # scan carry is structurally identical to the pre-routing program.
        rcarry0 = None
    else:
        rstate0 = init_router_state(
            store.hosts,
            num_routers=routing["num_routers"],
            cache_entries=routing["cache_entries"],
            publish_lag_chunks=routing["publish_lag_chunks"],
            active=policy.is_active,
            # Faults can pause the publish pipeline (directory home node
            # down), which needs a ring slot to freeze even at lag 0. The
            # forced 1-slot ring is value-identical under full availability.
            force_ring=fault is not None,
        )
        # RouterState + running consult/fetch/mis-route/stale counters.
        rcarry0 = (
            rstate0,
            zero,
            zero,
            zero,
            zero,
        )
    if fault is None:
        # None is a legal (empty) pytree carry leaf: with faults off the
        # scan carry is structurally identical to the pre-fault program.
        fcarry0 = None
    else:
        # wiped-keys mask + running unavailable-read/-write, failover and
        # repair-move counters.
        fcarry0 = (
            jnp.zeros((local_keys,), bool),
            zero,
            zero,
            zero,
            zero,
        )
    # Counters ride the carry as int32: an f32 running total stops being
    # exact past 2**24, which a deployment-size trace passes (and a
    # sharded run would then round differently from an unsharded one).
    izero = jnp.int32(0)
    init = (
        store,
        pstate,
        jnp.zeros((n,), jnp.float32),  # busy
        zero,  # lat_sum
        izero,  # hits
        izero,  # reads
        izero,  # repl
        izero,  # drop
        izero,  # evic (expiry)
        izero,  # cap_evic
        occ0,  # peak (seeded by the initial map)
        rcarry0,
        fcarry0,
    )
    scalars = _replay_scalars(cluster)

    def body(carry, x):
        (
            store, pstate, busy, lat_sum, hits, reads, repl, drop, evic,
            cap_evic, peak, rcarry, fcarry,
        ) = carry
        if trace_mode == "streamed":
            # In-scan trace generation: this chunk's request window, drawn
            # at its global positions — bit-identical to the slices the
            # materialised path reshapes out of the full trace. The final
            # chunk's positions past R are garbage masked by cv.
            c = x
            with jax.named_scope("trace_window"):
                pos = c * daemon_interval + jnp.arange(
                    daemon_interval, dtype=jnp.int32
                )
                cv = pos < r
                ck, cn, cr = _request_window(
                    workload, stream_keys, pos, natural
                )
        else:
            c, ck, cn, cr, cv = x
        if shard.active:
            # Each shard replays only requests for ITS contiguous key
            # block: localise the key id and fold foreign rows into the
            # validity mask (same masking machinery the trace padding
            # uses, so foreign rows cost zero everywhere downstream).
            mine = (ck // kps) == shard_idx
            ck = jnp.where(mine, ck - shard_base, 0)
            cv = cv & mine
        # Degraded-mode serving state for this chunk. With faults off these
        # aliases leave the program byte-identical: served IS cv, hosts_eff
        # IS the authoritative map — every downstream consumer below uses
        # the aliases, so the no-fault compile is structurally unchanged.
        served = cv
        hosts_eff = store.hosts
        avail_c = None
        f_extra = None
        if fault is not None:
            with jax.named_scope("fault_prepass"):
                wiped, f_unav_r, f_unav_w, f_fo, f_rep = fcarry
                avail_c = fault["avail"][c]
                crash_c = fault["crash"][c]
                # One-shot replica wipe at each crash's first chunk: the
                # crashed nodes' copies leave the authoritative map (data
                # loss, not just unreachability). Keys whose row emptied
                # are dark until the daemon re-seeds a live copy —
                # partitions, by contrast, never touch the map here.
                pre_hosts = store.hosts
                post_hosts = pre_hosts & ~crash_c[None, :]
                wiped = wiped | (
                    jnp.any(pre_hosts, axis=-1)
                    & ~jnp.any(post_hosts, axis=-1)
                )
                store = store._replace(hosts=post_hosts)
                # Canonical degraded-mode oracle: per-request unavailability
                # + the write-failover surcharge (reads reprice natively via
                # the hosts_eff mask below — see fault_extra_ms_ref).
                f_extra, unavail, failover = fault_extra_ms_ref(
                    store.hosts, ck, cn, cr, cv, avail_c, rtt,
                    read_mode=policy.read_mode,
                    master=scalars["master"],
                    xfer_write_ms=scalars["xfer_write_ms"],
                    wiped=wiped,
                )
                served = cv & ~unavail
                hosts_eff = store.hosts & avail_c[None, :]
        route = detour_part = fetch_part = None
        if routing is not None:
            # Routing pre-pass on the chunk's frozen map: consult the
            # region's router cache against the PUBLISHED (possibly lagged)
            # ownership view and price fresh hits / stale mis-routes /
            # directory fetches per request (routing_extra_split_ref is the
            # canonical oracle both replay backends consume; the
            # detour/fetch split is row-wise bit-identical to the fused
            # surcharge, so ``route`` carries the exact pre-split bits).
            with jax.named_scope("routing_prepass"):
                rstate, r_consults, r_fetches, r_mis, r_stale = rcarry
                pub_hosts, pub_ver = published_view(
                    rstate, store.hosts, c,
                    publish_lag_chunks=routing["publish_lag_chunks"],
                )
                rb = router_of(cn, routing["num_routers"])
                ent_cached, fresh, age = consult_probe(rstate, rb, ck)
                (
                    detour_part, fetch_part, consult, fetchb, staleb, misb,
                ) = routing_extra_split_ref(
                    # True serving happens on LIVE replicas; the published
                    # view stays the router's (liveness-blind) metadata.
                    # Refused requests never reach a router: valid=served.
                    hosts_eff, pub_hosts, ent_cached, fresh, ck, cn, cr,
                    served, rtt, read_mode=policy.read_mode,
                    home_node=routing["home_node"],
                )
                route = detour_part + fetch_part
        rho = None
        cont_extra = None
        if contention is not None:
            # Queueing pre-pass on the chunk's frozen map: per-request
            # contention wait + per-node load factor (the canonical
            # composition both replay backends consume). Sharded, each
            # shard folds its own requests' demand and the psum inside
            # load_factor_ref assembles the cluster-wide rho.
            with jax.named_scope("contention_prepass"):
                # Demand lands on LIVE serving replicas only, and refused
                # requests contribute no demand (valid=served) — a downed
                # node queues nothing.
                cont_extra, rho = contention_extra_ms_ref(
                    hosts_eff, ck, cn, cr, served, rtt, obj_local,
                    **contention,
                    axis_name=shard.axis_name if shard.active else None,
                )
        extra = cont_extra
        if route is not None:
            # Canonical composition order (routing first, ONE f32 add):
            # every engine and backend folds the same composed surcharge at
            # the same elementwise position, so the bits agree everywhere.
            extra = route if extra is None else route + extra
        if f_extra is not None:
            # Fault surcharge composes FIRST (prepended last) — the write
            # failover delta rides in front of routing + contention. Under
            # full availability the delta is exactly +0.0 per request, so
            # an all-up schedule stays bit-exact with faults off.
            extra = f_extra if extra is None else f_extra + extra
        comps = None
        if acfg is not None or fcfg is not None:
            # Latency provenance: re-price this chunk through the component
            # oracle (identical sub-expressions to chunk_latency_ref, so
            # the per-request component sum reconstructs the total — see
            # tests/test_attribution.py). Invalid/foreign rows zero out.
            with jax.named_scope("attribution_components"):
                comps = chunk_components_ref(
                    hosts_eff, ck, cn, cr, rtt,
                    read_mode=policy.read_mode,
                    contention_ms=cont_extra,
                    routing_detour_ms=detour_part,
                    directory_fetch_ms=fetch_part,
                    avail=avail_c,
                    **scalars,
                )
                comps = jnp.where(served[None, :], comps, 0.0)
        if replay_backend == "pallas":
            # The fused one-pass kernel: gather, latency, hit flags, busy
            # fold — and the telemetry histogram when enabled — in one
            # pass over request tiles (no [B, N] HBM intermediates).
            with jax.named_scope("chunk_replay"):
                (
                    d_busy, chunk_lat, chunk_hits, chunk_reads, chunk_count,
                    hist,
                ) = chunk_replay(
                    # Degraded mode reaches the kernel as DATA: the
                    # avail-masked map + served validity + the composed
                    # extra_ms (fault failover delta included) — no kernel
                    # math changes (see kernels/chunk_replay/ops.py).
                    hosts_eff, ck, cn, cr, served, rtt,
                    read_mode=policy.read_mode,
                    num_bins=0 if telemetry is None else telemetry.num_bins,
                    lo=1.0 if telemetry is None else telemetry.lo_ms,
                    hi=10_000.0 if telemetry is None else telemetry.hi_ms,
                    backend="pallas",
                    extra_ms=extra,
                    **scalars,
                )
            busy = busy + d_busy
        else:
            # Pure-jnp path, op-for-op the pre-fusion engine (bit-exact
            # with the seed goldens).
            with jax.named_scope("chunk_replay"):
                lat, read_hits = _chunk_latency(
                    hosts_eff, ck, cn, cr, rtt, cluster, policy.read_mode
                )
                if extra is not None:
                    # Same elementwise position as chunk_replay_ref: after
                    # the base latency, before the validity mask —
                    # identical bits across engines and backends.
                    lat = lat + extra
                lat = jnp.where(served, lat, 0.0)
            with jax.named_scope("replay_fold"):
                chunk_lat = jnp.sum(lat)
                chunk_hits = jnp.sum(
                    (read_hits & served).astype(jnp.float32)
                )
                chunk_reads = jnp.sum((cr & served).astype(jnp.float32))
                chunk_count = jnp.sum(served.astype(jnp.float32))
                busy = busy + _busy_fold(lat, cn, n)
            hist = None
        with jax.named_scope("replay_fold"):
            lat_sum = lat_sum + chunk_lat
            hits = hits + chunk_hits.astype(jnp.int32)
            reads = reads + chunk_reads.astype(jnp.int32)
        zero = jnp.float32(0.0)
        if fault is not None:
            with jax.named_scope("fault_counters"):
                fsum_f = lambda m: jnp.sum(m.astype(jnp.float32))
                d_unav_r = fsum_f(unavail & cr)
                d_unav_w = fsum_f(unavail & ~cr)
                d_fo = fsum_f(failover)
                d_rep = zero  # set by the repair accounting below
                f_unav_r = f_unav_r + d_unav_r
                f_unav_w = f_unav_w + d_unav_w
                f_fo = f_fo + d_fo
                # Blast-radius point samples on THIS chunk's serving state:
                # the fraction of the keyspace with no live replica
                # (partition-dark or crash-wiped) and the wiped subset.
                # Emitted as already-global fractions — sharded, the key
                # counts psum at the sample point (LEAF_KINDS kind "mean").
                unreach = (
                    jnp.any(store.hosts, axis=-1)
                    & ~jnp.any(hosts_eff, axis=-1)
                ) | wiped
                cnt_u = fsum_f(unreach)
                cnt_w = fsum_f(wiped)
                if shard.active:
                    cnt_u = jax.lax.psum(cnt_u, shard.axis_name)
                    cnt_w = jax.lax.psum(cnt_w, shard.axis_name)
                real_keys = jnp.float32(num_keys - shard.pad)
                d_unreach = cnt_u / real_keys
                d_wiped = cnt_w / real_keys
        # Occupancy is sampled per chunk for EVERY policy, on the same
        # frozen-at-chunk-start map the requests see (the initial placement
        # seeds the peak); for inactive policies the sample is the hoisted
        # loop constant — numerically identical, O(K·N) cheaper per chunk.
        # Crashes mutate the map even under a static policy, so fault runs
        # always re-sample.
        with jax.named_scope("occupancy"):
            if policy.is_active or fault is not None:
                occ = _node_occupancy(store.hosts, obj_local)
                if shard.active:
                    occ = jax.lax.psum(occ, shard.axis_name)
            else:
                occ = occ0
            peak = jnp.maximum(peak, occ)
        if routing is not None:
            # Per-chunk routing diagnostics + decay-LFU cache refresh.
            # Consulted entries re-sync to the PUBLISHED version — a stale
            # router learns at most the lagged view, never the live map.
            fsum = lambda m: jnp.sum(m.astype(jnp.float32))
            d_consults, d_fetches = fsum(consult), fsum(fetchb)
            d_mis, d_stale = fsum(misb), fsum(staleb)
            d_age = stale_age_fold(age, staleb)
            r_consults = r_consults + d_consults
            r_fetches = r_fetches + d_fetches
            r_mis = r_mis + d_mis
            r_stale = r_stale + d_stale
            rstate = router_cache_update(
                rstate, rb, ck, consult, pub_ver,
                cache_entries=routing["cache_entries"],
                decay=routing["decay"],
                axis_name=shard.axis_name if shard.active else None,
            )
        chunk_moves = (zero, zero, zero, zero)
        if policy.is_active:
            # Algorithm 1 bookkeeping: log usage heuristics per request
            # (sharded: only the shard's own rows fold into its local
            # store — foreign rows are already masked out of cv).
            with jax.named_scope("policy_step"):
                # Down-origin users are offline: their requests leave no
                # demand signal. Dark reads from LIVE origins DO record —
                # that demand is how the daemon learns to repair wiped keys.
                with jax.named_scope("access_log"):
                    demand_valid = (
                        cv if fault is None else cv & avail_c[cn]
                    )
                    store = record_accesses(
                        store, ck, cn, now=c, valid=demand_valid
                    )
                with jax.named_scope("daemon_sweep"):
                    prev_hosts = store.hosts
                    # The daemon sweeps against the chunk's availability
                    # mask: down nodes take no new replicas and their held
                    # copies are dropped from the map (rejoin-resync
                    # semantics).
                    step_ctx = (
                        ctx if fault is None else ctx._replace(
                            avail=avail_c, reseed=hosts0 & wiped[:, None]
                        )
                    )
                    stats, pstate, store = policy_masked_step(
                        policy, pstate, store, c, (c % policy.period) == 0,
                        step_ctx,
                    )
                    repl = repl + stats.adds.astype(jnp.int32)
                    drop = drop + stats.drops.astype(jnp.int32)
                    evic = evic + stats.expiry_evictions.astype(jnp.int32)
                    cap_evic = cap_evic + stats.capacity_evictions.astype(
                        jnp.int32
                    )
            chunk_moves = (
                stats.adds, stats.drops, stats.expiry_evictions,
                stats.capacity_evictions,
            )
            if fault is not None:
                with jax.named_scope("repair_accounting"):
                    # Re-replication audit: replicas the sweep just created
                    # for keys that had lost every live copy (crash-wiped
                    # or partition-dark at chunk start) count as repairs.
                    added = store.hosts & ~prev_hosts
                    lost_live = jnp.any(prev_hosts, axis=-1) & ~jnp.any(
                        prev_hosts & avail_c[None, :], axis=-1
                    )
                    d_rep = jnp.sum(
                        (added & (wiped | lost_live)[:, None]).astype(
                            jnp.float32
                        )
                    )
                    f_rep = f_rep + d_rep
                    # A wiped key heals once any LIVE node holds it again.
                    wiped = wiped & ~jnp.any(
                        store.hosts & avail_c[None, :], axis=-1
                    )
            if routing is not None:
                # Versioned publish: keys the daemon just moved bump their
                # directory version and enter the publish queue; routers
                # see the new owners publish_lag_chunks later. With the
                # directory home node down, versions still bump but the
                # published ring slot freezes (see routing.publish_commit).
                rstate = publish_commit(
                    rstate, publish_mask(prev_hosts, store.hosts),
                    store.hosts, c,
                    publish_lag_chunks=routing["publish_lag_chunks"],
                    daemon_up=(
                        None if fault is None
                        else avail_c[routing["home_node"]]
                    ),
                )
        if telemetry is None:
            ys = None
        else:
            if hist is None:
                # jax replay path: fused bucketize+scatter-add over the
                # chunk (group id = node * 2 + is_read), padding masked by
                # weight 0 — dispatched per TelemetryConfig.backend. The
                # pallas replay path already folded the histogram inside
                # the chunk-replay kernel.
                # Refused (unavailable) requests carry weight 0: latency
                # histograms cover SERVED requests only.
                with jax.named_scope("telemetry_fold"):
                    hist = chunk_histogram(
                        lat, cn * 2 + cr.astype(jnp.int32),
                        served.astype(jnp.float32), telemetry, n,
                    )
            ahist = asum = fmeta = fvals = None
            if acfg is not None:
                # Per-component grouped histograms + per-chunk component
                # sums. ALWAYS the pure-jnp scatter-add, regardless of
                # replay backend — integer counts are bit-exact across
                # jax/pallas by construction.
                with jax.named_scope("attribution_fold"):
                    ahist = attribution_chunk_hist(
                        comps, cn * 2 + cr.astype(jnp.int32),
                        served.astype(jnp.float32), acfg, n,
                    )
                    asum = jnp.sum(comps, axis=1)
            if fcfg is not None:
                # Flight recorder: sample S in-chunk offsets and capture
                # each sampled request's identity + component vector.
                # EVERY field is masked by ownership/validity (zeros
                # otherwise, valid bit 0) — sharded, at most one shard
                # contributes a given slot and psum IS the assembly
                # (LEAF_KINDS kind "records").
                with jax.named_scope("flight_recorder"):
                    jpos = _flight_positions(fcfg, c, daemon_interval)
                    own = served[jpos]
                    gpos = c * daemon_interval + jpos
                    gkey = (
                        ck[jpos] + shard_base if shard.active else ck[jpos]
                    )
                    srouter = (
                        rb[jpos] if routing is not None
                        else jnp.full_like(jpos, -1)
                    )
                    mi32 = lambda v: jnp.where(own, v, 0).astype(jnp.int32)
                    fmeta = jnp.stack(
                        [
                            mi32(gpos),
                            mi32(gkey),
                            mi32(cn[jpos]),
                            mi32(srouter),
                            mi32(cr[jpos].astype(jnp.int32) | 2),
                        ],
                        axis=1,
                    )
                    scomps = comps[:, jpos]  # masked via comps' cv zeroing
                    fvals = jnp.concatenate(
                        [jnp.sum(scomps, axis=0, keepdims=True), scomps],
                        axis=0,
                    ).T
            ys = TelemetryLeaves(
                hist=hist,
                hits=chunk_hits,
                reads=chunk_reads,
                lat_sum=chunk_lat,
                count=chunk_count,
                adds=chunk_moves[0],
                drops=chunk_moves[1],
                expiry_evictions=chunk_moves[2],
                capacity_evictions=chunk_moves[3],
                occupancy=occ,
                load_factor=(
                    jnp.zeros((n,), jnp.float32) if rho is None else rho
                ),
                router_consults=zero if routing is None else d_consults,
                directory_fetches=zero if routing is None else d_fetches,
                mis_routes=zero if routing is None else d_mis,
                stale_consults=zero if routing is None else d_stale,
                stale_age_hist=(
                    jnp.zeros((STALE_AGE_BINS,), jnp.float32)
                    if routing is None else d_age
                ),
                unavailable_reads=zero if fault is None else d_unav_r,
                unavailable_writes=zero if fault is None else d_unav_w,
                failovers=zero if fault is None else d_fo,
                repair_moves=zero if fault is None else d_rep,
                unreachable_frac=zero if fault is None else d_unreach,
                wiped_frac=zero if fault is None else d_wiped,
                attr_hist=ahist,
                attr_sum=asum,
                flight_meta=fmeta,
                flight_vals=fvals,
            )
        rcarry = (
            None if routing is None
            else (rstate, r_consults, r_fetches, r_mis, r_stale)
        )
        fcarry = (
            None if fault is None
            else (wiped, f_unav_r, f_unav_w, f_fo, f_rep)
        )
        return (
            store, pstate, busy, lat_sum, hits, reads, repl, drop, evic,
            cap_evic, peak, rcarry, fcarry,
        ), ys

    (
        (_, _, busy, lat_sum, hits, reads, repl, drop, evic, cap_evic, peak,
         rcarry, fcarry),
        ys,
    ) = jax.lax.scan(body, init, xs)
    routing_totals = () if rcarry is None else tuple(rcarry[1:])
    fault_totals = () if fcarry is None else tuple(fcarry[1:])
    if shard.active:
        # One collective round after the scan assembles the global
        # aggregates from the per-shard partial sums (peak and the
        # telemetry occupancy/load_factor leaves are already global — they
        # were psum'd at the sample point inside the body).
        agg = (
            busy, lat_sum, hits, reads, repl, drop, evic, cap_evic,
        ) + routing_totals + fault_totals
        agg = jax.lax.psum(agg, shard.axis_name)
        busy, lat_sum, hits, reads, repl, drop, evic, cap_evic = agg[:8]
        routing_totals = agg[8:8 + len(routing_totals)]
        fault_totals = agg[8 + len(routing_totals):]
        if ys is not None:
            ys = psum_leaves(ys, shard.axis_name)
    makespan_ms = jnp.max(busy)
    if fault_totals:
        # Served-request mean: unavailable requests produced no latency, so
        # they leave the numerator AND the denominator (throughput keeps
        # dividing the full attempted count — the cluster's offered load).
        served_r = r - fault_totals[0] - fault_totals[1]
        mean_lat = _mean_latency(lat_sum, r, served_r)
        if not routing_totals:
            # SimResult is constructed positionally and the routing
            # counters are a strict prefix of the fault counters — fill
            # their slots with (traced) zeros when only faults are on.
            routing_totals = (zero,) * 4
    else:
        mean_lat = _mean_latency(lat_sum, r)
    return (
        r / (makespan_ms / 1000.0),
        hits.astype(jnp.float32)
        / jnp.maximum(reads.astype(jnp.float32), 1.0),
        mean_lat,
        busy,
        repl,
        drop,
        evic,
        cap_evic,
        peak,
    ) + routing_totals + fault_totals, ys


@lru_cache(maxsize=1)
def _simulate_jit():
    """The jitted single-seed engine, built lazily so importing this
    module never initialises the XLA backend as a side effect.

    The trace buffers ([R] keys/nodes/is_read) are consumed by the
    reshape at the top of _simulate and never read again by the caller
    (run_scenario regenerates the trace per call), so they are donated —
    XLA reuses their HBM for the chunked copies instead of
    double-buffering a whole trace. Donation is a no-op (with a warning)
    on CPU, so it is gated on the backend. The batched/grid engines share
    traces across policy groups and must NOT donate."""
    donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
    return partial(
        jax.jit, static_argnames=_SIM_STATICS, donate_argnums=donate
    )(_simulate)


@partial(jax.jit, static_argnames=_SIM_STATICS)
def _simulate_batch(keys, nodes, is_read, natural, object_bytes, params, **statics):
    """Seed-batched fused engine: vmap over the leading (iteration) axis of
    the trace; the policy's dynamic params are broadcast."""
    return jax.vmap(
        lambda a, b, c, d, e: _simulate(a, b, c, d, e, params, **statics)
    )(keys, nodes, is_read, natural, object_bytes)


@partial(jax.jit, static_argnames=_SIM_STATICS)
def _simulate_grid(keys, nodes, is_read, natural, object_bytes, params, **statics):
    """Policy-grid engine: vmap the policy-parameter axis (leading ``[P]``
    on every ``params`` leaf) around the seed-batched engine — a whole
    same-family head-to-head grid as ONE compiled program, result leaves
    shaped ``[P, S, ...]``."""
    return jax.vmap(
        lambda p: jax.vmap(
            lambda a, b, c, d, e: _simulate(a, b, c, d, e, p, **statics)
        )(keys, nodes, is_read, natural, object_bytes)
    )(params)


@partial(jax.jit, static_argnames=("cfg",))
def _traces_for_seeds(cfg: WorkloadConfig, seeds: Array) -> Trace:
    """Batched trace generation (seed axis leading on every field)."""
    return jax.vmap(lambda s: generate_trace(cfg, s))(seeds)


# Single-seed trace generation, jitted: the eager spelling dispatched ~10
# device ops per call, a measurable slice of a warm 1M-request run (PRNG
# is deterministic, so the jitted trace is bit-identical).
_generate_trace_jit = partial(jax.jit, static_argnames=("cfg",))(generate_trace)

# Per-key state only (natural node + object sizes), for the streamed path:
# O(K) instead of the O(R) trace, same fold_in draws → identical bits.
_generate_key_state_jit = partial(jax.jit, static_argnames=("cfg",))(
    generate_key_state
)


@lru_cache(maxsize=None)
def _sharded_simulate_jit(num_shards: int):
    """The key-sharded engine: ``_simulate`` wrapped in ``shard_map`` over a
    1-D ``Mesh`` with a ``keys`` axis (grown from the ``publish_and_fill``
    2-rank seam in ``core/repartition.py``).

    Every INPUT is replicated (``in_specs=P()``): the O(R) trace (or the
    streamed seed) and the O(K) natural/object_bytes vectors are cheap and
    any shard's requests may reference any key; what shards is the O(K·N)
    per-key STATE built inside ``_simulate`` from each shard's
    ``dynamic_slice``. Outputs are psum-assembled global aggregates, so
    ``out_specs=P()`` (replicated) as well. ``check_vma=False`` because the
    body mixes shard-local intermediates with psum'd results inside a scan,
    which the replication checker cannot prove."""
    from jax.sharding import Mesh, PartitionSpec

    devices = jax.devices()
    if len(devices) < num_shards:
        raise ValueError(
            f"num_shards={num_shards} needs {num_shards} devices, have "
            f"{len(devices)} (CPU: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_shards} before "
            "importing jax)"
        )
    mesh = Mesh(np.array(devices[:num_shards]), ("keys",))
    replicated = PartitionSpec()

    def wrapped(keys, nodes, is_read, natural, object_bytes, params, seed,
                **statics):
        fn = jax.shard_map(
            lambda a, b, c, d, e, f, g: _simulate(a, b, c, d, e, f, g,
                                                  **statics),
            mesh=mesh,
            in_specs=(replicated,) * 7,
            out_specs=replicated,
            check_vma=False,
        )
        return fn(keys, nodes, is_read, natural, object_bytes, params, seed)

    return partial(jax.jit, static_argnames=_SIM_STATICS)(wrapped)


def _check_scale_out(
    caller: str,
    workload: WorkloadConfig,
    cluster: ClusterConfig,
    static,
    trace_mode: str,
    num_shards: int,
) -> None:
    """Host-side validation for the scale-out engine options."""
    if trace_mode not in TRACE_MODES:
        raise ValueError(
            f"{caller}: trace_mode={trace_mode!r}; expected one of "
            f"{TRACE_MODES}"
        )
    if num_shards < 1:
        raise ValueError(f"{caller}: num_shards={num_shards} must be >= 1")
    if num_shards == 1:
        return
    if getattr(type(static), "name", "") == "topk":
        raise ValueError(
            f"{caller}: the topk policy ranks keys with a GLOBAL argsort "
            "and is not supported sharded (num_shards > 1)"
        )
    if cluster.has_finite_capacity:
        raise ValueError(
            f"{caller}: finite capacity_bytes needs the global projection "
            "sort and is not supported sharded (num_shards > 1)"
        )


def run_scenario(
    workload: WorkloadConfig,
    cluster: ClusterConfig,
    policy=None,
    seed: int = 0,
    daemon_interval: int = 1000,
    *,
    telemetry: TelemetryConfig | None = None,
    replay_backend: str = "jax",
    trace_mode: str = "materialized",
    num_shards: int = 1,
) -> SimResult | tuple[SimResult, SimTrace]:
    """Simulate one policy over one generated trace (fused scan engine).

    policy: a ``repro.core.policy`` instance — ``RedynisPolicy(...)``,
        ``StaticPolicy(mode=...)``, ``TopKPolicy(...)``, ... The policy
        carries every decision hyperparameter (H, expiry, decay, period,
        sweep backend); ``daemon_interval`` stays an engine argument (the
        chunking granularity both engines share). The legacy ``Scenario``
        spelling was removed; passing one raises with the replacement.
    telemetry: optional :class:`TelemetryConfig`. When enabled the scan
        additionally accumulates grouped log-bin latency histograms and
        per-chunk convergence series *inside* the fused program and the
        return value becomes ``(SimResult, SimTrace)``; when ``None`` (the
        default) the engine and its results are bit-identical to the
        pre-telemetry code path.
    replay_backend: the per-chunk request-path implementation — ``"jax"``
        (the bit-exact jnp composition, default) or ``"pallas"`` (the
        fused one-pass ``kernels.chunk_replay`` kernel; aggregates are
        allclose, histogram counts bit-exact). See the module docstring.

    Queueing-aware contention rides on the cluster: set
    ``cluster.service=ServiceConfig(...)`` and every request pays the
    M/M/1-style wait on top of its RTT-model latency (see the module
    docstring §Queueing model).

    trace_mode: ``"materialized"`` (default — generate the full ``[R]``
        trace up front, the historical path) or ``"streamed"`` — regenerate
        each chunk's requests *inside* the scan from the same fold_in
        stream, bit-identical results with peak live memory
        O(daemon_interval + K) instead of O(R + K).
    num_shards: shard the key axis across this many devices via
        ``shard_map`` (1 = the degenerate single-device program, compiled
        identically to previous releases). Requires ``num_keys %
        num_shards == 0`` and that many visible devices; the ``topk``
        policy and finite ``capacity_bytes`` need global sorts and are
        rejected sharded. Histogram counts and move counters stay
        bit-exact; f32 reductions (busy, latency sums) re-associate across
        shards and are allclose.
    """
    _check_replay_backend("run_scenario", replay_backend)
    static, params = _prepare(workload, cluster, "run_scenario", policy)
    telemetry = normalize_telemetry(telemetry)
    _check_scale_out(
        "run_scenario", workload, cluster, static, trace_mode, num_shards
    )
    if num_shards > 1:
        # Ceil-division block sharding: a non-dividing K pads the final
        # shard with dead keys (zero bytes, masked out of the live map
        # inside _simulate) so every shard holds the same block length.
        kps = -(-workload.num_keys // num_shards)
        shard = ShardSpec("keys", num_shards, kps * num_shards - workload.num_keys)
    else:
        shard = ShardSpec()
    if trace_mode == "streamed":
        keys = nodes = is_read = None
        natural, object_bytes = _generate_key_state_jit(workload, seed)
        stream_seed = jnp.asarray(seed, jnp.int32)
        stream_workload = workload
    else:
        trace = _generate_trace_jit(workload, seed)
        keys, nodes, is_read = trace.keys, trace.nodes, trace.is_read
        natural, object_bytes = trace.natural_node, trace.object_bytes
        stream_seed = None
        stream_workload = None
    if shard.pad:
        natural = jnp.concatenate(
            [natural, jnp.zeros((shard.pad,), natural.dtype)]
        )
        object_bytes = jnp.concatenate(
            [object_bytes, jnp.zeros((shard.pad,), object_bytes.dtype)]
        )
    engine = (
        _sharded_simulate_jit(num_shards) if shard.active else _simulate_jit()
    )
    leaves, telem = engine(
        keys,
        nodes,
        is_read,
        natural,
        object_bytes,
        params,
        stream_seed,
        cluster=cluster,
        policy=static,
        daemon_interval=daemon_interval,
        telemetry=telemetry,
        replay_backend=replay_backend,
        trace_mode=trace_mode,
        workload=stream_workload,
        shard=shard,
    )
    (
        tput, hit, mean_lat, busy, repl, drop, evic, cap_evic, peak,
        *routing_totals,
    ) = leaves
    result = SimResult(
        float(tput),
        float(hit),
        float(mean_lat),
        np.asarray(busy, dtype=np.float64),
        float(repl),
        float(drop),
        float(evic),
        float(cap_evic),
        np.asarray(peak, dtype=np.float64),
        # Routing counters, then fault counters — each optional block is a
        # strict prefix extension, and the engine zero-fills the routing
        # slots whenever the fault block is present, so the positional
        # tail is always length 0, 4, or 8 and the defaults fill the rest.
        *[float(x) for x in routing_totals],
    )
    if telemetry is None:
        return result
    return result, build_trace(telem, telemetry)


# ---------------------------------------------------------------------------
# Reference engine: the original per-chunk Python loop, kept as the oracle.
# ---------------------------------------------------------------------------


def _reference_engine(
    workload: WorkloadConfig,
    cluster: ClusterConfig,
    static,
    params: dict,
    seed: int,
    daemon_interval: int,
    telemetry: TelemetryConfig | None,
) -> tuple[
    SimResult, TelemetryLeaves | None, np.ndarray | None, np.ndarray | None
]:
    """The retained per-chunk Python loop. Returns ``(result, telemetry
    leaves | None, raw per-request latencies | None, raw per-request
    component matrix | None)`` — the raw latencies are what the
    histogram-quantile tests compare ``np.percentile`` against, and only
    this engine materialises them (the fused scan never leaves the
    device). The raw ``[NUM_COMPONENTS, R]`` component matrix is the
    attribution analogue (present only with attribution/flight enabled)."""
    trace = generate_trace(workload, seed)
    k, n, r = workload.num_keys, workload.num_nodes, workload.num_requests
    rtt = cluster.rtt_matrix()
    capacity = (
        cluster.capacity_vector() if cluster.has_finite_capacity else None
    )
    obj = jnp.asarray(trace.object_bytes, jnp.float32)
    ctx = PolicyContext(
        rtt=rtt, object_bytes=obj, capacity_bytes=capacity, params=params
    )

    hosts0 = _initial_hosts(
        trace.natural_node, k, n, static.initial_placement
    )
    store = _seed_store(hosts0, k, n)
    pstate = static.init(store, ctx)
    contention = _contention_kwargs(cluster, static.read_mode, daemon_interval)
    routing = _routing_kwargs(cluster, k)
    num_chunks = (r + daemon_interval - 1) // daemon_interval
    fault = _fault_kwargs(cluster, num_chunks)
    sc = _replay_scalars(cluster)
    rstate = None
    history: list = []
    if routing is not None:
        rstate = init_router_state(
            store.hosts,
            num_routers=routing["num_routers"],
            cache_entries=routing["cache_entries"],
            publish_lag_chunks=routing["publish_lag_chunks"],
            active=static.is_active,
            force_ring=fault is not None,
        )
    r_consults = r_fetches = r_mis = r_stale = 0.0
    # Fault-run carry: wiped-keys mask + availability/repair counters
    # (Python floats — the reference engine is the float64 oracle).
    wiped = None if fault is None else jnp.zeros((k,), bool)
    unav_r = unav_w = failover_total = repair_total = 0.0

    total_lat = np.zeros((n,), dtype=np.float64)
    hits = 0.0
    reads = 0.0
    lat_sum = 0.0
    repl_moves = 0.0
    drop_moves = 0.0
    evictions = 0.0
    cap_evictions = 0.0
    peak_occ = np.asarray(
        _node_occupancy(store.hosts, obj), dtype=np.float64
    )
    telem: list = []
    raw_lats: list = []
    raw_comps: list = []
    acfg = None if telemetry is None else telemetry.attribution
    fcfg = None if telemetry is None else telemetry.flight

    for c in range(num_chunks):
        lo, hi = c * daemon_interval, min((c + 1) * daemon_interval, r)
        keys = trace.keys[lo:hi]
        nodes = trace.nodes[lo:hi]
        is_read = trace.is_read[lo:hi]
        cv = jnp.ones(keys.shape, bool)

        # Degraded-mode serving state, mirroring the scan body exactly:
        # with faults off these aliases ARE the pre-fault operands.
        served = cv
        hosts_eff = store.hosts
        avail_c = None
        f_extra = None
        if fault is not None:
            avail_c = fault["avail"][c]
            crash_c = fault["crash"][c]
            pre_hosts = store.hosts
            post_hosts = pre_hosts & ~crash_c[None, :]
            wiped = wiped | (
                jnp.any(pre_hosts, axis=-1) & ~jnp.any(post_hosts, axis=-1)
            )
            store = store._replace(hosts=post_hosts)
            f_extra, unavail, failover = fault_extra_ms_ref(
                store.hosts, keys, nodes, is_read, cv, avail_c, rtt,
                read_mode=static.read_mode,
                master=sc["master"],
                xfer_write_ms=sc["xfer_write_ms"],
                wiped=wiped,
            )
            served = cv & ~unavail
            hosts_eff = store.hosts & avail_c[None, :]

        lat, read_hits = _chunk_latency(
            hosts_eff, keys, nodes, is_read, rtt, cluster, static.read_mode
        )
        route = detour_part = fetch_part = None
        if routing is not None:
            # Same routing pre-pass as the fused engine. The published view
            # is reconstructed from a Python history of (hosts, version)
            # chunk-start snapshots: the view at chunk c is the snapshot
            # taken publish_lag_chunks earlier (clamped to the initial map)
            # — exactly what the scan's ring buffer holds.
            lag = routing["publish_lag_chunks"]
            if static.is_active:
                if fault is not None:
                    # Fault runs publish through the REAL ring machinery
                    # (publish_commit below can freeze it while the home
                    # node is down); the slot arithmetic reproduces the
                    # history reconstruction exactly when nothing freezes.
                    pub_hosts, pub_ver = published_view(
                        rstate, store.hosts, c, publish_lag_chunks=lag
                    )
                else:
                    history.append((store.hosts, rstate.ver))
                    pub_hosts, pub_ver = history[max(c - lag, 0)]
            else:
                pub_hosts = store.hosts
                pub_ver = jnp.zeros((k,), jnp.int32)
            rb = router_of(nodes, routing["num_routers"])
            ent_cached, fresh, age = consult_probe(rstate, rb, keys)
            (
                detour_part, fetch_part, consult, fetchb, staleb, misb,
            ) = routing_extra_split_ref(
                hosts_eff, pub_hosts, ent_cached, fresh, keys, nodes,
                is_read, served, rtt,
                read_mode=static.read_mode, home_node=routing["home_node"],
            )
            route = detour_part + fetch_part
        rho = None
        cont_extra = None
        if contention is not None:
            # Same pre-pass, same elementwise position as the fused engine
            # (reference chunks carry no padding — every row is valid).
            cont_extra, rho = contention_extra_ms_ref(
                hosts_eff, keys, nodes, is_read,
                served, rtt, obj, **contention,
            )
        extra = cont_extra
        if route is not None:
            # Canonical composition order (routing first, ONE f32 add).
            extra = route if extra is None else route + extra
        if f_extra is not None:
            # Fault surcharge composes FIRST — same order as the scan body.
            extra = f_extra if extra is None else f_extra + extra
        if extra is not None:
            lat = lat + extra
        if fault is not None:
            # The scan body's validity mask: refused requests cost nothing.
            lat = jnp.where(served, lat, 0.0)
        comps = None
        if acfg is not None or fcfg is not None:
            # Same component oracle as the fused engine, on the same frozen
            # map and pre-pass outputs (reference chunks have no padding).
            comps = chunk_components_ref(
                hosts_eff, keys, nodes, is_read, rtt,
                read_mode=static.read_mode,
                contention_ms=cont_extra,
                routing_detour_ms=detour_part,
                directory_fetch_ms=fetch_part,
                avail=avail_c,
                **sc,
            )
            if fault is not None:
                comps = jnp.where(served[None, :], comps, 0.0)
        total_lat += np.bincount(
            np.asarray(nodes), np.asarray(lat, np.float64), minlength=n
        )
        chunk_lat = float(jnp.sum(lat))
        chunk_hits = float(jnp.sum(read_hits & served))
        chunk_reads = float(jnp.sum(is_read & served))
        lat_sum += chunk_lat
        hits += chunk_hits
        reads += chunk_reads
        c_unav_r = c_unav_w = c_fo = c_rep = 0.0
        c_unreach = c_wiped = 0.0
        if fault is not None:
            c_unav_r = float(jnp.sum(unavail & is_read))
            c_unav_w = float(jnp.sum(unavail & ~is_read))
            c_fo = float(jnp.sum(failover))
            unav_r += c_unav_r
            unav_w += c_unav_w
            failover_total += c_fo
            unreach = (
                jnp.any(store.hosts, axis=-1) & ~jnp.any(hosts_eff, axis=-1)
            ) | wiped
            c_unreach = float(jnp.sum(unreach)) / k
            c_wiped = float(jnp.sum(wiped)) / k

        # Per-chunk occupancy sample on the frozen map, for every policy.
        occ = np.asarray(_node_occupancy(store.hosts, obj), np.float64)
        peak_occ = np.maximum(peak_occ, occ)
        chunk_routing = (0.0, 0.0, 0.0, 0.0)
        age_hist = np.zeros((STALE_AGE_BINS,), np.float64)
        if routing is not None:
            chunk_routing = (
                float(jnp.sum(consult)),
                float(jnp.sum(fetchb)),
                float(jnp.sum(misb)),
                float(jnp.sum(staleb)),
            )
            r_consults += chunk_routing[0]
            r_fetches += chunk_routing[1]
            r_mis += chunk_routing[2]
            r_stale += chunk_routing[3]
            age_hist = np.asarray(stale_age_fold(age, staleb), np.float64)
            rstate = router_cache_update(
                rstate, rb, keys, consult, pub_ver,
                cache_entries=routing["cache_entries"],
                decay=routing["decay"],
            )
        chunk_moves = (0.0, 0.0, 0.0, 0.0)
        if static.is_active:
            # Algorithm 1 bookkeeping: log usage heuristics per request
            # (down-origin users are offline and leave no demand signal).
            store = record_accesses(
                store, keys, nodes, now=c,
                valid=None if fault is None else avail_c[nodes],
            )
            prev_hosts = store.hosts
            if c % static.period == 0:
                step_ctx = (
                    ctx if fault is None else ctx._replace(
                        avail=avail_c, reseed=hosts0 & wiped[:, None]
                    )
                )
                plan, pstate, store = policy_sweep(
                    static, pstate, store, c, step_ctx
                )
                chunk_moves = (
                    float(jnp.sum(plan.to_add)),
                    float(jnp.sum(plan.to_drop)),
                    float(jnp.sum(plan.to_drop & plan.expired[:, None])),
                    float(jnp.sum(plan.capacity_evicted)),
                )
                repl_moves += chunk_moves[0]
                drop_moves += chunk_moves[1]
                evictions += chunk_moves[2]
                cap_evictions += chunk_moves[3]
            if fault is not None:
                # Re-replication audit + wiped-key healing, mirroring the
                # scan body's repair accounting exactly.
                added = store.hosts & ~prev_hosts
                lost_live = jnp.any(prev_hosts, axis=-1) & ~jnp.any(
                    prev_hosts & avail_c[None, :], axis=-1
                )
                c_rep = float(
                    jnp.sum(added & (wiped | lost_live)[:, None])
                )
                repair_total += c_rep
                wiped = wiped & ~jnp.any(
                    store.hosts & avail_c[None, :], axis=-1
                )
            if routing is not None:
                changed = publish_mask(prev_hosts, store.hosts)
                if fault is not None:
                    # The real publish pipeline: versions always bump, the
                    # ring slot freezes while the directory home is down.
                    rstate = publish_commit(
                        rstate, changed, store.hosts, c,
                        publish_lag_chunks=routing["publish_lag_chunks"],
                        daemon_up=avail_c[routing["home_node"]],
                    )
                else:
                    # Versioned publish — same bump the fused engine
                    # applies after its masked policy step.
                    rstate = rstate._replace(
                        ver=rstate.ver + changed.astype(jnp.int32)
                    )
        if telemetry is not None:
            group = nodes * 2 + is_read.astype(jnp.int32)
            # Refused requests carry weight 0 (identical ones when off).
            w = served.astype(jnp.float32)
            ahist = asum = fmeta = fvals = None
            if acfg is not None:
                ahist = np.asarray(
                    attribution_chunk_hist(comps, group, w, acfg, n),
                    np.float64,
                )
                asum = np.asarray(jnp.sum(comps, axis=1), np.float64)
            if fcfg is not None:
                # Same per-chunk sample plan as the scan engine; offsets
                # past this (possibly short, final) chunk's length are
                # masked exactly like the scan masks its padded tail.
                b = int(lat.shape[0])
                jpos = np.asarray(
                    _flight_positions(fcfg, c, daemon_interval)
                )
                jc0 = np.minimum(jpos, b - 1)
                own = (jpos < b) & np.asarray(served)[jc0]
                jc = np.minimum(jpos, b - 1)
                mi = lambda v: np.where(own, v, 0).astype(np.int64)
                router_np = (
                    np.asarray(rb, np.int64) if routing is not None
                    else np.full((b,), -1, np.int64)
                )
                fmeta = np.stack(
                    [
                        mi(lo + jpos),
                        mi(np.asarray(keys)[jc]),
                        mi(np.asarray(nodes)[jc]),
                        mi(router_np[jc]),
                        mi(np.asarray(is_read)[jc].astype(np.int64) | 2),
                    ],
                    axis=1,
                )
                comps_np = np.asarray(comps, np.float64)
                scomps = np.where(own[None, :], comps_np[:, jc], 0.0)
                fvals = np.concatenate(
                    [scomps.sum(axis=0, keepdims=True), scomps], axis=0
                ).T
            telem.append(TelemetryLeaves(
                hist=np.asarray(
                    chunk_histogram(lat, group, w, telemetry, n), np.float64
                ),
                hits=chunk_hits,
                reads=chunk_reads,
                lat_sum=chunk_lat,
                count=(
                    float(lat.shape[0]) if fault is None
                    else float(jnp.sum(served))
                ),
                adds=chunk_moves[0],
                drops=chunk_moves[1],
                expiry_evictions=chunk_moves[2],
                capacity_evictions=chunk_moves[3],
                occupancy=occ,
                load_factor=(
                    np.zeros((n,), np.float64) if rho is None
                    else np.asarray(rho, np.float64)
                ),
                router_consults=chunk_routing[0],
                directory_fetches=chunk_routing[1],
                mis_routes=chunk_routing[2],
                stale_consults=chunk_routing[3],
                stale_age_hist=age_hist,
                unavailable_reads=c_unav_r,
                unavailable_writes=c_unav_w,
                failovers=c_fo,
                repair_moves=c_rep,
                unreachable_frac=c_unreach,
                wiped_frac=c_wiped,
                attr_hist=ahist,
                attr_sum=asum,
                flight_meta=fmeta,
                flight_vals=fvals,
            ))
            raw_lats.append(np.asarray(lat, np.float64))
            if comps is not None:
                raw_comps.append(np.asarray(comps, np.float64))

    makespan_ms = float(total_lat.max())
    served_r = r if fault is None else max(r - unav_r - unav_w, 1.0)
    result = SimResult(
        throughput_ops_s=r / (makespan_ms / 1000.0),
        hit_rate=hits / max(reads, 1.0),
        mean_latency_ms=lat_sum / served_r,
        node_busy_ms=total_lat,
        replication_moves=repl_moves,
        deletion_moves=drop_moves,
        evictions=evictions,
        capacity_evictions=cap_evictions,
        peak_occupancy_bytes=peak_occ,
        router_consults=r_consults,
        directory_fetches=r_fetches,
        mis_routes=r_mis,
        stale_consults=r_stale,
        unavailable_reads=unav_r,
        unavailable_writes=unav_w,
        failovers=failover_total,
        repair_moves=repair_total,
    )
    if telemetry is None:
        return result, None, None, None
    leaves = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *telem)
    raw_c = np.concatenate(raw_comps, axis=1) if raw_comps else None
    return result, leaves, np.concatenate(raw_lats), raw_c


def run_scenario_reference(
    workload: WorkloadConfig,
    cluster: ClusterConfig,
    policy=None,
    seed: int = 0,
    daemon_interval: int = 1000,
    *,
    telemetry: TelemetryConfig | None = None,
) -> SimResult | tuple[SimResult, SimTrace]:
    """Slow-path reference: one host dispatch per chunk, the policy stepped
    with Python control flow. Semantically identical to :func:`run_scenario`
    (same policy protocol, same shared stages, same queueing model via
    ``cluster.service``). With ``telemetry`` the return value becomes
    ``(SimResult, SimTrace)``, and the trace carries ``raw_latency_ms`` —
    the exact per-request latencies (contention wait included) the
    histogram quantiles are validated against."""
    static, params = _prepare(
        workload, cluster, "run_scenario_reference", policy
    )
    telemetry = normalize_telemetry(telemetry)
    result, leaves, raw, raw_c = _reference_engine(
        workload, cluster, static, params, seed, daemon_interval, telemetry
    )
    if telemetry is None:
        return result
    return result, build_trace(
        leaves, telemetry, raw_latency_ms=raw, raw_components=raw_c
    )


def confidence_interval_99(samples: np.ndarray) -> tuple:
    """Mean ± 99% CI half-width (normal approx — matches the paper's error
    bars over repeated iterations).

    ``samples`` is per-seed: a ``[S]`` vector of scalars (the legacy
    throughput use) or an ``[S, ...]`` stack of per-seed statistic vectors —
    e.g. per-seed quantile samples ``[S, Q]`` — reduced along axis 0, in
    which case the mean/half-width come back as arrays of the trailing
    shape. Scalars still return plain floats."""
    samples = np.asarray(samples, dtype=np.float64)
    s = samples.shape[0]
    mean = np.mean(samples, axis=0)
    if s < 2:
        ci = np.zeros_like(mean)
    else:
        sem = np.std(samples, axis=0, ddof=1) / np.sqrt(s)
        ci = 2.576 * sem
    if mean.ndim == 0:
        return float(mean), float(ci)
    return mean, ci


# ---------------------------------------------------------------------------
# Batched experiments: seeds vmapped, same-family policy params vmapped too.
# ---------------------------------------------------------------------------


def _result_from_leaves(leaves, seed_idx: int) -> SimResult:
    (
        tput, hit, mean_lat, busy, repl, drop, evic, cap_evic, peak,
        *routing_totals,
    ) = leaves
    return SimResult(
        float(tput[seed_idx]),
        float(hit[seed_idx]),
        float(mean_lat[seed_idx]),
        np.asarray(busy[seed_idx], dtype=np.float64),
        float(repl[seed_idx]),
        float(drop[seed_idx]),
        float(evic[seed_idx]),
        float(cap_evic[seed_idx]),
        np.asarray(peak[seed_idx], dtype=np.float64),
        *[float(x[seed_idx]) for x in routing_totals],
    )


def _batched_policy_rows(
    policies, wl, cluster, iterations, daemon_interval, telemetry=None,
    replay_backend="jax",
):
    """All policies × all seeds for one workload: same-family policies
    (identical static key) have their dynamic params stacked and the policy
    axis vmapped alongside the seed axis. Returns ``(per-policy
    (aggregate leaves, telemetry leaves | None), number of compiled-program
    invocations)`` — telemetry histograms vmap across seeds (and the policy
    axis) exactly like the aggregates, so each policy row's leaves carry a
    leading ``[S]`` seed axis that merges by summation."""
    traces = _traces_for_seeds(wl, jnp.arange(iterations, dtype=jnp.int32))
    trace_args = (
        traces.keys, traces.nodes, traces.is_read, traces.natural_node,
        traces.object_bytes,
    )
    statics = dict(
        cluster=cluster, daemon_interval=daemon_interval, telemetry=telemetry,
        replay_backend=replay_backend,
    )

    groups: dict = {}  # static key -> list of (position, params)
    for i, pol in enumerate(policies):
        static, params = split_policy(pol)
        groups.setdefault(static, []).append((i, params))

    out: list = [None] * len(policies)
    calls = 0
    for static, members in groups.items():
        if members[0][1] and len(members) > 1:
            # Same family, different knobs: stack each dynamic field into a
            # [P] vector and vmap the policy axis — ONE batched program.
            stacked = {
                key: jnp.asarray([params[key] for _, params in members],
                                 jnp.float32)
                for key in members[0][1]
            }
            leaves = _simulate_grid(
                *trace_args, stacked, policy=static, **statics
            )
            calls += 1
            for p, (i, _) in enumerate(members):
                out[i] = jax.tree_util.tree_map(lambda leaf: leaf[p], leaves)
        else:
            for i, params in members:
                out[i] = _simulate_batch(
                    *trace_args, params, policy=static, **statics
                )
                calls += 1
    return out, calls


def run_experiment(
    read_fractions: tuple[float, ...] = (1.0, 0.9, 0.75, 0.5),
    skewed: bool = False,
    iterations: int = 5,
    num_requests: int = 100_000,
    cluster: ClusterConfig | None = None,
    engine: str = "scan",
    daemon_interval: int = 1000,
    policies=None,
    telemetry: TelemetryConfig | None = None,
    replay_backend: str = "jax",
    **workload_kwargs,
) -> dict:
    """Paper Figure 2/3 grid — and its generalisation to arbitrary policy
    head-to-heads — with 99% CIs over repeated iterations.

    policies: required list of ``repro.core.policy`` instances. The result
        dict maps each policy's label (``describe_policy``) to its
        read-fraction rows under ``"policies"``, each row carrying the
        aggregate stats AND the per-seed :class:`SimResult`s under
        ``"results"``. Same-family policies (e.g. four ``RedynisPolicy``
        variants) are batched into ONE compiled program per read ratio: the
        dynamic-parameter axis is vmapped alongside the seed axis
        (``"num_batched_calls"`` reports how many programs actually ran).
        The legacy no-``policies`` scenario grid was removed with the
        ``scenario=`` shim.
    engine: "scan" (default) runs every CI iteration as one vmapped
        program; "reference" replays the retained per-chunk Python loop
        (the oracle the equivalence tests pin the scan engine to).
    replay_backend: the scan engine's per-chunk request path —
        ``"jax"`` (bit-exact jnp, default) or ``"pallas"`` (the fused
        ``kernels.chunk_replay`` kernel). The reference engine is the jnp
        oracle by definition and rejects ``"pallas"``.
    telemetry: optional :class:`TelemetryConfig`. When enabled each row
        additionally reports ``p99_latency_ms`` with a ``p99_ci99`` CI band
        (99% CI over the per-seed interpolated P99 samples), the canonical
        ``quantiles`` block, and a seed-merged :class:`SimTrace` under
        ``"trace"`` (histograms summed across seeds — the merge the
        associativity tests pin).
    """
    if cluster is None:
        cluster = ClusterConfig()
    workload_kwargs.setdefault("num_nodes", cluster.num_nodes)
    if engine not in ("scan", "reference"):
        raise ValueError(f"unknown engine {engine!r}")
    _check_replay_backend("run_experiment", replay_backend)
    if engine == "reference" and replay_backend != "jax":
        raise ValueError(
            "run_experiment: engine='reference' is the jnp oracle and only "
            "supports replay_backend='jax'"
        )
    telemetry = normalize_telemetry(telemetry)

    if policies is None:
        raise ValueError(
            "run_experiment: policies is required — e.g. policies=["
            "StaticPolicy(mode='remote'), RedynisPolicy()] (the legacy "
            "scenario grid was removed with the scenario= shim)"
        )
    named = []
    for pol in policies:
        _reject_scenario("run_experiment", pol)
        pol = pol.resolve(cluster.num_nodes)
        pol.validate(cluster.num_nodes)
        named.append((describe_policy(pol), pol))
    if len({label for label, _ in named}) != len(named):
        raise ValueError(
            f"duplicate policy labels in {[l for l, _ in named]}; "
            f"vary at least one hyperparameter per entry"
        )
    labels = [label for label, _ in named]
    pols = [pol for _, pol in named]

    out: dict = {
        "skewed": skewed,
        "read_fractions": list(read_fractions),
        "policies": {label: [] for label in labels},
        "num_batched_calls": 0,
    }
    table = out["policies"]
    for rf in read_fractions:
        wl = WorkloadConfig(
            num_requests=num_requests,
            read_fraction=rf,
            skewed=skewed,
            **workload_kwargs,
        )
        _check_topology(wl, cluster)
        if engine == "reference":
            per_policy, per_telem = [], []
            for pol in pols:
                static, params = split_policy(pol)
                results, leaves = [], []
                for it in range(iterations):
                    res, lv, _, _ = _reference_engine(
                        wl, cluster, static, params, it, daemon_interval,
                        telemetry,
                    )
                    results.append(res)
                    leaves.append(lv)
                per_policy.append(results)
                per_telem.append(
                    None if telemetry is None
                    else jax.tree_util.tree_map(
                        lambda *xs: np.stack(xs), *leaves
                    )
                )
        else:
            rows_leaves, calls = _batched_policy_rows(
                pols, wl, cluster, iterations, daemon_interval, telemetry,
                replay_backend,
            )
            out["num_batched_calls"] += calls
            per_policy = [
                [_result_from_leaves(sim, it) for it in range(iterations)]
                for sim, _ in rows_leaves
            ]
            per_telem = [telem for _, telem in rows_leaves]
        for label, results, telem in zip(labels, per_policy, per_telem):
            samples = np.array([r.throughput_ops_s for r in results])
            mean, ci = confidence_interval_99(samples)
            # hit_rate is the seed MEAN with its own 99% CI band — the
            # seed-0 point estimate it replaces was biased for any policy
            # whose convergence depends on the trace (EXPERIMENTS.md
            # §Engine-performance notes the change).
            hit_mean, hit_ci = confidence_interval_99(
                np.array([r.hit_rate for r in results])
            )
            row = {
                "read_fraction": rf,
                "throughput": mean,
                "ci99": ci,
                "hit_rate": hit_mean,
                "hit_rate_ci99": hit_ci,
                "mean_latency_ms": float(
                    np.mean([r.mean_latency_ms for r in results])
                ),
                "results": results,
            }
            if telemetry is not None:
                # Per-seed P99 samples feed the CI band; the row's trace is
                # the seed-merged aggregate (histograms sum across seeds).
                p99s = np.array([
                    leaves_quantile(
                        jax.tree_util.tree_map(lambda a, s=s: a[s], telem),
                        telemetry, 0.99,
                    )
                    for s in range(iterations)
                ])
                p99_mean, p99_ci = confidence_interval_99(p99s)
                trace = build_trace(merge_leaves(telem), telemetry)
                row["p99_latency_ms"] = p99_mean
                row["p99_ci99"] = p99_ci
                row["quantiles"] = trace.tail_summary()
                row["trace"] = trace
            table[label].append(row)
    return out
