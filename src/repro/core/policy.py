"""First-class placement policies — the decision algorithm as a value.

Redynis's contribution is a *decision algorithm* (Algorithm 3), but the
policy space around it is wide: size-aware sharding scores placements by
bytes moved per latency saved (Didona & Zwaenepoel, 1802.00696), Crux
preserves locality structurally (1405.0637), and classic caches rank by
decayed frequency. This module makes the decision rule a first-class,
composable value instead of a hardwired enum + kwarg sprawl:

    policy = RedynisPolicy(h=0.2, decay=0.9)
    run_scenario(workload, cluster, policy)

Protocol
--------
A placement policy is a registered ``NamedTuple`` of hyperparameters with
two pure hooks::

    init(store, ctx)                 -> state          # pytree, () if stateless
    decide(state, store, f, now, ctx) -> (owners, state)

``f`` is the ``[K, N]`` ownership-fraction matrix (eq. 1), computed once by
the engine; ``owners`` is the *candidate* replica set. Both hooks are pure
fixed-shape JAX, so the fused ``lax.scan`` simulation engine calls the
policy inside its scan body with zero Python in the hot loop. A policy
whose backend already produces ``f`` (the Pallas ownership-sweep kernel)
may set ``supplies_fractions`` and implement
``decide_fused(state, store, now, ctx) -> (owners, f, state)`` — the
engine then skips its own fractions stage and reuses the supplied ``f``
for scoring, with no ``[K, N]`` recompute. Every policy
then flows through the same shared stages, in order::

    fractions ─► decide ─► live/expiry mask ─► capacity projection ─► plan

so expiry semantics and per-node replica-byte budgets apply uniformly — a
policy cannot opt out of the cluster's memory limits.

Static vs dynamic hyperparameters
---------------------------------
Each policy class names its ``DYNAMIC_FIELDS`` — float-valued knobs (H,
decay, K, thresholds) that are *traced*, not compiled in. ``split_policy``
divides an instance into a hashable static key (used as the jit static) and
a dict of traced params, so (a) re-running with a new H never recompiles,
and (b) ``run_experiment(policies=[...])`` can stack the params of
same-family policies and ``vmap`` the policy axis alongside the seed axis —
a whole head-to-head grid as one batched program. Inside ``decide``,
dynamic knobs are read from ``ctx.params``, never from ``self``.

Built-ins
---------
========== ==================================================================
redynis    Algorithm 3 (ownership coefficient), bit-exact with the legacy
           OPTIMIZED path; ``backend="pallas"`` routes the [K, N] pass
           through the ``kernels.ownership_sweep`` TPU kernel.
static     The non-adaptive baselines: ``mode="local" | "remote" |
           "replicated"`` absorb the three legacy ``Scenario`` enum values.
topk       Replicate the K globally hottest keys everywhere; cold keys
           collapse to their modal request source.
costgreedy Size-aware greedy growth: add a replica where the RTT saved per
           byte moved clears a threshold (the Didona & Zwaenepoel angle).
decaylfu   Redynis's eligibility rule on an exponentially-decayed access
           EMA — a *stateful* policy that tracks traffic shifts without
           mutating the metadata layer's raw counters.
sizeaware  Minos-style small/large pools (1802.00696): small objects
           replicate wide (cheap bytes, served anywhere), large objects
           keep a bounded fanout of their hottest request sources — the
           placement that spreads queueing load under ``ServiceConfig``
           contention instead of piling large-object demand on one node.
========== ==================================================================

Registry: ``POLICIES`` maps names to classes; ``parse_policy`` turns CLI
specs (``"redynis:h=0.2,decay=0.9"``, or bare aliases ``"local"``) into
instances for the benchmark drivers.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.costmodel import project_capacity
from repro.core.metadata import MetadataStore
from repro.core.ownership import (
    eligible_from_fractions,
    ownership_fraction,
    validate_coefficient,
)
from repro.core.placement import (
    SWEEP_BACKENDS,
    PlacementPlan,
    SweepStats,
    redynis_candidates,
)

__all__ = [
    "POLICIES",
    "PolicyContext",
    "RedynisPolicy",
    "StaticPolicy",
    "TopKPolicy",
    "CostGreedyPolicy",
    "DecayLFUPolicy",
    "SizeAwarePolicy",
    "register_policy",
    "make_policy",
    "parse_policy",
    "split_policy",
    "describe_policy",
    "policy_repr",
    "policy_sweep",
    "policy_masked_step",
    "publish_mask",
]


class _Vmapped:
    """Singleton placeholder a dynamic field holds on a *static key* — the
    actual value travels in ``PolicyContext.params`` (traced / vmapped)."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return "<vmapped>"


VMAPPED = _Vmapped()


class PolicyContext(NamedTuple):
    """Trace-time inputs every policy hook receives.

    rtt:            ``[N, N]`` pairwise RTT matrix (ms).
    object_bytes:   ``[K]`` per-key payload size.
    capacity_bytes: ``[N]`` per-node replica-byte budget, or ``None`` when
                    every budget is infinite (the projection stage then
                    compiles away — bit-exact Algorithm 3).
    params:         dict of this policy's *dynamic* hyperparameters
                    (``DYNAMIC_FIELDS``), traced scalars — or ``[P]``
                    vectors under the batched policy-grid vmap.
    avail:          ``[N] bool`` node availability this chunk under failure
                    injection, or ``None`` (the default) for the fault-free
                    program — the sweep then compiles with no membership
                    mask at all (the bit-exact golden path).
    reseed:         ``[K, N] bool`` durable-store re-seed rows under failure
                    injection: the initial placement of every key that lost
                    its last replica to a crash, empty for every other key
                    (``None`` with faults off).
    """

    rtt: Array
    object_bytes: Array
    capacity_bytes: Array | None
    params: dict
    avail: Array | None = None
    reseed: Array | None = None


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------

POLICIES: dict[str, type] = {}
_ALIASES: dict[str, tuple[str, dict]] = {
    # Bare scenario-style shorthands for CLI ergonomics.
    "local": ("static", {"mode": "local"}),
    "remote": ("static", {"mode": "remote"}),
    "replicated": ("static", {"mode": "replicated"}),
}


def register_policy(cls: type) -> type:
    """Class decorator: add ``cls`` to the registry under ``cls.name``.

    Also makes equality/hash *class-aware*: NamedTuple inherits plain tuple
    semantics, under which two different policy families with equal field
    tuples would compare equal — colliding as grouping keys and, fatally,
    in the jit static-argument cache.
    """

    def __eq__(self, other):
        return type(other) is type(self) and tuple.__eq__(self, other) is True

    def __ne__(self, other):
        return not self.__eq__(other)

    def __hash__(self):
        return hash((type(self).__qualname__,) + tuple(self))

    cls.__eq__ = __eq__
    cls.__ne__ = __ne__
    cls.__hash__ = __hash__
    POLICIES[cls.name] = cls
    return cls


def make_policy(name: str, **kwargs):
    """Instantiate a registered policy by name (aliases resolved)."""
    if name in _ALIASES:
        base, preset = _ALIASES[name]
        return POLICIES[base](**{**preset, **kwargs})
    if name not in POLICIES:
        known = sorted(set(POLICIES) | set(_ALIASES))
        raise ValueError(f"unknown policy {name!r}; expected one of {known}")
    return POLICIES[name](**kwargs)


def _coerce(text: str):
    low = text.lower()
    if low == "none":
        return None
    if low in ("true", "false"):
        return low == "true"
    for cast in (int, float):
        try:
            return cast(text)
        except ValueError:
            pass
    return text


def parse_policy(spec: str):
    """Parse a CLI policy spec: ``name[:k=v,...]``.

    Examples: ``"redynis"``, ``"redynis:h=0.2,decay=0.9"``,
    ``"topk:k=50"``, ``"static:mode=remote"``, or the bare aliases
    ``"local" | "remote" | "replicated"``.
    """
    name, _, tail = spec.partition(":")
    kwargs = {}
    if tail:
        for item in tail.split(","):
            key, eq, value = item.partition("=")
            if not eq:
                raise ValueError(
                    f"bad policy spec {spec!r}: expected k=v, got {item!r}"
                )
            kwargs[key.strip()] = _coerce(value.strip())
    return make_policy(name.strip(), **kwargs)


def split_policy(policy) -> tuple:
    """Split an instance into ``(static_key, params)``.

    ``static_key`` is the policy with every dynamic field replaced by the
    ``VMAPPED`` sentinel — hashable, shared across a whole family, the jit
    static. ``params`` maps each dynamic field to a float, ready to be
    traced (or stacked into ``[P]`` vectors for a batched policy grid).
    """
    dyn = type(policy).DYNAMIC_FIELDS
    params = {name: float(getattr(policy, name)) for name in dyn}
    static = policy._replace(**{name: VMAPPED for name in dyn})
    return static, params


def _label_fields(policy) -> list[str]:
    """``k=v`` parts for labels/reprs: non-default fields, plus any field
    the class lists in ``ALWAYS_LABEL`` (e.g. StaticPolicy's mode, so the
    'local' baseline is never an ambiguous bare ``static``)."""
    cls = type(policy)
    always = getattr(cls, "ALWAYS_LABEL", ())
    return [
        f"{name}={getattr(policy, name)!r}"
        for name in cls._fields
        if name in always
        or getattr(policy, name) != cls._field_defaults.get(name)
    ]


def describe_policy(policy) -> str:
    """Compact registry-name label: ``redynis(h=0.2)``."""
    parts = _label_fields(policy)
    return f"{type(policy).name}({', '.join(parts)})" if parts else type(policy).name


def policy_repr(policy) -> str:
    """Constructor spelling — the exact replacement quoted by the
    ``scenario=`` deprecation warning."""
    return f"{type(policy).__name__}({', '.join(_label_fields(policy))})"


def _validate_common(policy, *, decay=None, period=None, backend=None):
    if decay is not None and not (0.0 < decay <= 1.0):
        raise ValueError(f"{type(policy).__name__}: decay must be in (0, 1], got {decay}")
    if period is not None and period < 1:
        raise ValueError(f"{type(policy).__name__}: period must be >= 1, got {period}")
    if backend is not None and backend not in SWEEP_BACKENDS:
        raise ValueError(
            f"{type(policy).__name__}: unknown sweep backend {backend!r}; "
            f"expected one of {SWEEP_BACKENDS}"
        )


# ---------------------------------------------------------------------------
# Built-in policies.
# ---------------------------------------------------------------------------


@register_policy
class RedynisPolicy(NamedTuple):
    """Paper Algorithm 3: replicate where the ownership fraction clears H.

    Bit-exact with the legacy ``Scenario.OPTIMIZED`` path (pinned by the
    seed goldens and the policy-equivalence tests). ``h=None`` resolves to
    the starvation-safe maximum ``1/n`` at run time.
    """

    h: float | None = None  # ownership coefficient (eq. 2); None -> 1/n
    expiry: int = 0  # ticks before untouched keys are purged; 0 disables
    decay: float = 1.0  # post-sweep count decay (1.0 = paper's raw counters)
    period: int = 1  # sweep every `period`-th tick
    backend: str = "jax"  # "jax" | "pallas" ([K, N] pass routing)

    name = "redynis"
    DYNAMIC_FIELDS = ("h", "decay")
    is_active = True
    read_mode = "map"
    initial_placement = "offsite"

    def resolve(self, num_nodes: int) -> "RedynisPolicy":
        return self if self.h is not None else self._replace(h=1.0 / num_nodes)

    def validate(self, num_nodes: int) -> None:
        validate_coefficient(self.h, num_nodes)
        if self.expiry < 0:
            raise ValueError(
                f"expiry must be a non-negative tick count, got {self.expiry} "
                f"(0 disables expiry)"
            )
        _validate_common(
            self, decay=self.decay, period=self.period, backend=self.backend
        )

    @property
    def supplies_fractions(self) -> bool:
        """The Pallas kernel emits ``f`` alongside ``owners``; the engine
        skips its own fractions stage and reuses it (no [K, N] recompute —
        the PR-2 'f output feeds the scoring' property, preserved)."""
        return self.backend == "pallas"

    def init(self, store: MetadataStore, ctx: PolicyContext):
        return ()

    def decide_fused(self, state, store: MetadataStore, now, ctx: PolicyContext):
        from repro.kernels.ownership_sweep.ops import ownership_sweep

        owners, _, _, _, f = ownership_sweep(
            store.access_counts,
            store.hosts,
            store.live,
            store.last_access,
            now,
            h=ctx.params["h"],
            expiry=self.expiry,
        )
        return owners, f, state

    def decide(self, state, store: MetadataStore, f: Array, now, ctx: PolicyContext):
        if self.backend == "pallas":
            owners, _, state = self.decide_fused(state, store, now, ctx)
            return owners, state
        return redynis_candidates(store, f, ctx.params["h"]), state


@register_policy
class StaticPolicy(NamedTuple):
    """The non-adaptive baselines (paper §9), absorbing the legacy enum:

    mode="local"       the idealised everything-local scenario
    mode="remote"      no local replicas ever; every op pays a WAN hop
    mode="replicated"  naive full replication — local reads, broadcast writes

    Static policies never run the daemon loop: the replica map is frozen at
    its initial placement and the whole decision machinery compiles away.
    """

    mode: str = "local"

    name = "static"
    MODES = ("local", "remote", "replicated")
    DYNAMIC_FIELDS = ()
    ALWAYS_LABEL = ("mode",)
    is_active = False

    @property
    def read_mode(self) -> str:
        return {"local": "ideal", "remote": "no_local", "replicated": "map"}[
            self.mode
        ]

    @property
    def initial_placement(self) -> str:
        return "offsite" if self.mode == "remote" else "full"

    def resolve(self, num_nodes: int) -> "StaticPolicy":
        return self

    def validate(self, num_nodes: int) -> None:
        if self.mode not in self.MODES:
            raise ValueError(
                f"unknown StaticPolicy mode {self.mode!r}; expected one of "
                f"{self.MODES}"
            )

    def init(self, store: MetadataStore, ctx: PolicyContext):
        return ()

    def decide(self, state, store: MetadataStore, f: Array, now, ctx: PolicyContext):
        return store.hosts, state  # never called (is_active=False); identity


@register_policy
class TopKPolicy(NamedTuple):
    """Replicate the K globally hottest keys on every node; each cold key
    collapses to its modal request source (the node issuing most of its
    accesses). A global-frequency baseline: no per-node fractions, so it
    wins when hotness is global (every node hammers the same keys) and loses
    to Redynis when hotness is regional."""

    k: float = 100.0  # number of globally-hottest keys to replicate
    decay: float = 1.0
    period: int = 1

    name = "topk"
    DYNAMIC_FIELDS = ("k", "decay")
    is_active = True
    read_mode = "map"
    initial_placement = "offsite"

    def resolve(self, num_nodes: int) -> "TopKPolicy":
        return self

    def validate(self, num_nodes: int) -> None:
        if self.k < 0:
            raise ValueError(f"k must be non-negative, got {self.k}")
        _validate_common(self, decay=self.decay, period=self.period)

    def init(self, store: MetadataStore, ctx: PolicyContext):
        return ()

    def decide(self, state, store: MetadataStore, f: Array, now, ctx: PolicyContext):
        counts = store.access_counts
        total = jnp.sum(counts, axis=-1)
        # Dense rank by total accesses, hottest first; ties break to the
        # lower key id (argsort is stable), so the cut is deterministic.
        order = jnp.argsort(-total)
        ranks = jnp.zeros_like(order).at[order].set(
            jnp.arange(total.shape[0], dtype=order.dtype)
        )
        touched = total > 0
        # The rank cut alone would sweep zero-traffic keys into the hot set
        # whenever k exceeds the touched count — silence keeps placement.
        hot = (ranks < ctx.params["k"]) & touched
        modal = (
            jnp.arange(counts.shape[1], dtype=jnp.int32)
            == jnp.argmax(counts, axis=-1).astype(jnp.int32)[:, None]
        )
        cold = jnp.where(touched[:, None], modal, store.hosts)
        owners = jnp.where(hot[:, None], jnp.ones_like(store.hosts), cold)
        return owners, state


@register_policy
class CostGreedyPolicy(NamedTuple):
    """Size-aware greedy growth (after Didona & Zwaenepoel, 1802.00696):
    add a replica of O on x when the RTT milliseconds its traffic would save
    per KiB moved clears ``min_saved_ms_per_kib``. Saved ms = accesses from
    x × (current nearest-replica RTT − local RTT). The policy only *grows*
    the replica set — shrinking is delegated to the shared expiry and
    capacity-projection stages, so a finite budget evicts the coldest
    replicas exactly as for every other policy.

    Memory note: scoring materialises a ``[K, N, N]`` intermediate; sized
    for simulator-scale K (thousands), not the 1e6-key daemon benches.
    """

    min_saved_ms_per_kib: float = 100.0
    decay: float = 1.0
    period: int = 1

    name = "costgreedy"
    DYNAMIC_FIELDS = ("min_saved_ms_per_kib", "decay")
    is_active = True
    read_mode = "map"
    initial_placement = "offsite"

    def resolve(self, num_nodes: int) -> "CostGreedyPolicy":
        return self

    def validate(self, num_nodes: int) -> None:
        if self.min_saved_ms_per_kib < 0:
            raise ValueError(
                f"min_saved_ms_per_kib must be non-negative, got "
                f"{self.min_saved_ms_per_kib}"
            )
        _validate_common(self, decay=self.decay, period=self.period)

    def init(self, store: MetadataStore, ctx: PolicyContext):
        return ()

    def decide(self, state, store: MetadataStore, f: Array, now, ctx: PolicyContext):
        rtt = ctx.rtt
        hosts = store.hosts
        # Current read cost from node x: nearest replica in the key's set;
        # an empty set pays the topology's worst RTT (backing-store fetch).
        cost_now = jnp.min(
            jnp.where(hosts[:, None, :], rtt[None, :, :], jnp.inf), axis=-1
        )  # [K, N]
        cost_now = jnp.where(jnp.isfinite(cost_now), cost_now, jnp.max(rtt))
        local = jnp.diagonal(rtt)  # [N]
        saved_ms = store.access_counts.astype(jnp.float32) * jnp.maximum(
            cost_now - local[None, :], 0.0
        )
        per_kib = saved_ms / (ctx.object_bytes[:, None] / 1024.0)
        owners = hosts | (per_kib >= ctx.params["min_saved_ms_per_kib"])
        return owners, state


@register_policy
class DecayLFUPolicy(NamedTuple):
    """Redynis's eligibility rule computed on an exponentially-decayed
    access EMA the policy keeps in its *own state* (the metadata layer's
    raw counters stay untouched). Each sweep folds the accesses since the
    last committed sweep into ``ema = alpha * ema + delta`` and replicates
    where the EMA fraction clears H — reactive to traffic shifts like the
    engine-level count decay, but per-policy and composable."""

    h: float | None = None  # eligibility threshold on EMA fractions
    alpha: float = 0.5  # EMA retention per sweep (1.0 = raw counts)
    period: int = 1

    name = "decaylfu"
    DYNAMIC_FIELDS = ("h", "alpha")
    is_active = True
    read_mode = "map"
    initial_placement = "offsite"

    def resolve(self, num_nodes: int) -> "DecayLFUPolicy":
        return self if self.h is not None else self._replace(h=1.0 / num_nodes)

    def validate(self, num_nodes: int) -> None:
        validate_coefficient(self.h, num_nodes)
        if not (0.0 < self.alpha <= 1.0):
            raise ValueError(f"alpha must be in (0, 1], got {self.alpha}")
        _validate_common(self, period=self.period)

    def init(self, store: MetadataStore, ctx: PolicyContext):
        shape = store.access_counts.shape
        ema = jnp.zeros(shape, jnp.float32)
        prev = store.access_counts.astype(jnp.float32)
        return (ema, prev)

    def decide(self, state, store: MetadataStore, f: Array, now, ctx: PolicyContext):
        ema, prev = state
        counts = store.access_counts.astype(jnp.float32)
        ema = ema * ctx.params["alpha"] + (counts - prev)
        f_ema = ownership_fraction(ema)
        eligible = eligible_from_fractions(f_ema, ema, ctx.params["h"])
        touched = jnp.sum(ema, axis=-1) > 0
        owners = jnp.where(touched[:, None], eligible, store.hosts)
        return owners, (ema, counts)


@register_policy
class SizeAwarePolicy(NamedTuple):
    """Minos-style size-aware sharding (Didona & Zwaenepoel, 1802.00696):
    partition keys into small/large *pools* by object size and condition
    replica admission on the pool.

    Small objects (``object_bytes <= size_threshold_bytes``) replicate on
    every node once touched — they are cheap to hold and any node can then
    serve them locally, keeping the small-request pool free of queueing
    behind large transfers. Large objects keep a bounded fanout: the
    ``large_fanout`` nodes issuing most of their accesses (their modal
    source always included), which spreads each large object's service
    demand across its hottest sources instead of concentrating it — under
    ``ServiceConfig`` contention this is exactly the placement that keeps
    per-node load factors low, where ``costgreedy``'s per-KiB threshold
    refuses to replicate large objects at all and piles their demand onto
    a single serving node. Untouched keys keep their current placement."""

    size_threshold_bytes: float = 4096.0  # small/large pool cut
    large_fanout: float = 2.0  # replicas kept per touched large object
    decay: float = 1.0  # post-sweep count decay (shared stage)
    period: int = 1

    name = "sizeaware"
    DYNAMIC_FIELDS = ("size_threshold_bytes", "large_fanout", "decay")
    is_active = True
    read_mode = "map"
    initial_placement = "offsite"

    def resolve(self, num_nodes: int) -> "SizeAwarePolicy":
        return self

    def validate(self, num_nodes: int) -> None:
        if self.size_threshold_bytes < 0:
            raise ValueError(
                f"size_threshold_bytes must be non-negative, got "
                f"{self.size_threshold_bytes}"
            )
        if self.large_fanout < 1:
            raise ValueError(
                f"large_fanout must be >= 1 (every touched large object "
                f"keeps at least its modal source), got {self.large_fanout}"
            )
        _validate_common(self, decay=self.decay, period=self.period)

    def init(self, store: MetadataStore, ctx: PolicyContext):
        return ()

    def decide(self, state, store: MetadataStore, f: Array, now, ctx: PolicyContext):
        counts = store.access_counts  # [K, N]
        k, n = counts.shape
        touched = jnp.sum(counts, axis=-1) > 0
        small = ctx.object_bytes <= ctx.params["size_threshold_bytes"]
        # Per-key dense rank of nodes by access count, hottest first
        # (argsort is stable, so ties break to the lower node id).
        order = jnp.argsort(-counts, axis=-1)
        ranks = jnp.zeros_like(order).at[
            jnp.arange(k)[:, None], order
        ].set(jnp.arange(n, dtype=order.dtype)[None, :])
        # The rank cut alone would admit zero-traffic nodes whenever the
        # fanout exceeds a key's distinct sources — require real traffic,
        # but always keep the modal source (fanout >= 1 by validate()).
        modal = (
            jnp.arange(n, dtype=jnp.int32)
            == jnp.argmax(counts, axis=-1).astype(jnp.int32)[:, None]
        )
        narrow = ((ranks < ctx.params["large_fanout"]) & (counts > 0)) | modal
        pool = jnp.where(small[:, None], jnp.ones_like(store.hosts), narrow)
        owners = jnp.where(touched[:, None], pool, store.hosts)
        return owners, state


# ---------------------------------------------------------------------------
# The shared policy engine: decide + uniform expiry / capacity stages.
# ---------------------------------------------------------------------------


def _policy_sweep(
    policy,
    state,
    store: MetadataStore,
    now: Array | int,
    ctx: PolicyContext,
) -> tuple[PlacementPlan, object, MetadataStore]:
    """One full decision pass for any policy: fractions → ``decide`` →
    live/expiry mask → capacity projection → plan + store update (+ the
    policy's optional post-sweep count decay). ``policy`` must be a *static
    key* from :func:`split_policy`; dynamic knobs come from ``ctx.params``.
    """
    counts, hosts, live = store.access_counts, store.hosts, store.live

    if getattr(policy, "supplies_fractions", False):
        # Stages 1+2 fused: the policy's backend already produces f (the
        # Pallas ownership-sweep kernel) — reuse it, no [K, N] recompute.
        owners, f, state = policy.decide_fused(state, store, now, ctx)
    else:
        f = ownership_fraction(counts)  # stage 1: eq. 1, shared
        owners, state = policy.decide(state, store, f, now, ctx)  # stage 2

    # Stage 2b (failure injection): a crash-wiped key the policy assigns no
    # node — nobody has read it, and silence keeps its emptied placement —
    # is re-seeded from the durable backing store onto its initial
    # placement (masked below like any owner: it waits for a live node).
    if ctx.reseed is not None:
        dark = ~jnp.any(owners, axis=-1)
        owners = jnp.where(dark[:, None], ctx.reseed, owners)

    # Stage 3 (uniform): dead keys own nothing; expiry purges silence.
    expiry = getattr(policy, "expiry", 0)
    if expiry and expiry > 0:
        expired = live & (
            (jnp.asarray(now, jnp.int32) - store.last_access) > expiry
        )
    else:
        expired = jnp.zeros_like(live)
    owners = owners & live[:, None] & ~expired[:, None]

    # Stage 3b (failure injection, compiled away at ctx.avail=None): the
    # daemon never places replicas on down nodes, and drops the copies a
    # down node still notionally holds — a rejoining node resyncs from
    # scratch, and a *crashed* node's lost copies get re-seeded onto live
    # nodes here, capped by the same capacity projection as any other move.
    if ctx.avail is not None:
        owners = owners & ctx.avail[None, :]

    # Stage 4 (uniform): per-node replica-byte budgets. Skipped entirely at
    # infinite budget (ctx.capacity_bytes is None — host-side static).
    if ctx.capacity_bytes is None:
        evicted = jnp.zeros_like(owners)
    else:
        owners, evicted, _ = project_capacity(
            owners, hosts, f, ctx.object_bytes, ctx.capacity_bytes
        )

    plan = PlacementPlan(
        owners=owners,
        to_add=owners & ~hosts,
        to_drop=hosts & ~owners,
        expired=expired,
        f=f,
        capacity_evicted=evicted,
    )
    new_counts = jnp.where(expired[:, None], 0, counts)
    if "decay" in ctx.params:
        # floor(count * decay) is an exact identity at decay == 1.0 for any
        # count below 2**24 (int32 -> f32 is exact there), so the legacy
        # static decay==1.0 fast path and this traced form are bit-equal.
        new_counts = jnp.floor(
            new_counts.astype(jnp.float32) * ctx.params["decay"]
        ).astype(jnp.int32)
    new_store = store._replace(
        hosts=owners,
        live=live & ~expired,
        access_counts=new_counts,
    )
    return plan, state, new_store


policy_sweep = partial(jax.jit, static_argnames=("policy",))(_policy_sweep)


def publish_mask(old_hosts: Array, new_hosts: Array) -> Array:
    """Per-key ``[K] bool``: which keys' replica rows a daemon step actually
    changed — the *versioned publish* a placement commit emits toward the
    directory tier (``repro.kvsim.routing``). Due-masked steps that commit
    nothing publish nothing, so directory versions only advance on real
    placement changes."""
    return jnp.any(old_hosts != new_hosts, axis=-1)


def policy_masked_step(
    policy,
    state,
    store: MetadataStore,
    now: Array | int,
    due: Array,
    ctx: PolicyContext,
) -> tuple[SweepStats, object, MetadataStore]:
    """Scan-compatible policy step: the sweep is always computed but only
    *committed* (store AND policy state) where ``due`` — the policy-generic
    analogue of :func:`repro.core.placement.masked_step`, safe inside
    ``lax.scan`` / ``vmap`` bodies with no data-dependent control flow."""
    plan, new_state, new_store = _policy_sweep(policy, state, store, now, ctx)
    new_state, new_store = jax.tree_util.tree_map(
        lambda a, b: jnp.where(due, a, b), (new_state, new_store), (state, store)
    )
    gate = lambda v: jnp.where(due, v.astype(jnp.float32), 0.0)
    stats = SweepStats(
        adds=gate(jnp.sum(plan.to_add)),
        drops=gate(jnp.sum(plan.to_drop)),
        expiry_evictions=gate(jnp.sum(plan.to_drop & plan.expired[:, None])),
        capacity_evictions=gate(jnp.sum(plan.capacity_evicted)),
    )
    return stats, new_state, new_store
