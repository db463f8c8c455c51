"""Key metadata store — device-resident analogue of the paper's metadata layer.

The paper keeps, per key (§6.2)::

    { totalAccessCount, hosts (set), hostAccesses (dict), lastAccessedDate }

Here the whole metadata cluster is a struct-of-dense-arrays over a fixed key
universe of size K and N nodes, so every operation the paper performs per-key
in O(1) becomes a vectorised O(batch) device op with no host round-trips:

    access_counts [K, N] int32   -- hostAccesses  (g(O, x))
    hosts         [K, N] bool    -- replica set
    last_access   [K]    int32   -- lastAccessedDate, in *ticks* (see note)
    live          [K]    bool    -- key exists
    home          [K]    int32   -- node that first stored the key (write home)

``totalAccessCount`` is derived (= access_counts.sum(-1)) rather than stored,
removing a redundancy in the paper's format.

Timestamp note: the paper stores epoch-milliseconds (int64). JAX defaults to
32-bit ints; rather than force x64 globally we store *relative ticks* (ms
since store creation, or step indices) — semantics are identical for the
expiry test ``now - last_access > expiry``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import Array

__all__ = [
    "MetadataStore",
    "create_store",
    "record_accesses",
    "record_new_keys",
    "local_hit",
    "owner_of",
]


class MetadataStore(NamedTuple):
    """Dense metadata for K keys × N nodes (paper §6.2, vectorised)."""

    access_counts: Array  # [K, N] int32
    hosts: Array  # [K, N] bool
    last_access: Array  # [K] int32 ticks
    live: Array  # [K] bool
    home: Array  # [K] int32

    @property
    def num_keys(self) -> int:
        return self.access_counts.shape[0]

    @property
    def num_nodes(self) -> int:
        return self.access_counts.shape[1]

    def total_access_count(self) -> Array:
        """The paper's ``totalAccessCount`` (derived)."""
        return jnp.sum(self.access_counts, axis=-1)


def create_store(num_keys: int, num_nodes: int) -> MetadataStore:
    """Empty metadata cluster for a fixed key universe."""
    return MetadataStore(
        access_counts=jnp.zeros((num_keys, num_nodes), dtype=jnp.int32),
        hosts=jnp.zeros((num_keys, num_nodes), dtype=bool),
        last_access=jnp.zeros((num_keys,), dtype=jnp.int32),
        live=jnp.zeros((num_keys,), dtype=bool),
        home=jnp.zeros((num_keys,), dtype=jnp.int32),
    )


def record_accesses(
    store: MetadataStore,
    keys: Array,
    nodes: Array,
    now: Array | int,
    weights: Array | None = None,
    valid: Array | None = None,
) -> MetadataStore:
    """Fold a batch of accesses into the metadata (Algorithm 1's bookkeeping).

    keys, nodes: ``[B]`` int32 — key accessed / node that served the request.
    weights: optional ``[B]`` int32 multiplicity (e.g. tokens per route).
    valid: optional ``[B]`` bool — False rows are ignored entirely (counts
        *and* last_access). Lets fixed-shape callers (``lax.scan`` over padded
        request chunks) fold partial batches without host-side slicing.

    The paper updates metadata per request over HTTP; we fold the whole batch
    with one scatter-add — this is the "non-blocking, off the critical path"
    property taken to its limit (the update *is* part of the fused step).
    The scatter indexes the ``[K, N]`` counts by ``(key, node)`` in their own
    shape and leaves their layout to XLA. A flat ``key * N + node`` index
    needs a row-major view, into and out of which a TPU relays the counts
    through a layout that pads the N-wide rows to 128 lanes: four passes over
    the padded array per call, together over three times the scatter's own
    cost. An integer scatter-add gives the same counts in either form.
    """
    k, n = store.access_counts.shape
    if weights is None:
        weights = jnp.ones_like(keys, dtype=jnp.int32)
    sel, col = keys, nodes
    if valid is not None:
        # Out-of-range rows drop below; the counts drop a row by its node,
        # not its key (why: PERF.md §6, the access log's fold into [K, N]).
        sel = jnp.where(valid, keys, k)
        col = jnp.where(valid, nodes, n)
    counts = store.access_counts.at[keys, col].add(
        weights.astype(jnp.int32), mode="drop"
    )
    last = store.last_access.at[sel].max(
        jnp.asarray(now, dtype=jnp.int32), mode="drop"
    )
    return store._replace(access_counts=counts, last_access=last)


def record_new_keys(
    store: MetadataStore,
    keys: Array,
    nodes: Array,
    now: Array | int,
) -> MetadataStore:
    """Algorithm 1 'metadata == null' branch / Algorithm 2 local store.

    New keys are stored on the node that received the request (its *home*),
    a metadata object is generated, and the access is logged. Existing keys
    are left untouched (mask applied), so replaying a mixed batch is safe.
    """
    is_new = ~store.live[keys]
    sel = jnp.where(is_new, keys, store.num_keys)  # out-of-range rows drop
    hosts = store.hosts.at[sel, nodes].set(True, mode="drop")
    live = store.live.at[sel].set(True, mode="drop")
    home = store.home.at[sel].set(nodes.astype(jnp.int32), mode="drop")
    store = store._replace(hosts=hosts, live=live, home=home)
    return record_accesses(store, keys, nodes, now)


def local_hit(store: MetadataStore, keys: Array, nodes: Array) -> Array:
    """``[B]`` bool — does the requesting node hold a replica? (Alg. 1 test)."""
    return store.hosts[keys, nodes] & store.live[keys]


def owner_of(store: MetadataStore, keys: Array) -> Array:
    """An arbitrary-but-deterministic owner for remote fetches: the home node
    if it still holds a replica, else the lowest-indexed replica holder."""
    home_ok = store.hosts[keys, store.home[keys]]
    first = jnp.argmax(store.hosts[keys], axis=-1)
    return jnp.where(home_ok, store.home[keys], first).astype(jnp.int32)
