"""Plain reference of the simulated deployment, independent of the program.

It imports nothing of ``repro``. From the configuration file and the
traffic mix alone it

1. draws the whole request trace at once with ``jax.random`` (the stream
   the configuration's generator defines: six subkeys of
   ``PRNGKey(seed)``; YCSB hotspot keys, natural regions from the region
   weights, reads with the mix's read fraction),
2. replays it chunk by chunk, one chunk per daemon period, against the
   replica map frozen at the chunk's start:
   a read is served by the nearest replica (service + RTT, plus the
   transfer charge when no local copy exists; an empty map pays the worst
   RTT); a write commits locally when the requester is the key's only
   owner, else relays to the master and completes when the farthest
   non-master owner acknowledges (Algorithm 2),
3. counts every request against (key, requesting node) and then sweeps:
   a node owns a key iff its share of the key's accesses is at least H,
   keys never accessed keep their map (Algorithm 3); replica memory is
   unbounded, so nothing is evicted,
4. returns per-chunk counts, latency sums, busy time per node, replica
   counts per node and the grouped log-bin latency histogram.

The state is kept as ``[N, K]`` planes. ``control=True`` breaks the first
guarantee the configuration states: every chunk is served from the map
one sweep older than its own, as a pipeline that overlaps the sweep with
the next chunk's replay would serve it.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Deployment(NamedTuple):
    num_keys: int
    num_nodes: int
    rtt: tuple
    service_ms: float
    master: int
    xfer_read_ms: float
    xfer_write_ms: float
    record_bytes: float
    region_weights: tuple
    affinity: float
    hot_fraction: float
    hot_traffic: float
    read_fraction: float
    num_requests: int
    interval: int
    h: float
    num_bins: int
    lo_ms: float
    hi_ms: float


# The semantics this reference implements; a configuration that states
# others is refused rather than compared against the wrong thing. The
# engine options and the sweep backend choose an implementation, not a
# result.
POLICY = "RedynisPolicy"
POLICY_PARAMS = {"h", "backend"}
ENGINE_OPTIONS = {"trace_mode", "replay_backend"}


def deployment(config: dict, traffic: dict) -> Deployment:
    placement = config["placement"]
    params = placement.get("params", {})
    unmodelled = {
        "policy": placement["policy"] != POLICY,
        "policy parameters": not set(params) <= POLICY_PARAMS,
        "initial placement": placement["initial"] != "offsite",
        "engine options": not set(config.get("engine", {})) <= ENGINE_OPTIONS,
        "bounded replica memory": config["replica_memory_bytes"] is not None,
    }
    if any(unmodelled.values()):
        raise ValueError("the reference does not model the configuration's "
                         + ", ".join(k for k, v in unmodelled.items() if v))
    per_kb = float(config["transfer_ms_per_kb"])
    record = float(config["record_bytes"])
    tel = config["telemetry"]
    return Deployment(
        num_keys=int(config["records"]),
        num_nodes=len(config["rtt_ms"]),
        rtt=tuple(tuple(float(x) for x in row) for row in config["rtt_ms"]),
        service_ms=float(config["service_ms"]),
        master=int(config["master"]),
        xfer_read_ms=per_kb * record / 1024.0,
        xfer_write_ms=per_kb * (record + float(config["key_bytes"])) / 1024.0,
        record_bytes=record,
        region_weights=tuple(float(w) for w in config["region_weights"]),
        affinity=float(config["affinity"]),
        hot_fraction=float(config["hotspot_data_fraction"]),
        hot_traffic=float(config["hotspot_opn_fraction"]),
        read_fraction=float(traffic["read_fraction"]),
        num_requests=int(traffic["requests_per_call"]),
        interval=int(traffic["requests_per_sweep"]),
        h=float(params["h"]),
        num_bins=int(tel["num_bins"]),
        lo_ms=float(tel["lo_ms"]),
        hi_ms=float(tel["hi_ms"]),
    )


def _trace(d: Deployment, seed):
    """The whole trace: keys, requesting nodes, read flags ``[R]`` and each
    key's natural region ``[K]``."""
    r, k, n = d.num_requests, d.num_keys, d.num_nodes
    k_hot, k_key, k_node, k_rw, k_nat, k_other = jax.random.split(
        jax.random.PRNGKey(seed), 6
    )
    w = jnp.asarray(d.region_weights, jnp.float32)
    natural = jax.random.choice(k_nat, n, (k,), p=w / jnp.sum(w))
    natural = natural.astype(jnp.int32)
    n_hot = max(1, int(k * d.hot_fraction))
    pick_hot = jax.random.bernoulli(k_hot, d.hot_traffic, (r,))
    hot = jax.random.randint(k_key, (r,), 0, n_hot)
    cold = jax.random.randint(jax.random.fold_in(k_key, 1), (r,), n_hot, k)
    keys = jnp.where(pick_hot, hot, cold).astype(jnp.int32)
    # A request arrives at its key's natural region with probability
    # `affinity`, else at one of the other regions, uniformly.
    stay = jax.random.bernoulli(k_node, d.affinity, (r,))
    shift = jax.random.randint(k_other, (r,), 1, n)
    home = natural[keys]
    nodes = jnp.where(stay, home, (home + shift) % n).astype(jnp.int32)
    is_read = jax.random.bernoulli(k_rw, d.read_fraction, (r,))
    return keys, nodes, is_read, natural


def _latency(d: Deployment, rep, nodes, is_read, rtt):
    """Per-request latency ``[B]`` and local-copy flags from the replica
    rows ``rep [B, N]`` the requests see."""
    b, n = rep.shape
    ids = jnp.arange(n)
    local = jnp.take_along_axis(rep, nodes[:, None], axis=1)[:, 0]
    row = rtt[nodes]
    nearest = jnp.min(jnp.where(rep, row, jnp.inf), axis=1)
    nearest = jnp.where(jnp.isfinite(nearest), nearest, jnp.max(rtt))
    read = d.service_ms + nearest + jnp.where(local, 0.0, d.xfer_read_ms)
    sole = local & (jnp.sum(rep, axis=1) == 1)
    relay = jnp.where(nodes == d.master, 0.0, rtt[nodes, d.master])
    post = jnp.max(
        jnp.where(rep & (ids != d.master)[None, :], rtt[d.master][None, :],
                  0.0),
        axis=1,
    )
    cost = relay + post
    cost = cost + jnp.where(cost > 0, d.xfer_write_ms, 0.0)
    write = d.service_ms + jnp.where(sole, 0.0, cost)
    return jnp.where(is_read, read, write), local


def _bin(d: Deployment, lat):
    """Bin 0 below ``lo``, bin ``B-1`` at or above ``hi``, log-spaced bins
    between."""
    inner = d.num_bins - 2
    edges = d.lo_ms * (d.hi_ms / d.lo_ms) ** (np.arange(inner + 1) / inner)
    return jnp.searchsorted(
        jnp.asarray(edges, jnp.float32), lat, side="right"
    ).astype(jnp.int32)


@partial(jax.jit, static_argnames=("d", "control"))
def _simulate(d: Deployment, seed, control: bool):
    r, k, n, b = d.num_requests, d.num_keys, d.num_nodes, d.interval
    chunks = -(-r // b)
    keys, nodes, is_read, natural = _trace(d, seed)
    pad = chunks * b - r
    valid = jnp.arange(chunks * b) < r
    chunked = lambda x: jnp.pad(x, (0, pad)).reshape(chunks, b)
    rtt = jnp.asarray(d.rtt, jnp.float32)
    hosts0 = jnp.arange(n)[:, None] == ((natural + 1) % n)[None, :]
    counts0 = jnp.zeros((n, k), jnp.int32)
    groups = 2 * n * d.num_bins

    def chunk(carry, xs):
        hosts, counts, older = carry
        ck, cn, cr, cv = xs
        seen = older if control else hosts
        lat, local = _latency(d, seen[:, ck].T, cn, cr, rtt)
        lat = jnp.where(cv, lat, 0.0)
        own = cn[:, None] == jnp.arange(n)[None, :]
        hist = jnp.zeros((groups,), jnp.int32).at[
            (cn * 2 + cr.astype(jnp.int32)) * d.num_bins + _bin(d, lat)
        ].add(cv.astype(jnp.int32))
        out = dict(
            hits=jnp.sum(local & cr & cv, dtype=jnp.int32),
            reads=jnp.sum(cr & cv, dtype=jnp.int32),
            count=jnp.sum(cv, dtype=jnp.int32),
            lat_sum=jnp.sum(lat),
            busy=jnp.sum(jnp.where(own, lat[:, None], 0.0), axis=0),
            hist=hist,
            replicas=jnp.sum(hosts, axis=1, dtype=jnp.int32),
        )
        counts = counts.at[cn, ck].add(cv.astype(jnp.int32))
        total = jnp.sum(counts, axis=0)
        share = counts.astype(jnp.float32) / jnp.maximum(total, 1).astype(
            jnp.float32
        )
        owners = share >= jnp.float32(d.h)
        touched = total > 0
        hottest = jnp.arange(n)[:, None] == jnp.argmax(counts, axis=0)[None, :]
        starved = touched & ~jnp.any(owners, axis=0)
        owners = jnp.where(starved[None, :], hottest, owners)
        owners = jnp.where(touched[None, :], owners, hosts)
        out.update(
            adds=jnp.sum(owners & ~hosts, dtype=jnp.int32),
            drops=jnp.sum(hosts & ~owners, dtype=jnp.int32),
            evicted=jnp.int32(0),
        )
        return (owners, counts, hosts), out

    xs = (chunked(keys), chunked(nodes), chunked(is_read), chunked(valid))
    _, per_chunk = jax.lax.scan(chunk, (hosts0, counts0, hosts0), xs)
    return per_chunk


def simulate(d: Deployment, seed: int, control: bool = False) -> dict:
    """Per-chunk results on the host: ``hits``, ``reads``, ``count``,
    ``lat_sum``, ``adds``, ``drops``, ``evicted`` ``[C]``, ``busy`` and
    ``replica_bytes`` ``[C, N]``, and ``hist`` ``[C, 2N, bins]``."""
    out = jax.device_get(_simulate(d, jnp.int32(seed), control))
    res = {key: np.asarray(v, np.float64) for key, v in out.items()}
    res["hist"] = res["hist"].reshape(-1, 2 * d.num_nodes, d.num_bins)
    res["replica_bytes"] = res.pop("replicas") * d.record_bytes
    return res
