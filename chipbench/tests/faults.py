"""Faults planted in the timed path, for the tests that show the
comparison catches them. Each maps a name to the attribute of
``repro.kvsim.simulate`` it replaces and a maker of the broken version
from the original. The engine reads these module globals while it traces,
so compiled programs must be cleared before and after a fault."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.placement import SweepStats


def _unchanged_step(orig):
    def step(policy, state, store, now, due, ctx):
        zero = jnp.float32(0.0)
        return SweepStats(zero, zero, zero, zero), state, store
    return step


def _half_chunk(orig):
    def replay(hosts, keys, nodes, is_read, *args):
        lat, hit = orig(hosts, keys, nodes, is_read, *args)
        kept = jnp.arange(keys.shape[0]) < keys.shape[0] // 2
        return jnp.where(kept, lat, 0.0), hit & kept
    return replay


def _one_answer_altered(orig):
    def replay(hosts, keys, nodes, is_read, *args):
        lat, hit = orig(hosts, keys, nodes, is_read, *args)
        return lat.at[0].add(1.0), hit
    return replay


FAULTS = {
    "step_returns_state_unchanged": ("policy_masked_step", _unchanged_step),
    "half_of_each_chunk_left_out": ("_chunk_latency", _half_chunk),
    "one_answer_altered": ("_chunk_latency", _one_answer_altered),
}


def plant(simulate, fault: str):
    """Replace the attribute ``fault`` breaks; returns the original."""
    name, broken = FAULTS[fault]
    orig = getattr(simulate, name)
    setattr(simulate, name, broken(orig))
    jax.clear_caches()
    return orig

