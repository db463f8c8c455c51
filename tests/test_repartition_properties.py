"""Property tests for the repartition execution layer (core/repartition.py):
`plan_moves` schedules are unique / capacity-bounded / hottest-consistent,
and `publish_and_fill` with ``axis_name=None`` matches the real ``shard_map``
collective path on a 2-rank CPU mesh (via the ``run_multi_rank`` conftest
fixture — a subprocess, so the main pytest process stays single-device)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.placement import PlacementPlan
from repro.core.repartition import create_cache, plan_moves, publish_and_fill


def random_plan(rng, k, n):
    owners = rng.random((k, n)) < 0.5
    home = rng.integers(0, n, size=k).astype(np.int32)
    owners[np.arange(k), home] = True  # every key keeps its home replica
    prev = rng.random((k, n)) < 0.3
    return (
        PlacementPlan(
            owners=jnp.asarray(owners),
            to_add=jnp.asarray(owners & ~prev),
            to_drop=jnp.asarray(prev & ~owners),
            expired=jnp.zeros((k,), bool),
        ),
        jnp.asarray(home),
        owners,
        home,
    )


@pytest.mark.parametrize("seed", range(8))
def test_plan_moves_slots_unique_and_within_capacity(seed):
    rng = np.random.default_rng(seed)
    k, n, cap = int(rng.integers(4, 40)), int(rng.integers(2, 6)), int(rng.integers(1, 9))
    plan, home, owners, home_np = random_plan(rng, k, n)
    moves = plan_moves(plan, home, cap, max_moves=k, object_bytes=8.0)

    slot_ids = np.asarray(moves.slot_ids)
    assert slot_ids.shape == (n, cap)
    for r in range(n):
        row = slot_ids[r]
        filled = row[row >= 0]
        # unique, in-range, and only objects this rank wants but doesn't home
        assert len(set(filled.tolist())) == len(filled)
        wanted = set(np.nonzero(owners[:, r] & (home_np != r))[0].tolist())
        assert set(filled.tolist()) <= wanted
        # truncation fills exactly min(|wanted|, capacity) slots
        assert len(filled) == min(len(wanted), cap)


@pytest.mark.parametrize("seed", range(8))
def test_plan_moves_priority_keeps_hottest(seed):
    """With a priority vector the truncated schedule must keep exactly the
    top-capacity hottest wanted objects (ties broken by object id)."""
    rng = np.random.default_rng(100 + seed)
    k, n, cap = int(rng.integers(6, 40)), int(rng.integers(2, 5)), int(rng.integers(1, 6))
    plan, home, owners, home_np = random_plan(rng, k, n)
    heat = rng.integers(0, 5, size=k).astype(np.float32)  # few levels -> ties
    moves = plan_moves(
        plan, home, cap, max_moves=k, object_bytes=8.0,
        priority=jnp.asarray(heat),
    )
    slot_ids = np.asarray(moves.slot_ids)
    for r in range(n):
        wanted = np.nonzero(owners[:, r] & (home_np != r))[0]
        expect = sorted(wanted.tolist(), key=lambda i: (-heat[i], i))[:cap]
        got = [i for i in slot_ids[r].tolist() if i >= 0]
        assert got == expect, (r, heat.tolist())


def test_plan_moves_publishes_every_add():
    rng = np.random.default_rng(7)
    k, n = 16, 3
    plan, home, _, _ = random_plan(rng, k, n)
    moves = plan_moves(plan, home, 8, max_moves=k, object_bytes=4.0)
    published = set(int(i) for i in np.asarray(moves.publish_ids) if i >= 0)
    added = set(np.nonzero(np.asarray(plan.to_add).any(axis=1))[0].tolist())
    assert published == added
    np.testing.assert_allclose(
        float(moves.moved_bytes), 4.0 * len(added), rtol=1e-6
    )


def test_publish_and_fill_fills_desired_slots():
    """Single-process (axis_name=None) semantics: every desired slot whose
    object was published this sweep holds the correct payload."""
    rng = np.random.default_rng(11)
    k, n, cap = 12, 2, 6
    plan, home, owners, home_np = random_plan(rng, k, n)
    moves = plan_moves(plan, home, cap, max_moves=k, object_bytes=4.0)
    values = jnp.arange(k * 3, dtype=jnp.float32).reshape(k, 3)
    for r in range(n):
        filled = publish_and_fill(
            create_cache(cap, (3,)), moves, values,
            jnp.arange(k, dtype=jnp.int32), rank=r,
        )
        ids = np.asarray(filled.ids)
        desired = np.asarray(moves.slot_ids)[r]
        published = set(int(i) for i in np.asarray(moves.publish_ids) if i >= 0)
        for slot, want in enumerate(desired.tolist()):
            if want >= 0 and want in published:
                assert ids[slot] == want
                np.testing.assert_allclose(
                    np.asarray(filled.data[slot]), np.asarray(values[want])
                )


SHARD_MAP_SCRIPT = r"""
from functools import partial
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.placement import PlacementPlan
from repro.core.repartition import create_cache, plan_moves, publish_and_fill

k, n, cap, d = 12, 2, 5, 3
rng = np.random.default_rng(0)
owners = rng.random((k, n)) < 0.6
home = (np.arange(k) % n).astype(np.int32)  # even split: k/2 objects per rank
owners[np.arange(k), home] = True
prev = rng.random((k, n)) < 0.3
plan = PlacementPlan(
    owners=jnp.asarray(owners),
    to_add=jnp.asarray(owners & ~prev),
    to_drop=jnp.asarray(prev & ~owners),
    expired=jnp.zeros((k,), bool),
)
moves = plan_moves(plan, jnp.asarray(home), cap, max_moves=k, object_bytes=4.0)
values = np.arange(k * d, dtype=np.float32).reshape(k, d)

# Reference path: axis_name=None, every "rank" sees the full object table.
ref = [
    publish_and_fill(
        create_cache(cap, (d,)), moves, jnp.asarray(values),
        jnp.arange(k, dtype=jnp.int32), rank=r,
    )
    for r in range(n)
]

# Collective path: each rank holds only its home shard; one psum assembles
# the publish buffer (the paper's per-key RPCs as a single fused collective).
local_ids = np.stack([np.where(home == r)[0] for r in range(n)])  # [n, k/2]
local_vals = values[local_ids]  # [n, k/2, d]
mesh = Mesh(np.array(jax.devices()[:n]), ("x",))

@partial(jax.shard_map, mesh=mesh, in_specs=(P("x"), P("x")), out_specs=P("x"))
def run(lv, lid):
    rank = jax.lax.axis_index("x")
    out = publish_and_fill(
        create_cache(cap, (d,)), moves, lv[0], lid[0],
        rank=rank, axis_name="x",
    )
    return jax.tree_util.tree_map(lambda a: a[None], out)

got = run(jnp.asarray(local_vals), jnp.asarray(local_ids, dtype=jnp.int32))
for r in range(n):
    np.testing.assert_array_equal(np.asarray(got.ids[r]), np.asarray(ref[r].ids))
    np.testing.assert_allclose(np.asarray(got.data[r]), np.asarray(ref[r].data))
print("SHARD_MAP_EQUIVALENCE_OK")
"""


def test_publish_and_fill_matches_shard_map_two_ranks(run_multi_rank):
    out = run_multi_rank(SHARD_MAP_SCRIPT, num_devices=2, timeout=300)
    assert "SHARD_MAP_EQUIVALENCE_OK" in out
