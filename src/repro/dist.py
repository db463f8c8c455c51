"""Distribution context threaded through the models.

Models are written as pure functions over params and activations; every
placement decision funnels through a :class:`DistSpec` so the same model code
runs (a) un-distributed on CPU for smoke tests (``dist=None`` — every helper
degenerates to plain jnp), (b) under ``pjit`` on the production mesh, where
the helpers emit sharding constraints and the two genuinely placement-
sensitive ops — vocab-sharded embedding lookup and vocab-sharded softmax
cross-entropy — are implemented explicitly rather than left to the SPMD
partitioner's gather heuristics (which may all-gather a multi-GB table).

This module is the seam between the model layer and the launch layer:
``launch/sharding.py`` builds the DistSpec; models only consume it.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import Array
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "DistSpec",
    "local_dist",
    "constrain",
    "embed_lookup",
    "softmax_xent",
    "unembed_logits",
]


class DistSpec(NamedTuple):
    """Mesh + logical-axis bindings for one run.

    batch_axes: mesh axes the global batch is split over — ``("data",)``
                single-pod, ``("pod", "data")`` multi-pod.
    model_axis: mesh axis for tensor/expert/vocab parallelism (None = off).
    """

    mesh: Optional[Mesh] = None
    batch_axes: tuple[str, ...] = ()
    model_axis: Optional[str] = None

    @property
    def batch(self):  # PartitionSpec entry for the batch dim
        return self.batch_axes if self.batch_axes else None

    @property
    def tensor_parallel(self) -> bool:
        """True when the model axis is free for TP (not consumed by batch).
        The fsdp layout spreads the batch over the model axis too; head/
        expert constraints must then stay unsharded."""
        return self.model_axis is not None and self.model_axis not in self.batch_axes

    @property
    def loss_batch(self):
        """Row spec for vocab-sharded ops (embedding lookup, xent): the
        batch axes minus the model axis — vocab occupies the model axis, so
        token rows reshard off it for the loss path."""
        axes = tuple(a for a in self.batch_axes if a != self.model_axis)
        return axes if axes else None

    @property
    def model_size(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def batch_size(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n


def local_dist() -> DistSpec:
    """The no-mesh context used by CPU smoke tests."""
    return DistSpec()


def constrain(x: Array, dist: Optional[DistSpec], *spec) -> Array:
    """``with_sharding_constraint`` that no-ops without a mesh.

    ``spec`` entries are mesh-axis names / tuples / None, one per dim of x.
    """
    if dist is None or dist.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(dist.mesh, P(*spec))
    )


# ---------------------------------------------------------------------------
# Vocab-sharded embedding lookup.
#
# table [V, D] is sharded V-over-model. A plain jnp.take would leave the SPMD
# partitioner to choose between all-gathering the table (V up to 256k rows —
# gigabytes) and the masked-local-gather + psum pattern; we write the latter
# explicitly with shard_map so the collective is one all-reduce over the
# [tokens, D] activation, never the table.


def embed_lookup(table: Array, tokens: Array, dist: Optional[DistSpec]) -> Array:
    """tokens [B, S] int32 -> [B, S, D]; table [V, D] (V sharded over model)."""
    if dist is None or dist.mesh is None or dist.model_axis is None:
        return jnp.take(table, tokens, axis=0)

    axis = dist.model_axis
    n_shards = dist.model_size
    v = table.shape[0]
    assert v % n_shards == 0, (v, n_shards)
    v_local = v // n_shards

    def local_lookup(tab: Array, tok: Array) -> Array:
        lo = jax.lax.axis_index(axis) * v_local
        idx = tok - lo
        ok = (idx >= 0) & (idx < v_local)
        rows = jnp.take(tab, jnp.clip(idx, 0, v_local - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, 0).astype(tab.dtype)
        return jax.lax.psum(rows, axis)

    # Batches too small to split (long_500k decodes one stream) replicate.
    lb = dist.loss_batch
    n_rows = 1
    if lb:
        for a in lb:
            n_rows *= dist.mesh.shape[a]
    bspec = lb if tokens.shape[0] % max(n_rows, 1) == 0 else None
    return jax.shard_map(
        local_lookup,
        mesh=dist.mesh,
        in_specs=(P(axis, None), P(bspec, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(table, tokens)


# ---------------------------------------------------------------------------
# Vocab-sharded softmax cross-entropy (the LM head + loss, fused).
#
# logits [T, V] for T ~ 1M tokens and V ~ 128k would be ~0.5-1 GB *per chip*
# if materialised at once, and an all-gathered version would be 16x that. We
# (a) keep logits sharded over V (the matmul needs no comm: x is replicated
# over model, the table shard produces the local logit shard), (b) reduce
# over V with psum-backed logsumexp, (c) pick the label logit with a fused
# masked reduce (never a gather across the sharded axis), and (d) scan over
# token chunks so only one chunk of logits is ever live.


def unembed_logits(
    x: Array, table: Array, dist: Optional[DistSpec], vocab_size: int = 0
) -> Array:
    """x [..., D] @ table.T -> logits [..., V], V-sharded when distributed.

    ``vocab_size``: real vocab; rows beyond it (table padding for shard
    divisibility) are masked to -inf so samplers never pick them.
    """
    logits = jnp.einsum(
        "...d,vd->...v", x, table, preferred_element_type=jnp.float32
    )
    v = table.shape[0]
    if vocab_size and vocab_size < v:
        pad_mask = jnp.arange(v) >= vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    if dist is not None and dist.mesh is not None:
        spec = [None] * (logits.ndim - 1) + [dist.model_axis]
        spec[0] = dist.loss_batch
        logits = constrain(logits, dist, *spec)
    return logits


def _xent_chunk(
    x: Array,  # [C, D] activations for this chunk
    targets: Array,  # [C] int32
    mask: Array,  # [C] bool (loss mask)
    table: Array,  # [V, D]
    dist: Optional[DistSpec],
    vocab_size: int,
) -> tuple[Array, Array]:
    """Sum of token losses + correct-token count for one chunk."""
    logits = jnp.einsum(
        "cd,vd->cv", x, table, preferred_element_type=jnp.float32
    )
    v = logits.shape[-1]
    if vocab_size and vocab_size < v:
        logits = jnp.where(jnp.arange(v) >= vocab_size, -1e30, logits)
    if dist is not None and dist.mesh is not None:
        # Chunk rows shard over the non-model batch axes; vocab over model.
        # A None row spec here would FORCE replication — i.e. all-gather
        # the logits (an early bug the roofline analyser caught).
        logits = constrain(logits, dist, dist.loss_batch, dist.model_axis)
    m = jnp.max(logits, axis=-1, keepdims=True)  # psum-max under SPMD
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[:, 0]
    onehot_sel = jnp.arange(v, dtype=targets.dtype)[None, :] == targets[:, None]
    label_logit = jnp.sum(jnp.where(onehot_sel, logits, 0.0), axis=-1)
    loss = (lse - label_logit) * mask
    return jnp.sum(loss), jnp.sum(mask.astype(jnp.float32))


def softmax_xent(
    x: Array,  # [B, S, D] final hidden states
    table: Array,  # [V, D] embedding/unembedding table
    targets: Array,  # [B, S] int32
    dist: Optional[DistSpec] = None,
    mask: Array | None = None,
    num_chunks: int = 8,
    vocab_size: int = 0,
) -> Array:
    """Mean cross-entropy over masked tokens, chunked over the token dim.

    The chunk body is rematerialised on the backward pass (jax.checkpoint),
    so peak logits memory is one chunk forward + one chunk backward.
    """
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    tf = targets.reshape(t)
    mf = (
        jnp.ones((t,), jnp.float32)
        if mask is None
        else mask.reshape(t).astype(jnp.float32)
    )
    num_chunks = min(num_chunks, t)
    while t % num_chunks:
        num_chunks -= 1
    c = t // num_chunks

    chunk_fn = jax.checkpoint(
        lambda xa, ta, ma: _xent_chunk(xa, ta, ma, table, dist, vocab_size)
    )

    def body(carry, args):
        tot, cnt = carry
        l, n = chunk_fn(*args)
        return (tot + l, cnt + n), None

    (tot, cnt), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (
            xf.reshape(num_chunks, c, d),
            tf.reshape(num_chunks, c),
            mf.reshape(num_chunks, c),
        ),
    )
    return tot / jnp.maximum(cnt, 1.0)
