"""Beyond-paper: hot-row embedding cache (Redynis integration #2).

Sweeps the ownership coefficient / cache size against zipfian token traffic
and reports: cache hit rate, analytic HBM bytes saved per training step at
production shapes (hits × d_model × dtype — rows served from VMEM instead
of HBM), and the lookup correctness/latency through the hot_gather kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import banner, emit, enable_compile_cache, time_fn
from repro.core.hot_embedding import HotEmbedding, embed_with_cache


def main() -> None:
    banner("hot_embedding: hot-row cache hit rate vs cache size")
    vocab, d = 32_000, 2048
    rng = np.random.default_rng(0)
    ranks = np.arange(1, vocab + 1) ** -1.1
    probs = ranks / ranks.sum()

    for rows in (512, 2048, 8192):
        he = HotEmbedding(vocab=vocab, num_nodes=16, rows=rows, period=2)
        hs = he.init_state()
        for step in range(6):
            toks = rng.choice(vocab, (16, 512), p=probs)
            hs = he.fold(hs, jnp.asarray(toks, jnp.int32), jnp.arange(16, dtype=jnp.int32))
            if he.due(step + 1):
                hs = he.sweep(hs)
        # measured hit rate on a fresh batch
        toks = jnp.asarray(rng.choice(vocab, (4, 512), p=probs), jnp.int32)
        table = jnp.zeros((vocab, 64), jnp.bfloat16)  # d=64 for CPU speed
        rows_out, hit = embed_with_cache(table, toks, hs, use_kernel=False)
        hit_rate = float(hit.mean())
        # production shapes: train_4k tokens/step/chip = 4096*256/256 = 4096
        tokens_per_chip = 4096
        saved = hit_rate * tokens_per_chip * d * 2
        emit(
            "hot_embedding",
            round(hit_rate, 4),
            "hit_rate",
            rows=rows,
            hbm_saved_per_step_chip_MB=round(saved / 1e6, 2),
            traffic_frac=round(float(he.hit_rate(hs)), 4),
        )

    banner("hot_embedding: two-level lookup wall time (CPU, jnp fallback)")
    he = HotEmbedding(vocab=vocab, num_nodes=1, rows=2048, period=1)
    hs = he.init_state()
    toks0 = jnp.asarray(rng.choice(vocab, (16, 512), p=probs), jnp.int32)
    hs = he.fold(hs, toks0, jnp.zeros((16,), jnp.int32))
    hs = he.sweep(hs)
    table = jax.random.normal(jax.random.PRNGKey(0), (vocab, 256)).astype(jnp.bfloat16)
    toks = jnp.asarray(rng.choice(vocab, (4, 512), p=probs), jnp.int32)

    f_plain = jax.jit(lambda t, tok: jnp.take(t, tok, axis=0))
    f_cache = jax.jit(lambda t, tok, s: embed_with_cache(t, tok, s, use_kernel=False)[0])
    t_plain = time_fn(f_plain, table, toks, iters=20)
    t_cache = time_fn(f_cache, table, toks, hs, iters=20)
    emit("hot_embedding_lookup_us", round(t_plain * 1e6, 1), "us", mode="plain_take")
    emit("hot_embedding_lookup_us", round(t_cache * 1e6, 1), "us", mode="two_level")


if __name__ == "__main__":
    enable_compile_cache()
    main()
