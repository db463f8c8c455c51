"""Beyond-paper: session placement for serving (Redynis integration #3).

The paper's experiment, serving flavour: zipfian session popularity with
geo-affinity, comparing static placement (sessions pinned where they were
created — the paper's REMOTE analogue) vs the Redynis router migrating
caches toward request sources. Reports local-hit rate and migrated bytes.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import banner, emit, enable_compile_cache
from repro.serving import SessionRouter


def run(migrate: bool, requests: int = 3000, pods: int = 4, sessions: int = 64) -> dict:
    router = SessionRouter(
        num_pods=pods,
        max_sessions=sessions * 2,
        sweep_period=50 if migrate else 10**9,  # daemon off = static placement
        session_bytes=32e6,  # ~a 32k-cache session at 2B widths
    )
    rng = np.random.default_rng(0)
    ranks = np.arange(1, sessions + 1) ** -1.2
    pop = ranks / ranks.sum()
    home = {i: int(rng.integers(0, pods)) for i in range(sessions)}
    # all sessions first created on pod 0 (a deploy/failover artefact)
    for i in range(sessions):
        router.route(f"s{i}", 0)
    for _ in range(requests):
        i = int(rng.choice(sessions, p=pop))
        router.route(f"s{i}", home[i])
        router.tick()
    return {
        "hit_rate": router.hit_rate(),
        "migrations": router.stats["migrations"],
        "migrated_GB": router.stats["migrated_bytes"] / 1e9,
        "elections": router.stats["elections"],
    }


def main() -> None:
    banner("serving_sessions: static vs Redynis-migrated session placement")
    static = run(migrate=False)
    dyn = run(migrate=True)
    emit("serving_sessions", round(static["hit_rate"], 4), "hit_rate", mode="static")
    emit(
        "serving_sessions",
        round(dyn["hit_rate"], 4),
        "hit_rate",
        mode="redynis",
        migrations=dyn["migrations"],
        migrated_GB=round(dyn["migrated_GB"], 2),
    )
    emit(
        "serving_sessions_gain",
        round(dyn["hit_rate"] / max(static["hit_rate"], 1e-9), 2),
        "x_hit_rate",
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
