"""Device time under the ``replay_fold`` scope (the jax replay path's
per-node busy fold and per-chunk sums, and their running totals), in
milliseconds per 10^6 simulated requests."""

from chipbench.trace_reduce import under


def read(ctx):
    ns = ctx.reduced.time_ns(under("replay_fold"))
    return ns / 1e6 / (ctx.requests / 1e6) if ns > 0 else None
