"""Device time under the ``occupancy`` scope (the per-chunk replica-bytes
sample over the whole ``[K, N]`` map and the running peak), in
milliseconds per 10^6 simulated requests."""

from chipbench.trace_reduce import under


def read(ctx):
    ns = ctx.reduced.time_ns(under("occupancy"))
    return ns / 1e6 / (ctx.requests / 1e6) if ns > 0 else None
