"""Device time under the ``chunk_replay`` scope (request replay), in
milliseconds per 10^6 simulated requests."""

from chipbench.trace_reduce import under


def read(ctx):
    ns = ctx.reduced.time_ns(under("chunk_replay"))
    return ns / 1e6 / (ctx.requests / 1e6) if ns > 0 else None
