"""Device time under the ``trace_window`` scope (the streamed trace
generation: each chunk's request window drawn in the scan), in
milliseconds per 10^6 simulated requests."""

from chipbench.trace_reduce import under


def read(ctx):
    ns = ctx.reduced.time_ns(under("trace_window"))
    return ns / 1e6 / (ctx.requests / 1e6) if ns > 0 else None
