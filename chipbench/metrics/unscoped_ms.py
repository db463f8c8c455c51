"""Device time under none of the program's named scopes and outside every
collective: the streamed trace generation, the telemetry histogram fold
and the per-call key-state draw, with XLA's own operations (relayout and
async copies) whose neighbours carry no scope either; a copy that feeds or
is fed by a scoped operation counts under that scope (``trace_reduce``).
Milliseconds per 10^6 simulated requests."""

from chipbench.trace_reduce import unscoped


def read(ctx):
    ns = ctx.reduced.time_ns(unscoped)
    return ns / 1e6 / (ctx.requests / 1e6) if ns > 0 else None
