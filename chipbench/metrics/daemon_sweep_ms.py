"""Device time under the ``daemon_sweep`` scope, nested in ``policy_step``
(the placement policy's masked step: the ownership sweeps over the
``[K, N]`` store), in milliseconds per 10^6 simulated requests."""

from chipbench.trace_reduce import under


def read(ctx):
    ns = ctx.reduced.time_ns(under("daemon_sweep"))
    return ns / 1e6 / (ctx.requests / 1e6) if ns > 0 else None
