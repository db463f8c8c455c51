"""Device time under the ``access_log`` scope, nested in ``policy_step``
(Algorithm 1's access log: the per-request count scatter into the
``[K, N]`` store and its relayouts), in milliseconds per 10^6 simulated
requests."""

from chipbench.trace_reduce import under


def read(ctx):
    ns = ctx.reduced.time_ns(under("access_log"))
    return ns / 1e6 / (ctx.requests / 1e6) if ns > 0 else None
