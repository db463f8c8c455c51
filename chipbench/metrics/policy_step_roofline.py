"""Share of the HBM roofline of the ``policy_step`` scope: the least bytes
of the access log and the sweeps (``roofline.policy_step_bytes``) at peak
bandwidth, over its device time. On several chips each holds a share of the
work, so the least bytes are split over them."""

from chipbench.roofline import policy_step_bytes, share
from chipbench.trace_reduce import under


def read(ctx):
    ns = ctx.reduced.time_ns(under("policy_step"))
    if ns <= 0:
        return None
    least = policy_step_bytes(ctx.num_keys, ctx.num_nodes, ctx.sweeps,
                              ctx.requests)
    return share(least / ctx.chips, ns / 1e9, ctx.device_kind)
