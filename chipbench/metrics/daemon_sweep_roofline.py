"""Share of the HBM roofline of the ``daemon_sweep`` scope: the least bytes
of the sweeps alone (``roofline.policy_step_bytes`` with no request) at
peak bandwidth, over its device time. On several chips each holds a share
of the work, so the least bytes are split over them."""

from chipbench.roofline import policy_step_bytes, share
from chipbench.trace_reduce import under


def read(ctx):
    ns = ctx.reduced.time_ns(under("daemon_sweep"))
    if ns <= 0:
        return None
    least = policy_step_bytes(ctx.num_keys, ctx.num_nodes, ctx.sweeps, 0)
    return share(least / ctx.chips, ns / 1e9, ctx.device_kind)
