"""Share of the HBM roofline of the ``chunk_replay`` scope: the least bytes
Algorithms 1-2 touch per request (``roofline.chunk_replay_bytes``) at peak
bandwidth, over its device time. On several chips each holds a share of the
work, so the least bytes are split over them."""

from chipbench.roofline import chunk_replay_bytes, share
from chipbench.trace_reduce import under


def read(ctx):
    ns = ctx.reduced.time_ns(under("chunk_replay"))
    if ns <= 0:
        return None
    least = chunk_replay_bytes(ctx.num_nodes, ctx.requests)
    return share(least / ctx.chips, ns / 1e9, ctx.device_kind)
