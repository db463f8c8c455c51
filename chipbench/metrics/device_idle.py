"""Percent of the stretch (one call and the host gap after it) in which no
operation ran on the device, mean over the chips used."""


def read(ctx):
    r = ctx.reduced
    return 100.0 * (1.0 - r.busy_ns / r.window_ns) if r.window_ns > 0 else None
