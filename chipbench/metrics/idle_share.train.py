"""Share of the training window in which no program ran on the device, in %."""

from chipbench import trace as tr


def read(ctx):
    return 100.0 * tr.idle_share(ctx.trace)
