"""Device idle share of the traced window: 1 - (union of device op
intervals, averaged over the devices) / window."""

from benchmark.trace_reduce import idle_share


def read(ctx):
    return idle_share(ctx.get("trace"))
