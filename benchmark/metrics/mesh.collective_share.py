"""Share of the traced window the chips spend in collectives: each
chip's collective ops' self time (``benchmark/collectives.py``),
averaged over the chips, over the window. Nothing is read where the
trace holds no collective."""

from benchmark import collectives


def read(ctx):
    trace = ctx.get("trace")
    seconds = collectives.seconds_per_chip(trace)
    if seconds is None or trace["window_s"] <= 0:
        return None
    return 100.0 * seconds / trace["window_s"]
