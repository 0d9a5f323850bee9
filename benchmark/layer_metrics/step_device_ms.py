"""Layer: training step.  Source: device trace — busy time inside the traced
iterations over their number.  Moves ``train_iter_s``."""


def read(ctx):
    n = ctx.counters.get("iterations")
    if ctx.trace is None or not n:
        return None
    return 1e3 * ctx.trace.busy_s / n
