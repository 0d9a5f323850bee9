"""Layer: serving kernels.  Source: device trace — busy time in the traced
seconds over the batches dispatched in them.  Moves ``serve_p50_ms``."""


def read(ctx):
    n = ctx.counters.get("batches")
    if ctx.trace is None or not n:
        return None
    return 1e3 * ctx.trace.busy_s / n
