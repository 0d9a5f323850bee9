"""Layer: device.  Source: device trace — 1 - union of busy intervals over
the traced iterations.  Moves ``train_iter_s``."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_pct
