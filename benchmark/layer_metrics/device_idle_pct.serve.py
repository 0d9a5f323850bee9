"""Layer: device.  Source: device trace — 1 - union of busy intervals over
the traced seconds of serving.  Moves ``serve_p50_ms``."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_pct
