"""Layer: dispatch.  Source: program counter — the sum of
``probe_caches()`` ``meta["seconds"]`` after the fit.  Moves ``setup_s``."""


def read(ctx):
    return ctx.counters.get("probe_s")
