"""Layer: entry points + host data plane.  Source: the benchmark's clock
from the ``fit()`` call to the first callback boundary (id maps, bucketize,
upload, probes, trace, compile, first iteration).  Moves ``setup_s``."""


def read(ctx):
    return ctx.counters.get("fit_first_iter_s")
