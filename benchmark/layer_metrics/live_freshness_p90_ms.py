"""Layer: live write path.  Source: program counter — 90th percentile over
the window's events of the time from the event handed to
``LiveUpdater.submit`` to its batch's publish done (the ``t_done`` of the
updater's per-batch record): how stale a rating is before it moves a
recommendation.  Filed under ``serve_p90_ms`` until the benchmark has an
end-to-end metric for it."""

import numpy as np


def read(ctx):
    f = ctx.counters.get("freshness_ms")
    return None if f is None or not len(f) else float(np.percentile(f, 90))
