"""Layer: load generator (the benchmark's own).  Source: host clock — how
late after its due time each request was handed to ``submit``, 99th
percentile.  Moves the cell's tail metric (``serve_p90_ms``): a starved generator is not a fast
server."""

import numpy as np


def read(ctx):
    late = ctx.counters.get("late_ms")
    return None if late is None or not len(late) else float(np.percentile(late, 99))
