"""Layer: serving path.  Source: host clock — 95th percentile of the
latency (due to answer in hand) of all requests of the whole window (the
untraced one, also in a ``--trace 1`` run).  Not an end-to-end metric in a
cell that runs near its ceiling: there one 0.1 s stall of the process
(about once in 30-60 s, cause unknown) leaves a queue that takes a second
or more to drain, 5 % of a window, and the 95th percentile read 91-98 ms in
ten windows and 111 and 144 ms in two (PERF.md section 2).  Moves
``serve_p90_ms``."""

import numpy as np


def read(ctx):
    lat = ctx.counters.get("latency_ms")
    return None if lat is None or not len(lat) else float(np.percentile(lat, 95))
