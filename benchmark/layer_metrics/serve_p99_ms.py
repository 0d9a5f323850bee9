"""Layer: serving path.  Source: host clock — 99th percentile of the
latency (due to answer in hand) of all requests of the whole window (the
untraced one, also in a ``--trace 1`` run).  Not an end-to-end metric:
about once in 30-60 s the whole serving process stops for ~0.1 s (cause
unknown), which touches just under 1 % of a window's requests, so the 99th
percentile of any window the contract allows flips between two values
(PERF.md section 2).  Moves the cell's tail metric (``serve_p90_ms``)."""

import numpy as np


def read(ctx):
    lat = ctx.counters.get("latency_ms")
    return None if lat is None or not len(lat) else float(np.percentile(lat, 99))
