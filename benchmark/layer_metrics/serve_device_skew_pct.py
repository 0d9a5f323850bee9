"""Layer: device.  Source: device trace — (busiest chip's busy time - idlest
chip's) over the chips' mean busy time, in per cent, over the traced stream:
the zipf's hot users and the ragged last shard show here.  Moves
``serve_p90_ms``.  One chip, or a run without a trace, reads nothing."""

import os

from benchmark import program_spans
from benchmark import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        path = tr.find_xplane(os.path.join(ctx.cell.root, ".bench_cache",
                                           "runs", "trace"))
    except FileNotFoundError:
        return None
    busy = [sum(e - s for s, e in tr.busy_union(ops))
            for ops in program_spans.device_busy(path).values()]
    if len(busy) < 2 or not sum(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
