"""Layer: live write path.  Source: program span — summed duration of the
updater thread's ``live.batch.publish.history`` spans (the engine's, inside
``live.batch.publish``: the ids a publish adds to its users' histories
planned and uploaded; the write itself is dispatched with the row write) in
the traced seconds, per ``live.batch``.  ``None`` where the trace holds no
such span (a program whose histories do not move).  Moves
``serve_p50_ms``."""

import os

from benchmark import live_spans, program_spans
from benchmark import trace as tr

HISTORY = "live.batch.publish.history"


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        path = tr.find_xplane(os.path.join(ctx.cell.root, ".bench_cache",
                                           "runs", "trace"))
    except FileNotFoundError:
        return None
    spans = program_spans.read(path, prefix=live_spans.BATCH)
    spent = [s[2] for s in spans if s[0] == HISTORY]
    batches = sum(s[0] == live_spans.BATCH for s in spans)
    return 1e-6 * sum(spent) / batches if spent and batches else None
