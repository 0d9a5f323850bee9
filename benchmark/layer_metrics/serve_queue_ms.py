"""Layer: serving path (the micro-batcher).  Source: program counter —
median ``Ticket.t_dequeue - Ticket.t_submit`` over the window.  Moves
``serve_p50_ms``."""

import numpy as np


def read(ctx):
    q = ctx.counters.get("queue_ms")
    return None if q is None or not len(q) else float(np.median(q))
