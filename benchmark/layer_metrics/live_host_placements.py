"""Layer: live write path.  Source: program span — the stat ``placements``
of the updater thread's ``live.batch`` spans in the traced seconds: the
host→device placement calls (a ``jax.device_put`` beside a program's call,
≈ 0.35 ms of Python each on the chip's host) that thread made for one
micro-batch — the fold's inputs, the fold-in server's row writes, the
publish's rows, segment and history plan — mean a batch.  The program counts
them where it makes them (its counter ``live.host_placements`` is the same
number).  ``None`` where no span carries the stat (a commit that does not
write it, a cell without an updater).  Moves ``serve_p90_ms``."""

import os

from benchmark import live_spans, program_spans
from benchmark import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        path = tr.find_xplane(os.path.join(ctx.cell.root, ".bench_cache",
                                           "runs", "trace"))
    except FileNotFoundError:
        return None
    made = [s[3]["placements"] for s in program_spans.read(
        path, prefix=live_spans.BATCH) if s[0] == live_spans.BATCH
        and "placements" in s[3]]
    return sum(made) / len(made) if made else None
