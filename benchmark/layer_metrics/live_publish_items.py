"""Layer: live write path.  Source: program span — the stat ``items`` of
the updater thread's ``live.batch.publish`` spans in the traced seconds:
catalog rows one publish names (touched and appended), mean a publish;
beside it on the same span ``history_ids``, so one publish's three parts
can be read together.  ``None`` where no span carries the stat (an updater
that folds no items, a commit that does not write it).  Moves
``serve_p50_ms``."""

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
    rows = [s[3]["items"] for s in program_spans.read(
        path, prefix=live_spans.PUBLISH) if s[0] == live_spans.PUBLISH
        and "items" in s[3]]
    return sum(rows) / len(rows) if rows else None
