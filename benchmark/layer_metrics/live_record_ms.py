"""Layer: live write path.  Source: program span — ``live.batch.record``: the
updater's bookkeeping after the publish (counters, the batch span's stats,
the freshness samples, the flight record); median over the traced batches.
``None`` on a program that writes no such span (before ISSUE 54).  Moves
``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "live_record_ms")
