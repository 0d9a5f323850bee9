"""Layer: live write path.  Source: program span — ``live.batch.foldin.call``:
the fold-in program called, until the call returns (it carries the fold's one
host array up), both sides summed a batch, median over the traced batches.
``None`` on a program that writes no such span (before ISSUE 54).  Moves
``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "live_fold_call_ms")
