"""Layer: live write path.  Source: program span — the updater thread's
``live.batch.prepare`` (``LiveUpdater._process`` from its first line to the
fold: the events' arrays, their queue hops, the quarantine mask), wall time,
median over the traced batches.  ``None`` on a program that writes no such
span (before ISSUE 54).  Moves ``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "live_prepare_ms")
