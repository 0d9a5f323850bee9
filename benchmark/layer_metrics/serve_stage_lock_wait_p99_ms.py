"""Layer: serving path.  Source: program span — the stat ``lock_wait_us`` of
``serve.batch.stage``: the engine thread's wait for the table lock a publish
writes under (``live.batch.publish.writes``), 99th percentile over the traced
batches.  ``None`` on a program that writes no such stat (before ISSUE 54).
Moves ``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "serve_stage_lock_wait_p99_ms")
