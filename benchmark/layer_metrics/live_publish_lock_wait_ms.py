"""Layer: live write path.  Source: program span —
``live.batch.publish.lock_wait``: a publish's wait for the engine's table
lock, which the engine thread holds from a batch's stage to its scoring
call's return; median over the traced batches.  ``None`` on a program that
writes no such span (before ISSUE 54).  Moves ``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "live_publish_lock_wait_ms")
