"""Layer: live write path.  Source: program span —
``live.batch.publish.writes``: a publish under the engine's table lock (the
donating row writes dispatched, the generation swapped): how long a request's
stage can be kept out; median over the traced batches.  ``None`` on a program
that writes no such span (before ISSUE 54).  Moves ``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "live_publish_locked_ms")
