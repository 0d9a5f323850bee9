"""Layer: serving path.  Source: program span — median time from the later of
the program's end and the readback's start to the readback's end
(``T4 - max(D1, T3)``: the transfer back and the thread's wake-up); long by
at most ``serve_clock_slack_ms``.  Moves ``serve_p50_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    return pipeline_spans.median_ms(ctx, "readback_tail")
