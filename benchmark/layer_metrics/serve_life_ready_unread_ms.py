"""Layer: serving path.  Source: program span — median time a batch's answer
lay ready on the device before the completion thread began to read it
(``max(0, T3 - D1)``: that thread was busy with the batch before); short by
at most ``serve_clock_slack_ms``.  Moves ``serve_p90_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    return pipeline_spans.median_ms(ctx, "ready_unread")
