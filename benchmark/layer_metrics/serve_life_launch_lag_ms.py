"""Layer: serving path.  Source: program span — median time from the start of
a batch's launch span to the start of its program on the device (``D0 - TL``:
the call itself, and the program waiting behind the batch before), the
device's clock moved to the earliest the spans allow; off by at most
``serve_clock_slack_ms``.  Moves ``serve_p50_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    return pipeline_spans.median_ms(ctx, "launch_lag")
