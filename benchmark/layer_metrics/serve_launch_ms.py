"""Layer: serving path.  Source: program span — median duration of
``serve.batch.dispatch.launch`` (the scorer chosen and called, until the call
returns) over the traced batches of the most-ridden bucket.  Moves
``serve_p50_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    return pipeline_spans.median_ms(ctx, "launch")
