"""Layer: serving path.  Source: program span — median life of a traced
batch of the most-ridden bucket, stage start to the end of its last ticket's
bookkeeping (``T5 - T0``, the spans of one ``seq`` on two threads): what a
request lives through after its wait in the queue.  Moves ``serve_p50_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    return pipeline_spans.median_ms(ctx, "life")
