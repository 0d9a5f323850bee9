"""Layer: serving path.  Source: program span — the completion thread's own
CPU time inside ``serve.batch.complete`` (stat ``cpu_us``) as a share of the
wall time of the same intervals (stat ``wall_us``), summed over the traced
seconds.  The phase never blocks, so what is missing it waited for the
interpreter (or the machine stood).  Higher is better.  Moves
``serve_p90_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    return pipeline_spans.serve_cpu_pct(ctx, pipeline_spans.COMPLETE)
