"""Layer: serving path.  Source: program span — the engine thread's own CPU
time inside ``serve.batch.dispatch`` (stat ``cpu_us``) as a share of the
wall time of the same intervals (stat ``wall_us``), summed over the traced
seconds: what is missing the thread spent without a processor, waiting for
the interpreter or blocked in the runtime.  Higher is better.  Moves
``serve_p50_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    return pipeline_spans.serve_cpu_pct(ctx, pipeline_spans.DISPATCH)
