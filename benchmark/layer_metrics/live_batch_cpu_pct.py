"""Layer: live write path.  Source: program span — the updater thread's own
CPU time inside ``live.batch`` (stat ``cpu_us``) as a share of the wall time
of the same intervals (stat ``wall_us``), summed over the traced seconds:
what is missing it waited, for the device (the fold's readback, the row
writes) or for the interpreter.  Higher is better.  ``None`` where no
``live.batch`` span carries the stat.  Moves ``serve_p50_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    found = pipeline_spans.traced(ctx)
    if found is None:
        return None
    return pipeline_spans.cpu_pct(found[1], pipeline_spans.LIVE_BATCH)
