"""Layer: serving path.  Source: program span — summed duration of the engine
thread's coalesce, stage, dispatch and complete spans in the traced seconds,
per batch, whether the device idles under them or not.  Moves
``serve_p50_ms``."""

from benchmark import program_spans


def read(ctx):
    c = program_spans.traced_cycle(ctx)
    return None if c is None else 1e-6 * c["host_ns"] / c["batches"]
