"""Layer: serving path.  Source: program span — device idle in the traced
seconds that lies under the engine thread's ``serve.batch.readback`` spans, per
batch.  Moves ``serve_p50_ms``."""

from benchmark import program_spans


def read(ctx):
    return program_spans.gap_ms_per_batch(ctx, "serve.batch.readback")
