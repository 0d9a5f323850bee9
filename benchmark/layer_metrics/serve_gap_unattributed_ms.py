"""Layer: serving path.  Source: program span — device idle in the traced
seconds that lies under none of the engine thread's spans, per batch: what
the other ``serve_gap_*`` metrics do not explain.  Moves ``serve_p50_ms``."""

from benchmark import program_spans


def read(ctx):
    return program_spans.gap_ms_per_batch(ctx, program_spans.UNATTRIBUTED)
