"""Layer: live write path.  Source: program span — summed duration of the
updater thread's ``live.batch`` spans in the traced seconds (fold-in, publish
and the Python around them: what the thread takes of the interpreter the
engine thread needs), per batch.  Moves ``serve_p50_ms``."""

from benchmark import live_spans


def read(ctx):
    return live_spans.ms_per_batch(ctx, "batch_ns")
