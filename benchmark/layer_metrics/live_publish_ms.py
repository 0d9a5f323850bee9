"""Layer: live write path.  Source: program span — summed duration of the
updater thread's ``live.batch.publish`` spans (``publish_update``: the touched
rows gathered, uploaded, written, the generation swapped) in the traced
seconds, per batch.  Moves ``serve_p50_ms``."""

from benchmark import live_spans


def read(ctx):
    return live_spans.ms_per_batch(ctx, "publish_ns")
