"""Layer: live write path.  Source: program span — summed duration of the
updater thread's ``live.batch.foldin.readback`` spans (the fold-in program
called and its rows read back: the fold's wait for the device, one a side) in
the traced seconds, per ``live.batch``.  ``None`` where the trace holds no
such span.  Moves ``serve_p50_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    found = pipeline_spans.traced(ctx)
    if found is None:
        return None
    live = found[1]
    back = [s[2] for s in live if s[0] == pipeline_spans.LIVE_READBACK]
    batches = sum(s[0] == pipeline_spans.LIVE_BATCH for s in live)
    return 1e-6 * sum(back) / batches if back and batches else None
