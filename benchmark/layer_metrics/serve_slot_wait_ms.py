"""Layer: serving path.  Source: program span — summed duration of the
engine thread's ``pipe.slot_wait`` spans (its blocking wait for one of the
pipeline's two slots, written only when it blocks) in the traced seconds,
per ``serve.batch``: the part of a request's queue wait that is the pipeline's
step.  ``None`` on a program that writes no launch span (an older commit).
Moves ``serve_p50_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    life = pipeline_spans.traced_life(ctx)
    if life is None:
        return None
    return 1e-6 * life["slot_wait_ns"] / life["batches"]
