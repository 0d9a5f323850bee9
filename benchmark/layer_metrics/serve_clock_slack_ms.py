"""Layer: device.  Source: program span — width of the interval of shifts of
the device's clock under which no traced batch's program starts before its
launch span or ends after its readback span: how far
``serve_life_launch_lag_ms`` and ``serve_life_ready_unread_ms`` /
``_readback_tail_ms`` can be off.  Moves ``serve_p50_ms``."""

from benchmark import pipeline_spans


def read(ctx):
    life = pipeline_spans.traced_life(ctx)
    return None if life is None else 1e-6 * life["slack_ns"]
