"""Layer: live write path.  Source: program counter —
``live.publish_h2d_bytes`` over the window, per publish, in MB (1e6 bytes):
what one ``publish_update`` sends host -> device.  Moves ``serve_p50_ms``."""


def read(ctx):
    sent, n = (ctx.counters.get("publish_h2d_bytes"),
               ctx.counters.get("publishes"))
    return None if sent is None or not n else 1e-6 * sent / n
