"""Layer: live write path.  Source: program counter —
``live.catalog_h2d_bytes`` over the window, per publish, in MB (1e6 bytes):
what one ``publish_update`` sends host -> device of the CATALOG (the touched
and appended item rows, for the index's segment and for the engine's own
table; the whole catalog where it is re-placed).  Beside
``live_publish_h2d_mb``, which is the user table's.  ``None`` where the
program counts no such bytes (a commit without the counter).  Moves
``serve_p50_ms``."""


def read(ctx):
    sent, n = (ctx.counters.get("catalog_h2d_bytes"),
               ctx.counters.get("publishes"))
    return None if not sent or not n else 1e-6 * sent / n
