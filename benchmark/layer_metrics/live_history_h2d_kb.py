"""Layer: live write path.  Source: program counter —
``live.history_h2d_bytes`` over the window, per publish, in KB (1e3 bytes):
what one ``publish_update`` sends host -> device for the users' HISTORIES
(the appended ids, their positions, their users' starts and counts, padded
to 8 / 64 / 512 entries).  Must stay O(ids appended): 0.16 KB at up to 8
ids a publish, whatever the histories hold.  ``None`` where the program
counts no such bytes.  Moves ``serve_p50_ms``."""


def read(ctx):
    sent, n = (ctx.counters.get("history_h2d_bytes"),
               ctx.counters.get("publishes"))
    return None if not sent or not n else 1e-3 * sent / n
