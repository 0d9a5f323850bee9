"""Layer: serving path.  Source: program counter —
``serving.exclusion_upload_bytes`` over the window (what the requests' own
lists of excluded ids add to each batch's one upload) over the window's
batches, in KB (1e3 bytes) a batch.  Moves ``serve_p50_ms``.  A program
without the counter reads nothing."""


def read(ctx):
    sent, n = (ctx.counters.get("exclusion_upload_bytes"),
               ctx.counters.get("window_batches"))
    return None if not sent or not n else 1e-3 * sent / n
