"""Layer: serving path.  Source: program counter —
``serving.mesh_history_bytes`` over the window (what one device moves
between the chips for the users' histories: the all-reduce of the batch's
``[bucket, history pad]`` int32 lists, the owning shard's ids and zeros from
the others, by the program's closed form) over the window's batches, in KB
(1e3 bytes) a batch.  Moves ``serve_p50_ms``.  A program without the counter
reads nothing."""


def read(ctx):
    moved, n = (ctx.counters.get("mesh_history_bytes"),
                ctx.counters.get("window_batches"))
    return None if not moved or not n else 1e-3 * moved / n
