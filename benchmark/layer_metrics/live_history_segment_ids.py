"""Layer: live write path.  Source: program counter —
``live.history_segment_ids`` over the window, per publish: of the ids a
``publish_update`` appends to its users' histories, those that name an item
the index holds in its delta segment at that publish (a new listing and its
first rater's id go up together), which the scoring program masks by SLOT
and not by base column.  0 where the catalog does not move; ``None`` where
the program has no such counter.  Moves ``serve_p90_ms``."""


def read(ctx):
    ids, n = (ctx.counters.get("history_segment_ids"),
              ctx.counters.get("publishes"))
    return None if ids is None or not n else ids / n
