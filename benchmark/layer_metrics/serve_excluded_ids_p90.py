"""Layer: serving path.  Source: program counter — the 90th percentile of
the histogram ``serving.excluded_ids{source=history}``: catalog ids a
request's ranking lost to its user's published history (a bucketed upper
bound, four buckets a decade).  Moves ``serve_p90_ms``.  A program without
the histogram reads nothing."""


def read(ctx):
    return ctx.counters.get("excluded_ids_p90")
