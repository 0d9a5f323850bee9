"""Layer: live write path.  Source: program counter — the 90th percentile
of the histogram ``foldin.history_width{side=user}``: the padded width of a
run of the fold-in program (the rung that holds the longest history among
the users it solves; a bucketed upper bound, four buckets a decade).  A
server that folds over resident histories rides the histories' widths
(4,096 and 8,192 here), one that folds over the run's events 8 to 64.
``None`` where the program has no such histogram.  Moves ``serve_p90_ms``."""


def read(ctx):
    return ctx.counters.get("fold_width_p90")
