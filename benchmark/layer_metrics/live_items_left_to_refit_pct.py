"""Layer: live write path.  Source: program counter —
``live.items_left_to_refit`` over the window as a share of the window's
events, in %: the events whose ITEM the fold-in server left its factor,
because resident ratings name it and a fold over the run's events would not
be over all of its ratings (the events still enter their users' folds and
histories).  The other events' items are folded.  ``None`` where the program
has no such counter.  Moves ``serve_p50_ms``: what is not folded is not
written into the segment."""


def read(ctx):
    left, n = (ctx.counters.get("items_left_to_refit"),
               ctx.counters.get("events_in_window"))
    return None if left is None or not n else 100.0 * left / n
