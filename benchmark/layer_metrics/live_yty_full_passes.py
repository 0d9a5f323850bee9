"""Layer: live write path.  Source: program counter — whole-table Gram
programs (``core.foldin.whole_yty``: ``F^T F`` over a 1.5-1.7 M-row factor
table, O(table)) the fold-in server ran between the stream's start and the
window's end: the growth of the program's counter ``foldin.yty_full``
(every ``side`` and ``when``).  0 is the design — the Gram matrix is
computed whole only where its table is placed whole, at start, and moved
by the rows a fold wrote from then on (``foldin.yty_rows``).  ``None`` on a
program that keeps no such counter (one that recomputes the matrix a
batch says nothing here; its device trace does).  Moves ``serve_p90_ms``:
a whole-table pass is milliseconds of device a serving batch queues
behind."""


def read(ctx):
    passes = ctx.counters.get("yty_full_in_window")
    return None if passes is None else float(passes)
