"""Layer: live write path.  Source: program span — the fold's host work, both
sides: the own time of ``live.batch.foldin.group``, ``.history``, ``.map``,
``.pack`` and ``.write_back`` (``FoldInServer._fold_batch`` but the
program's call and the blocking read of its rows), summed a batch, median
over the traced batches.  ``None`` on a program that writes no such span
(before ISSUE 54).  Moves ``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "live_fold_host_ms")
