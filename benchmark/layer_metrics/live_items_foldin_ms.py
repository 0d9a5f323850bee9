"""Layer: live write path.  Source: program span — summed duration of the
updater thread's ``live.batch.foldin.items`` spans (``FoldInServer.
update_items``: the item side's history merge, fold-in program, write-back
and row write) in the traced seconds, per ``live.batch``.  ``None`` where the
trace holds no such span (a commit that does not write it).  Moves
``serve_p50_ms``."""

from benchmark import live_item_spans


def read(ctx):
    return live_item_spans.items_foldin_ms(ctx)
