"""Layer: live write path.  Source: program counter — the seconds of a start
under ``start.foldin_server.yty``: the Gram matrix ``F^T F`` of each fixed
table computed WHOLE in true float32, once a table (the catalog's at
``FoldInServer(...)``, the user table's where the item side is first asked
for; ``side`` on each) — the whole pass is paid here, not a batch.  0 on an
explicit start; ``None`` on a program without the start's counters (before
ISSUE 55).  Moves ``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.seconds(
        lambda paths: start_phases.named(paths, "start.foldin_server.yty"))
