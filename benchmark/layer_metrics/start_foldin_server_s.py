"""Layer: live write path.  Source: program counter —
``start.foldin_server``: ``FoldInServer(...)`` whole — the host's table
copied into a buffer with spare rows, the catalog placed as the folds' fixed
side, the resident histories' widths.
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.seconds(
        lambda paths: start_phases.named(paths, "start.foldin_server"))
