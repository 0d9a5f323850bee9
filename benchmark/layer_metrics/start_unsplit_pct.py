"""Layer: serving path.  Source: program counter —
the share of the top-level ``start.*`` phases' seconds that no leaf holds:
the Python between two phases of a start (as ``live_batch_unsplit_pct`` for
a batch).
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.unsplit_pct()
