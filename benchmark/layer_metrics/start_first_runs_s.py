"""Layer: serving path.  Source: program counter —
the ``start.first_run`` leaves: every program a warm-up RUNS for the first
time and waits for — the pinned programs that exclude or score a segment,
the row writes, the histories' moves.
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.seconds(
        lambda paths: start_phases.named(paths, "start.first_run"))
