"""Layer: live write path.  Source: program counter —
``start.prewarm``, every side: the fixed table of each fold direction on
the device, the ladder of fold-in programs compiled or fetched and run, the
row writes between the two sides.
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.seconds(
        lambda paths: start_phases.named(paths, "start.prewarm"))
