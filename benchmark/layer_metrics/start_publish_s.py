"""Layer: serving path.  Source: program counter —
``start.publish``: ``ServingEngine.publish`` whole — the user table, the
histories, the catalog and the index's own copy handed to the device, the
int8 rows made there.
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.seconds(
        lambda paths: start_phases.named(start_phases.top(paths), ".publish"))
