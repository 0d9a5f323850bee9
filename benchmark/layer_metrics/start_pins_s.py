"""Layer: serving path.  Source: program counter —
the ``start.pin`` leaves: every pinned scoring program loaded from the pin
store, or lowered, compiled and written there (the ``serving_pin`` event
has each one's split).
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.seconds(
        lambda paths: start_phases.named(paths, "start.pin"))
