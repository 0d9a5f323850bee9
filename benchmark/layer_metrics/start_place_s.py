"""Layer: serving path.  Source: program counter —
the seconds of every leaf that hands a table to the device (a phase named
``.place``, ``.users`` or ``.catalog``: the engine's user table and
catalog, the index's copy, the histories, a fold-in server's fixed sides).
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.seconds(lambda paths: start_phases.named(
        start_phases.leaves(paths), *start_phases.PLACES))
