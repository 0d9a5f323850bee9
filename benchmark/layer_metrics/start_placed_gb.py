"""Layer: serving path.  Source: program counter —
``start.placed_bytes`` of the top-level phases, in GB (1e9 bytes): every
byte of a whole table handed host → device inside a start phase
(``device.placed_bytes`` counts them where they go up) — the tables' copies
as a number: V up to three times and U twice where items are folded.
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.placed_gb()
