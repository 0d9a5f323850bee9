"""Layer: serving path.  Source: program counter —
the wall seconds of the TOP-level ``start.*`` phases (``start.seconds`` by
``path``, no '/'): ``publish``, ``warmup``, and in a live cell
``FoldInServer(...)``, ``prewarm`` and ``LiveUpdater.start`` — the program's
own share of ``setup_s``; the rest is the benchmark's (imports, factors,
histories, warm batches, the stream's head).  The traced run's own start,
like every ``start_*`` metric.
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.seconds(start_phases.top)
