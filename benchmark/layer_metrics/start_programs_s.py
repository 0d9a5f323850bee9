"""Layer: serving path.  Source: program counter —
``jax.program_seconds`` before traffic: the seconds JAX traced, lowered and
spent in the backend's compile call (a cache's fetch included) for every
program since the first engine or fold-in server was built, pinned or not.
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.before_traffic("jax.program_seconds")
