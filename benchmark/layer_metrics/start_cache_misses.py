"""Layer: serving path.  Source: program counter —
``jax.programs{stage=compile}`` before traffic whose ``cache`` is not
``hit``: backend-compile calls the persistent compilation cache did not
answer — 0 on a warm start, tens on a cold one.
``None`` on a program that keeps no such counter (before ISSUE 55).  Moves
``setup_s``."""

from benchmark import start_phases


def read(ctx):
    return start_phases.before_traffic(
        "jax.programs", lambda labels: labels.get("stage") == "compile"
        and labels.get("cache") != "hit")
