"""Layer: live write path.  Source: program span — the share of the
``live.batch`` spans' wall time that lies under no phase: the own time of the
spans that only hold others (``live.batch``, ``.foldin``, ``.foldin.users`` /
``.items``, ``.publish``), the Python between two phases.  Prints the run's
``phase_table`` line (per phase, and per container under ``<its name>/own``:
median wall ms a batch, own CPU ms a batch;
``stamps``: what the stamped spans cover beyond the interval they timed, the
instrument's own cost in a traced run, in no phase and not in this share).
``None`` on a program that writes no such span (before ISSUE 54).  Moves
``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "live_batch_unsplit_pct",
                                   table="phase_table")
