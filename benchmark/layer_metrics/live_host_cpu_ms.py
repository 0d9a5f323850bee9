"""Layer: live write path.  Source: program span — the updater thread's own
CPU time (stat ``cpu_us``) in the spans directly under its ``live.batch``
(``.prepare``, ``.foldin``, ``.publish``, ``.record``), summed over the traced
seconds, per batch: the interpreter time the engine's two threads compete
with.  Over ``live_batch_host_ms`` it is ``live_batch_cpu_pct`` less what lies
between those four and what their stamps burn; the run's ``phase_table`` line
splits it by phase (each phase's OWN: its stat less the stamped spans inside).
``None`` on a program that writes no such span (before ISSUE 54).  Moves
``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "live_host_cpu_ms")
