"""Layer: serving path.  Source: program span — the median life (stage start
to complete end) of the traced serving batches that lay mostly under a phase
of the updater's ``live.batch`` MINUS the median of those that lay mostly
under none (the updater idle or coalescing), most-ridden bucket: the price of
the overlap.  Prints the run's ``life_table`` line (per updater phase:
batches, median and 90th-percentile life, ms).  ``None`` on a program that
writes no phase span (before ISSUE 54).  Moves ``serve_p90_ms``."""

from benchmark import live_phase_spans


def read(ctx):
    return live_phase_spans.metric(ctx, "serve_life_beside_live_ms",
                                   table="life_table")
