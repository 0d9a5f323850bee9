"""Layer: live write path.  Source: program span — ``live.landing``: a whole
refit landed on the running updater (``LiveUpdater.land``), from the call to
the record — the wait for the loop to stand between two batches, the fold-in
server's tables replaced and the catch-up folded, the engine's generation
built beside the live one, the swap, the release — as the program's own
record of the landing gives it (``LiveUpdater.landings``: the seconds of the
same intervals its spans cover), mean over the landings of the measured
window.  ``None`` on a program that lands nothing (before ISSUE 59).  Moves
``serve_p90_ms``."""


def read(ctx):
    took = [rec["seconds"]["whole"] for rec in ctx.counters.get("landings")
            or () if "whole" in rec.get("seconds", {})]
    return 1e3 * sum(took) / len(took) if took else None
