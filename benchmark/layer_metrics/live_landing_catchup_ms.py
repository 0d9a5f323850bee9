"""Layer: live write path.  Source: program span — ``live.landing.catchup``:
the entities with an event admitted since the refit's snapshot folded again
over all their kept ratings, against the landed tables, users then items, in
rounds (the fold-in program's calls, their rows read back and written into
the fold-in server's tables); mean over the landings of the measured window,
from the program's own record.  ``None`` on a program that lands nothing
(before ISSUE 59).  Moves ``serve_p90_ms``."""


def read(ctx):
    took = [rec["seconds"]["catchup"] for rec in ctx.counters.get("landings")
            or () if "catchup" in rec.get("seconds", {})]
    return 1e3 * sum(took) / len(took) if took else None
