"""Layer: live write path.  Source: program span — ``live.landing.swap``: the
landed generation installed under the engine's table lock (one assignment:
nothing is written, the tables were built beside the live ones), how long a
request's stage can be kept out by a landing; mean over the landings of the
measured window, from the program's own record.  ``None`` on a program that
lands nothing (before ISSUE 59).  Moves ``serve_p90_ms``."""


def read(ctx):
    took = [rec["seconds"]["swap"] for rec in ctx.counters.get("landings")
            or () if "swap" in rec.get("seconds", {})]
    return 1e3 * sum(took) / len(took) if took else None
