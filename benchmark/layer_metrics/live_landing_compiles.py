"""Layer: live write path.  Source: program counter — programs that reached
the backend's compile call while a landing was under way (the compile
ledger's count across it, ``obs/compiles.py``: any thread's), summed over
the landings of the measured window: 0 where every program a landing runs
was run ahead of the traffic (``LiveUpdater(refits=True).start()``) and the
landed tables have the live generation's shapes.  ``None`` on a program that
lands nothing (before ISSUE 59).  Moves ``serve_p90_ms`` (a compile on a
serving host is seconds of it)."""


def read(ctx):
    made = [rec["programs"] for rec in ctx.counters.get("landings") or ()
            if "programs" in rec]
    return float(sum(made)) if made else None
