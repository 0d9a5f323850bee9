"""Layer: live write path.  Source: program counter —
``live.landing.peak_bytes``: ``peak_bytes_in_use`` of the device as a landing
ends (the most it has held since the process began: a landing holds two
engine generations until its swap), in GB (1e9 bytes), the largest over the
landings of the measured window.  0 where the backend keeps no such
statistic; ``None`` on a program that lands nothing (before ISSUE 59).  Moves
``serve_p90_ms`` (a landing that does not fit is an outage)."""


def read(ctx):
    peaks = [rec["peak_bytes"] for rec in ctx.counters.get("landings") or ()
             if "peak_bytes" in rec]
    return 1e-9 * max(peaks) if peaks else None
