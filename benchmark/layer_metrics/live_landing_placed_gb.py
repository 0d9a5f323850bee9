"""Layer: live write path.  Source: program counter —
``live.landing.bytes_placed``: bytes of whole tables a landing handed
host → device (the growth of ``device.placed_bytes`` across it: the fold-in
server's two tables; the engine's generation is copied from them on the
device), in GB (1e9 bytes), mean a landing of the measured window.  ``None``
on a program that lands nothing (before ISSUE 59).  Moves ``serve_p90_ms``."""


def read(ctx):
    sent = [rec["placed_bytes"] for rec in ctx.counters.get("landings") or ()
            if "placed_bytes" in rec]
    return 1e-9 * sum(sent) / len(sent) if sent else None
