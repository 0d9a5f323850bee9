"""Layer: serving path.  Source: program span — the median life (stage start
to complete end) of the traced serving batches that overlapped a
``live.landing`` span MINUS the median of those that overlapped none,
most-ridden bucket: what a landing under way costs a batch (its placements
share the link with the batch's upload, its programs the device).  The join
is ``pipeline_spans``' (a batch's spans of one ``seq`` on two threads), as
``serve_life_beside_live_ms`` joins the updater's phases.  Prints a
``landing_life_table`` line (batches, median and 90th-percentile life, ms, by
class).  ``None`` where the trace holds no ``live.landing`` span (a program
before ISSUE 59, a traced stream without a landing).  Moves
``serve_p90_ms``."""

import collections
import statistics

import numpy as np

from benchmark import pipeline_spans

LANDING = "live.landing"


def lives(serve, landings):
    """``{"beside" | "alone": [life ns]}`` of the serving batches of the
    most-ridden bucket by whether their life overlapped one of
    ``landings`` (``[(start_ns, end_ns)]``)."""
    whole, _ = pipeline_spans.batches(serve)
    if not whole:
        return {}
    (bucket, _), = collections.Counter(
        b.bucket for b in whole.values()).most_common(1)
    out = {"beside": [], "alone": []}
    for b in whole.values():
        if b.bucket == bucket:
            met = any(b.T0 < end and start < b.T5 for start, end in landings)
            out["beside" if met else "alone"].append(b.T5 - b.T0)
    return out


def read(ctx):
    found = pipeline_spans.traced(ctx)
    if found is None:
        return None
    serve, live = found[0], found[1]
    landings = [(s[1], s[1] + s[2]) for s in live if s[0] == LANDING]
    if not landings:
        return None
    by_class = lives(serve, landings)
    ctx.cell.say("landing_life_table", landings=len(landings), **{
        name: [len(ls), round(1e-6 * statistics.median(ls), 4),
               round(1e-6 * float(np.percentile(ls, 90)), 4)]
        for name, ls in by_class.items() if ls})
    if not by_class.get("beside") or not by_class.get("alone"):
        return None
    return 1e-6 * (statistics.median(by_class["beside"])
                   - statistics.median(by_class["alone"]))
