"""Layer: serving path.  Source: host clock — the longest latency (due to
answer in hand) among all requests of the whole window: the one reported
number that a stall of the serving process cannot hide from, whatever share
of the requests it touches.  Moves the cell's tail metric (``serve_p90_ms``)."""


def read(ctx):
    lat = ctx.counters.get("latency_ms")
    return None if lat is None or not len(lat) else float(lat.max())
