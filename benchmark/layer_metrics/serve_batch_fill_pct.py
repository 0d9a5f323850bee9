"""Layer: serving path.  Source: program span — requests scored over rows
paid for: 100 x the summed ``rows`` over the summed ``bucket`` of the
``serve.batch`` spans in the traced seconds.  Moves ``serve_p50_ms``."""

from benchmark import program_spans


def read(ctx):
    c = program_spans.traced_cycle(ctx)
    if c is None or not c["bucket_rows"]:
        return None
    return 100.0 * c["rows"] / c["bucket_rows"]
