"""``tpu_als.obs`` — unified metrics/tracing for the whole stack.

Usage (the instrumented hot paths all go through the module-level
default registry, so library users get process-wide aggregation for
free):

    from tpu_als import obs

    with obs.span("train.fit"):
        ...
    obs.counter("ingest.rows", n)
    obs.histogram("serve.request_seconds", dt, strategy="ring")
    obs.gauge("train.comm_bytes_per_iter", b, strategy="ring")

    obs.configure(run_dir)      # start of a run (CLI does this)
    ...
    obs.finalize()              # drain events.jsonl / metrics.prom /
                                # run_manifest.json into run_dir

Everything is cheap in-memory bookkeeping until ``finalize``; a registry
that is never configured simply accumulates (bounded) in-memory state —
safe for library use and for the test suite.  See
docs/observability.md for the event schema and run-dir layout.
"""

from __future__ import annotations

from tpu_als.obs.metrics import BUCKET_BOUNDS, MetricsRegistry  # noqa: F401
from tpu_als.obs import schema  # noqa: F401

_default = MetricsRegistry()


def default_registry():
    return _default


def reset():
    """Replace the default registry with a fresh one (tests)."""
    global _default
    _default = MetricsRegistry()
    return _default


def counter(name, value=1, **labels):
    _default.counter(name, value, **labels)


def gauge(name, value, **labels):
    _default.gauge(name, value, **labels)


def histogram(name, value, **labels):
    _default.histogram(name, value, **labels)


def histogram_many(name, values, **labels):
    _default.histogram_many(name, values, **labels)


def histogram_quantile(name, q, **labels):
    return _default.histogram_quantile(name, q, **labels)


def histogram_count(name, **labels):
    return _default.histogram_count(name, **labels)


def counter_value(name, **labels):
    return _default.counter_value(name, **labels)


def counter_series(name):
    return _default.counter_series(name)


def emit(etype, **fields):
    return _default.emit(etype, **fields)


def span(name, **labels):
    return _default.span(name, **labels)


def unscoped_span(name, **labels):
    return _default.unscoped_span(name, **labels)


def open_spans():
    return _default.open_spans()


def configure(run_dir, config=None, argv=None):
    _default.configure(run_dir, config=config, argv=argv)


def active():
    return _default.active()


def deconfigure():
    _default.deconfigure()


def update_manifest(**fields):
    _default.update_manifest(**fields)


def snapshot():
    return _default.snapshot()


def prometheus_text():
    return _default.prometheus_text()


def finalize():
    return _default.finalize()
