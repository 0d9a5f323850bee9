"""Online serving subsystem: the request-level path over ALS factors.

The batch surfaces (``recommendForAllUsers``, ``parallel/serve.py``)
score every user in one offline pass; this package turns the same
kernels into an ONLINE path — per-request latency, admission control,
SLO instrumentation:

- :mod:`tpu_als.serving.batcher` — micro-batching admission queue:
  bucketed fixed-shape batches, per-request deadlines, typed
  :class:`Overloaded` load shedding.
- :mod:`tpu_als.serving.index` — int8 symmetric-quantized candidate
  index with exact f32 rescore (top-k within ``SCORE_ULPS`` of the
  exact kernel; property-tested).
- :mod:`tpu_als.serving.engine` — the steady-state loop wiring batcher
  -> scorer -> response, with atomic model publishes, stale-index
  fallback, and the ``serving.score`` / ``serving.publish`` fault
  points.

``tpu_als serve-bench`` drives a synthetic open-loop load through the
engine and reports p50/p99 against an SLO; see docs/serving.md.
"""

from tpu_als.serving.batcher import (
    DEFAULT_BUCKETS,
    DeadlineExceeded,
    MicroBatcher,
    Overloaded,
    Ticket,
    bucket_for,
)
from tpu_als.serving.engine import NoModelPublished, ServingEngine
from tpu_als.serving.index import Int8CandidateIndex, build_index

__all__ = [
    "DEFAULT_BUCKETS",
    "DeadlineExceeded",
    "Int8CandidateIndex",
    "build_index",
    "MicroBatcher",
    "NoModelPublished",
    "Overloaded",
    "ServingEngine",
    "Ticket",
    "bucket_for",
]
