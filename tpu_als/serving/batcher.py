"""Micro-batching admission queue — the request-shaping front of serving.

The batch kernels (``ops/topk.py``, ``serving/index.py``) want fixed
shapes: one compiled executable per batch size, fed as full as possible.
Online traffic wants the opposite — single-user requests arriving at
arbitrary times with per-request deadlines.  This queue converts one
into the other:

- no request is held back for coalescing longer than ``max_wait_s``
  after it ARRIVED.  What closes a batch depends on who asks.  A
  consumer with a pipeline to ask (the engine's own loop, which takes
  one of its ``MAX_IN_FLIGHT`` slots BEFORE it dequeues) passes
  ``coalesce=False``: the batch closes as soon as the queue is not
  empty, with everything queued (``slot``).  Requests then coalesce
  only while that consumer is away — waiting for a slot, or staging
  and dispatching the batch before — so a batch's size follows the
  pipeline's state, not a clock: the first arrival into an empty queue
  beside an idle device rides alone, and under saturation the queue
  fills while both slots are taken (a queue that has outgrown the
  second-largest bucket by less than its size again goes as that
  bucket, full, and a remainder: the largest program, a third full,
  takes longer than its rows took to arrive, and a loop that pops on
  completion would then never leave it).  A consumer with no pipeline to
  ask (a scheduler's round, a synchronous ``serve_batch`` caller)
  keeps the timed rule: a batch closes when its oldest request has
  waited ``max_wait_s`` (or the largest bucket fills, whichever is
  first), counted from the head ticket's ``t_submit``, so requests
  that queued while the batch before was being scored have had their
  coalescing time and pop at once.  Either way ``max_wait_s`` is an
  upper bound: the slot rule only ever closes earlier;
- the engine pads each dequeued batch up to the smallest bucket that
  fits (``bucket_for``), so the scoring executable compiles once per
  bucket instead of once per observed batch size;
- when queue depth reaches ``max_queue`` the submit is refused with a
  typed :class:`Overloaded` (counted as ``serving.shed``) — shedding at
  admission beats queueing requests that will miss their deadline
  anyway;
- each request carries an absolute deadline; the engine expires
  requests whose deadline passed while queued (``serving.expired``)
  instead of spending device time on answers nobody is waiting for.

The consumer side writes the two spans of the engine thread's cycle that
belong to the queue (``serve.idle``, ``serve.batch.coalesce``:
``obs.schema.SERVE_BATCH_SPAN_KEYS``) onto the profiler's timeline; with
no profiler recording, an annotation costs about a microsecond.  Nothing
here touches a device, so the queue stays testable without one.
"""

from __future__ import annotations

import collections
import threading
import time

from jax.profiler import TraceAnnotation

from tpu_als import obs
from tpu_als.obs import tracing

DEFAULT_BUCKETS = (8, 32, 128)


class Overloaded(RuntimeError):
    """Admission refused: queue depth is at ``max_queue``.  Callers that
    can retry should back off; load balancers should route elsewhere."""


class DeadlineExceeded(RuntimeError):
    """The request's deadline passed before it was scored (expired in
    the queue, or the caller's ``result(timeout=...)`` ran out)."""


def bucket_for(n, buckets):
    """Smallest bucket >= n (the padded batch shape ``n`` rides in).
    ``n`` never exceeds ``max(buckets)`` — the batcher caps dequeues at
    the largest bucket."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} exceeds the largest bucket "
                     f"{buckets[-1]} (batcher dequeues are capped there)")


class Ticket:
    """One admitted request: payload + deadline + a completion event.

    ``payload`` is either an int user index into the published user
    table or a rank-length float vector (a fold-in factor row for a
    user the table doesn't hold yet); ``k`` trims the engine-wide top-k
    per request.  ``trace`` is the admitting causal-trace context
    (``obs.tracing``, None when disarmed): the ticket carries it into
    the batch, and each hop replaces it with the child context so the
    chain admission -> queue -> round -> score is one linked trail.
    ``seq`` is the publish sequence number of the generation that
    answered it, stamped where its batch read the live generation (None
    until then): every score and id of the answer is that generation's.
    ``exclude`` is the request's own list of catalog ids it is not to be
    answered with (an int32 array the engine validated, or None).
    """

    __slots__ = ("payload", "k", "deadline", "trace", "t_submit",
                 "t_dequeue", "t_done", "t_admit", "seq", "exclude",
                 "_event", "_result", "_error")

    def __init__(self, payload, k, deadline, trace=None, exclude=None):
        self.payload = payload
        self.k = k
        self.exclude = exclude
        self.deadline = deadline        # absolute perf_counter time, or None
        self.trace = trace              # TraceContext of the last hop, or None
        self.t_submit = time.perf_counter()
        self.t_dequeue = None
        self.t_done = None     # answered or failed (perf_counter, as above)
        self.t_admit = None    # admission DURATION (engine submit -> queued)
        self.seq = None        # the generation that answered (engine)
        self._event = threading.Event()
        self._result = None
        self._error = None

    def complete(self, result):
        self._result = result
        self.t_done = time.perf_counter()
        self._event.set()

    def fail(self, error):
        self._error = error
        self.t_done = time.perf_counter()
        self._event.set()

    def done(self):
        return self._event.is_set()

    def result(self, timeout=None):
        """Block until the engine answers; raises the typed error the
        engine failed the request with (Overloaded never reaches here —
        it raises at submit)."""
        if not self._event.wait(timeout):
            raise DeadlineExceeded(
                f"no result within {timeout}s (request still queued or "
                "in flight)")
        if self._error is not None:
            raise self._error
        return self._result


class MicroBatcher:
    """Bounded FIFO admission queue with coalescing dequeues.

    One producer-side method (:meth:`submit`) and one consumer-side
    method (:meth:`next_batch`, called by the engine loop).  A single
    condition variable guards the deque; the submit fast path is one
    lock round-trip.
    """

    def __init__(self, buckets=DEFAULT_BUCKETS, max_queue=1024,
                 max_wait_s=0.002, default_deadline_s=None, labels=None):
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise ValueError(f"buckets must be sorted and unique, got "
                             f"{buckets!r}")
        self.buckets = tuple(int(b) for b in buckets)
        self.max_queue = int(max_queue)
        self.max_wait_s = float(max_wait_s)
        self.default_deadline_s = default_deadline_s
        # obs attribution (e.g. tenant=<name> from the multi-tenant
        # control plane); every serving.* series this queue writes
        # carries these label keys, validated by obs.schema.LABELS
        self.labels = dict(labels) if labels else {}
        self._q = collections.deque()
        self._cond = threading.Condition()
        self._closed = False
        # the consumer's own account of its last dequeue, for the
        # engine's per-batch record: seconds blocked on an empty queue
        # since the batch before, requests waiting as the coalescing
        # wait began, seconds from then to the batch popped
        self.last_wait = (0.0, 0, 0.0)
        # what closed that batch (serving.batch_closed's ``by``) and
        # how long its head had already waited as the consumer arrived
        self.closed_by = None
        self.head_wait = 0.0
        self._idle_s = 0.0

    def depth(self):
        with self._cond:
            return len(self._q)

    def submit(self, payload, k=None, deadline_s=None, trace=None,
               exclude=None):
        """Admit one request; returns its :class:`Ticket`.

        Raises :class:`Overloaded` (and counts ``serving.shed``) when
        the queue is full — the caller gets the refusal in microseconds
        instead of a deadline miss in milliseconds.  ``trace`` is the
        admitting trace context (created BEFORE enqueue so the consumer
        thread never races an unset ``Ticket.trace``).
        """
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        deadline = (time.perf_counter() + deadline_s
                    if deadline_s is not None else None)
        t = Ticket(payload, k, deadline, trace=trace, exclude=exclude)
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            if len(self._q) >= self.max_queue:
                obs.counter("serving.shed", **self.labels)
                raise Overloaded(
                    f"admission queue at capacity ({self.max_queue}); "
                    "shedding")
            self._q.append(t)
            self._cond.notify()
        return t

    def next_batch(self, timeout=None, coalesce=True):
        """Dequeue the next micro-batch (one consumer only).

        Blocks up to ``timeout`` for the first request.  Then, with
        ``coalesce`` (the default: a caller with no pipeline to ask), it
        coalesces arrivals until the HEAD of the queue (its oldest
        ticket) has waited ``max_wait_s`` since its ``t_submit``, the
        largest bucket fills or the batcher closes.  A head that is
        already that old when the consumer arrives — it queued while
        the batch before was scored — pops at once with everything
        queued (``age``); a younger one waits only the remainder
        (``wait``); the first arrival into an empty queue waits all of
        ``max_wait_s`` for company.  With ``coalesce=False`` (the
        engine's own loop, which holds a free slot of its pipeline as it
        calls) nothing is waited for: whatever is queued is popped at
        once (``slot``; ``full`` and ``closed`` as above), and what
        coalesced did so while the caller was away.  Such a caller is
        back right after its dispatch, so where the queue holds more
        than the second-largest bucket and at most twice that, it gets
        that bucket full and the rest next time, not the largest
        program mostly empty.
        Returns a list of tickets (``t_dequeue`` stamped), or ``None``
        on timeout with an empty queue.  Also sets the
        ``serving.queue_depth`` gauge to the post-dequeue backlog,
        counts ``serving.batch_closed{by=...}``, and leaves
        ``last_wait``, ``closed_by`` and ``head_wait`` saying what the
        dequeue waited for.
        """
        cap = self.buckets[-1]
        with self._cond:
            if not self._q:
                t_idle = time.perf_counter()
                with TraceAnnotation("serve.idle"):
                    self._cond.wait_for(
                        lambda: self._q or self._closed, timeout)
                self._idle_s += time.perf_counter() - t_idle
                if not self._q:        # timed out, or closed and drained
                    return None
            # coalesce: the head's own clock runs the batching window
            t_first = time.perf_counter()
            waiting = len(self._q)
            t_head = self._q[0].t_submit
            t_close = t_head + self.max_wait_s
            head_wait = t_first - t_head
            # no wait here: the head had had its own, or none is asked for
            closed_by = "age" if coalesce else "slot"
            with TraceAnnotation("serve.batch.coalesce",
                                 waiting=waiting) as span:
                while True:
                    if len(self._q) >= cap:
                        closed_by = "full"
                        break
                    if self._closed:
                        closed_by = "closed"
                        break
                    if not coalesce:
                        break
                    remaining = t_close - time.perf_counter()
                    if remaining <= 0:
                        break
                    closed_by = "wait"
                    self._cond.wait(remaining)
                take = min(len(self._q), cap)
                if not coalesce and len(self.buckets) > 1:
                    # a few rows over the rung below would ride the
                    # largest program mostly empty, for longer than
                    # they took to arrive: the queue refills meanwhile
                    # and every later batch does the same.  This
                    # consumer is back as soon as it has dispatched, so
                    # a full batch of the rung below goes now and the
                    # rest next
                    below = self.buckets[-2]
                    if below < take <= 2 * below:
                        take = below
                batch = [self._q.popleft() for _ in range(take)]
                depth_after = len(self._q)
                span.set_metadata(closed_by=closed_by,
                                  head_wait=head_wait)
        now = time.perf_counter()
        self.last_wait = (self._idle_s, waiting, now - t_first)
        self.closed_by, self.head_wait = closed_by, head_wait
        self._idle_s = 0.0
        waits = []
        for t in batch:
            t.t_dequeue = now
            waits.append(now - t.t_submit)
            # the queue owns the queue-wait hop: chain it here so the
            # span's seconds are the histogram's sample, not a re-read
            if t.trace is not None:
                t.trace = tracing.record_span(
                    t.trace, "serve.queue", seconds=waits[-1])
        obs.histogram_many("serving.enqueue_seconds", waits, **self.labels)
        obs.gauge("serving.queue_depth", depth_after, **self.labels)
        obs.counter("serving.batch_closed", by=closed_by, **self.labels)
        return batch

    def close(self):
        """Stop admitting; wake the engine loop so it can drain + exit."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
