"""Steady-state serving loop: batcher -> scorer -> response.

Two background threads.  The ENGINE thread drains the
:class:`~tpu_als.serving.batcher.MicroBatcher`, pads each micro-batch to
its bucket and dispatches it against the currently-published model (int8
shortlist + exact rescore when an index is live, the exact chunked
kernel otherwise); the COMPLETION thread reads each response back and
completes the tickets, in dispatch order.  The pieces the rest of the
stack plugs into:

- **Two batches in flight.**  A batch is two halves:
  :meth:`ServingEngine._begin` (expiry, staging, the scoring call, all
  under one hold of ``_table_lock``) and :meth:`ServingEngine._finish`
  (the one device→host transfer, the tickets, their records).
  ``_run`` does the first and hands the batch over
  (:class:`_Flown`: the tickets, the response on the device, the stamps
  taken so far) through a FIFO to ``_run_completions``, which does the
  second: the engine thread is back at the queue after stage + dispatch
  and no request waits behind another batch's readback.  At most
  ``MAX_IN_FLIGHT`` (2) batches are handed over and not yet completed,
  and the slots are what closes a batch: the engine thread takes one
  BEFORE it dequeues, and with a slot in hand it pops whatever is
  queued at once (``next_batch(coalesce=False)``, ``closed_by`` /
  ``serving.batch_closed`` ``slot``) — no request waits out
  ``max_wait_s`` beside a device that could take it.  Requests coalesce
  only while the thread is away: staging and dispatching the batch
  before, or, with two in flight, waiting for the older to complete
  (``handoff_wait``) — the admission queue fills meanwhile and the next
  batch is larger, which is the back-pressure saturation wants (a
  queue just over the second-largest bucket goes as that bucket, full,
  and a remainder, not as the largest program mostly empty:
  ``MicroBatcher.next_batch``).  A
  batch's size so follows the pipeline's own state, at any rate, and
  ``max_wait_s`` stays the upper bound it was, binding only for callers
  that drive ``next_batch`` themselves.  Whether the
  overlap engages follows from the traffic alone (is the next batch
  closed before the last is read back?), and
  ``serving.batch_overlap{in_flight=0|1}`` counts it.  The completion
  thread touches neither ``_table_lock`` nor a table.
  :meth:`ServingEngine.serve_batch` runs both halves on its caller's
  thread, for schedulers and tests that drive the engine themselves.
- **Which thread writes which span** (``obs.schema.
  SERVE_BATCH_SPAN_KEYS``): the engine thread ``serve.idle``,
  ``serve.batch.coalesce`` (both in the batcher), ``serve.batch``
  around ``serve.batch.stage`` and ``serve.batch.dispatch`` (inside it
  ``.dispatch.upload`` and ``.dispatch.launch``,
  ``SERVE_DISPATCH_SPAN_KEYS``); the completion thread
  ``serve.batch.readback`` and ``serve.batch.complete``.  Every span of
  a batch carries its ``seq``, and — while a profiler session records —
  each phase ``cpu_us`` and ``wall_us``, the thread's own CPU time
  inside it beside the wall time of the same interval
  (:func:`cpu_mark`, :func:`stamp_cpu`), and the stage span
  ``lock_wait_us``, its wait for ``_table_lock``.  A publisher's thread
  writes the publish's phases (:class:`Stamped`: ``live.batch.publish.
  users``, ``.catalog``, ``.send``, ``.ride``, ``.lock_wait``,
  ``.writes``, ``.after``; ``LIVE_PHASE_SPAN_KEYS``).  The engine
  thread's wait
  for a slot is ``pipe.slot_wait`` (``PIPE_SPAN_KEYS``), outside the
  ``serve.`` prefix: a trace reader counts every ``serve.`` span as a
  phase of a batch.  What the completion thread waits for when it has
  nothing is no span for the same reason: it is the batch record's
  ``completion_idle``.
- **Atomic publishes, no recompile.**  :meth:`ServingEngine.publish`
  places the new U/V on device once and swaps a single reference under
  a lock; in-flight batches finish against the old tables, the next
  batch sees the new ones.  The scoring executables are keyed on
  (bucket, k, catalog shape) only, so a same-shape publish — the steady
  state of periodic retraining — reuses every compiled program, and the
  dropped reference releases the old device buffers (the donation
  pattern: the engine owns its buffers, callers hand factors over and
  must not mutate them afterwards).  On a STARTED engine that is a
  refit LANDING (``LiveUpdater.land``): the new generation is built
  beside the live one at the live capacities — spare rows, the
  segment's slots: same shapes, same pins — optionally from tables the
  caller already holds on the device (``placed=``), and ``release=``
  deletes the generation it replaced at once (:meth:`ServingEngine.
  publish`, :meth:`ServingEngine.warmup_landing`).
- **Stale-index fallback.**  Each publish carries a sequence number;
  an index whose ``seq`` doesn't match the live model (a publish with
  ``quantize=False`` after a quantized one, or a ``serving.publish``
  corrupt-mode fault) is never scored against — the batch takes the
  exact path and ``serving.fallback_exact`` counts it.
- **Incremental publishes.**  :meth:`ServingEngine.publish_update` is
  the live fold-in → publish path: the user table lives on the device
  with spare rows (``core.ratings.row_capacity``), so touched and
  appended user rows are uploaded alone and written into it IN PLACE:
  the table is donated to the row write (:func:`_scatter_users`), so a
  publish costs the device O(touched rows), never a copy of the table,
  and a generation's user table lives until the next row write, not for
  ever.  Programs run in dispatch order, so a batch dispatched before
  the write reads the old rows whole and a batch dispatched after it
  the new ones; what the donation deletes is only the HOST's handle, so
  one short lock (``_table_lock``) orders the two host-side uses of it:
  the engine thread holds it from reading the live generation (after
  the batch's dequeue) to the scoring call's return, a publisher around
  the donating call and the swap; a batch still in flight was
  dispatched before the write and reads the old rows.  No shape changes, the pinned executables stay valid, and
  nothing of the catalog crosses host→device; a user-only fold-in re-tags the
  current index (zero quantization).  An item fold-in uploads ONLY the
  touched/appended rows, once: they are quantized on the device into
  the index's delta segment (``serving/index.py``; a fixed number of
  slots, so the one scoring program "with a segment" a bucket keeps its
  shapes) and written, from the same device arrays, into the engine's
  own catalog in place (:func:`_scatter_items`, donated like the user
  table and under the same lock); appended items fall on the spare
  rows :meth:`ServingEngine.warmup_live` gave the catalog.  The segment
  is folded back into the base arrays, IN PLACE (they are donated:
  :meth:`ServingEngine._compact_live`), when it crosses the
  planner-resolved compaction threshold: ONE generation of the catalog
  on the device, whatever is published.  On a generation that holds
  its users' HISTORIES (``publish(user_seen=...)``) a publish also
  appends the ids its ratings add to them (``seen_appended``), behind
  their users' runs on the device, in place, and the rows and the ids
  become servable as ONE generation (:class:`_Seen`,
  :func:`_append_runs`); such a generation's catalog does not move.  Every
  mode lands in the ``serving.publish_seconds`` histogram so the
  O(touched)-vs-O(catalog) publish cost claim is measured, not assumed.
- **Fault points.**  ``serving.publish`` fires inside publish (corrupt
  = the fresh index is dropped before the swap — the previous
  generation's index is carried, stale by seq, or ``None`` on a first
  publish); ``serving.score`` fires per batch
  (corrupt = treat the index as stale for this batch; raise = the
  injected error fails the batch's tickets, visible to every waiting
  caller).
- **Metrics.**  enqueue/score/e2e latency histograms, queue-depth
  gauge, shed/expired/fallback counters — all through ``tpu_als.obs``
  (see docs/serving.md for the vocabulary).
- **Flight recorder.**  Every request outcome is recorded into a
  bounded ring (:class:`~tpu_als.obs.trace.FlightRecorder`) with its
  admission / queue-wait / score / respond span breakdown and the
  ``batch`` it rode; on an SLO breach (``slo_s``), a shed, or a
  degraded-mode (exact-fallback) answer, the ring's not-yet-dumped tail
  is emitted as ``flight_record`` events — so a p99 outlier leaves the
  last N request traces in the obs trail instead of vanishing into a
  histogram bucket.
- **The batch cycle on the profiler's clock.**  A batch's phases —
  idle, coalesce, stage, dispatch, readback, complete — are
  ``TraceAnnotation`` spans (``obs.schema.SERVE_BATCH_SPAN_KEYS``),
  always on: under ``jax.profiler.trace`` they sit beside the device's
  row, so every idle gap of the device falls under the phases that held
  the two threads.  The same durations go into one record per batch
  in a second ring, ``batch_flight``, dumped on the same triggers, with
  ``in_flight`` and ``handoff_wait`` (the engine thread's wait for one
  of two batches in flight to complete: the saturation signal), the two
  halves of dispatch (``upload``, ``launch``) and each phase's CPU
  seconds (``cpu``; ``None`` for a batch no profiler watched: the CPU
  clock costs too much on the chip's host to be read for nobody).
- **A start read from inside.**  ``publish`` and every warm-up are
  tiled by START PHASES (``obs.phases.phase``, names in
  ``obs.schema.START_PHASES``): ``start.publish`` around the user
  table's, the histories', the catalog's and the index's own placement
  and the quantization; ``start.warmup`` / ``.warmup_publish`` /
  ``.warmup_live`` / ``.warmup_histories`` around one ``start.pin`` a
  pinned program and one ``start.first_run`` wherever a warm-up RUNS
  what it pinned or a write program.  A phase closes with its seconds,
  CPU seconds, bytes handed to the device
  (``device.placed_bytes{table}``, counted where a table goes up, under
  traffic too), device bytes in use and the programs JAX made inside it
  (``obs.compiles``: the process's compile ledger, installed by the
  first engine; ``start()`` / ``stop()`` tell it when traffic runs, and a
  program that compiles then is named in a ``warning``).  A phase is a
  ``TraceAnnotation`` and never a ``jax.named_scope``, and a ``with``
  block, never a wrapper: it lies around ``lower()``.
- **One algorithm on any number of chips.**  The engine serves the
  int8 shortlist + exact f32 rescore from a candidate index: an
  :class:`~tpu_als.serving.index.Int8CandidateIndex` when ``mesh`` is
  None, a :class:`~tpu_als.serving.index.ShardedInt8Index` (the same
  shortlist and rescore per shard) when a mesh is given; a stale or
  absent index falls back to the exact chunked scan.  ``_build_index``
  is the one place that reads the mesh to choose.  With a mesh ALL the
  tables live sharded by rows over it, shard ``s`` holding a contiguous
  block: the catalog and its int8 rows (no device ever holds the whole
  of them, and the engine and its index share the one sharded copy) and
  the user table (spare rows on the last shards).  A batch is ONE
  program (``serving.index._build_sharded_int8`` behind
  :func:`_mesh_queries`, :meth:`ServingEngine._int8_call`; a device
  trace names it ``jit_serve_mesh_int8``): the staged batch is placed on
  the mesh's first device alone and one all-reduce spreads it
  (:meth:`ServingEngine._place_one`, :func:`_mesh_spread`), every
  shard takes the user rows
  it owns for the batch's ids and one all-reduce sums them
  (:func:`_mesh_lookup`), every shard scores its slice, two all-gathers
  and one ``top_k`` merge the local lists on every shard, and the
  packed response comes back in one transfer.  The row write of a
  publish goes into the owning shard in place
  (:func:`_build_mesh_scatter`), and the exact fallback scores per
  shard too (:func:`_build_mesh_exact`): nothing but the staged batch
  and a publish's touched rows is ever uploaded.  Users' HISTORIES
  (``publish(user_seen=...)``) are sharded with the user table — a
  history lies once on the mesh, on the chip that holds its user's row
  (:meth:`ServingEngine._shard_seen`) — the owning shard hands a batch
  that excludes its lists inside the program, one more all-reduce
  (:func:`_mesh_history`), and every shard masks the ids it owns among
  its own columns (``serving.index.shard_lists``): one program a bucket
  and history pad, on the int8 path and the exact fallback alike.
- **Host throughput.**  The request path stages each micro-batch into
  one ``[B, rank+2]`` int32 array (query rows' f32 bits | ids |
  row-mask; a new one every batch, since the batch before may still be
  in flight from its own) and uploads it as ONE transfer — no per-batch
  id/row/mask re-uploads (the payload is the only host→device traffic)
  — which rides the scoring program's call as its host argument: ONE
  trip into the runtime a batch, no ``jax.device_put`` on its path
  (with a mesh two: the one placement, then the call).
  Responses come back packed ``[B, 2k]`` (scores' f32 bits | indices) in
  one bulk transfer, and tickets complete with numpy VIEWS sliced from
  that buffer — zero per-ticket copies; the buffer snapshots an
  immutable device array, so the views stay valid indefinitely.
  :meth:`ServingEngine.warmup` additionally PINS the steady-state
  scoring executables ahead of time (one ``stages.Compiled`` per
  bucket, with a mesh or without: loaded from the compile cache's
  directory where it lies there, lowered and compiled where not —
  ``serving.pins``), taking jit-cache dispatch off the hot path;
  a shape-changing publish invalidates a pin and falls back to the
  ordinary jit call until the next warmup.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import queue
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax._src.interpreters.pxla import batched_device_put
from jax.profiler import TraceAnnotation

from tpu_als import obs
from tpu_als.core.foldin import padded_rows, place_rows, put
from tpu_als.core.ratings import (
    LIVE_PADS,
    growth_pads,
    growth_room,
    pad_for,
    pads_up_to,
    row_capacity,
    rung_for,
)
from tpu_als.obs import compiles, tracing
from tpu_als.obs.phases import count_placed, phase, placed_bytes
from tpu_als.obs.schema import (
    LIVE_HISTORY_SCOPE,
    SERVE_BATCH_SPAN_KEYS,
    SERVE_EXCLUDE_SCOPE,
    SERVE_MESH_HISTORY_SCOPE,
    SERVE_MESH_SCOPES,
)
from tpu_als.obs.trace import FlightRecorder
from tpu_als.ops.topk import (
    NOT_AN_ID,
    chunked_topk_scores,
    exclusion_plan,
)
from tpu_als.parallel.mesh import AXIS, shard_map
from tpu_als.resilience import faults
from tpu_als.serving import pins
from tpu_als.serving.batcher import (
    DEFAULT_BUCKETS,
    DeadlineExceeded,
    MicroBatcher,
    Overloaded,
    bucket_for,
)
from tpu_als.serving.index import (
    SEGMENT_SENT,
    Int8CandidateIndex,
    ShardedInt8Index,
    _build_sharded_int8,
    _next_pow2,
    _shard_merge,
    mask_block,
    mesh_exchange_bytes,
    mesh_history_bytes,
    mesh_spread_bytes,
    place_catalog,
    segment_write_bytes,
    shard_lists,
    shortlist_rescore,
)


# batches handed to the device and not yet completed, at most: one being
# read back and one dispatched behind it.  The engine thread waits for
# the older to complete before it dequeues a third
MAX_IN_FLIGHT = 2


# A publish's ONE host array (:class:`_Ride`), ``int32[PUBLISH_SENT,
# pad]``: its first row the user-table rows, from ``SENT_SEGMENT`` on what
# the segment's write takes (``serving.index._write_segment``), its last
# ``SENT_PLAN`` rows :func:`_append_runs`' plan.  Always all of them — a
# publish without a part leaves its rows unread — so that a write program
# meets few shapes
SENT_SEGMENT, SENT_PLAN = 1, 5
PUBLISH_SENT = SENT_SEGMENT + SEGMENT_SENT + SENT_PLAN


# catalog ids a request may bring of its own (``submit(exclude=...)``):
# the columns they ride in, after the ``rank + 2`` of the staging layout
MAX_EXCLUDE = 64


# ids a part of the histories' check holds (``_checked_seen``): 8 MB of
# int32, so that a part and the comparisons made of it stay in a core's
# cache between the passes over it
CHECK_PART = 1 << 21


class _Seen:
    """The users' histories of one generation: catalog ids on the device,
    each user's a contiguous run of ``indices``, and on the host what
    staging needs of them: each user's count (``lengths``) and the ladder
    of history pads the scoring programs are compiled for (``pads``: 64,
    512, ... up to the longest history) — a batch rides the least that
    holds its longest.

    ``runs`` says where the runs lie, in one of two layouts.  AS
    PUBLISHED: the CSR's ``indptr int32[n_users + 1]``, run after run
    with no room between them (a row's ids ascending, none twice):
    nothing can be appended.  GROWN (:meth:`ServingEngine._lay_out`, which
    ``warmup_live`` calls): ``(start, count)``, ``int32`` of the user
    table's row capacity each, every run with room behind it for an
    eighth more ids (``core.ratings.growth_room``) and free room behind
    the last run, and ``room`` the host's account of it
    (:class:`_Room`).  A publish then writes the ids its ratings add
    behind their users' runs IN PLACE (:func:`_append_runs`; appended
    ids stand in arrival order) and a run that is full moves to the free
    room first (:func:`_move_run`); ``lengths`` is shared by the
    generations of one layout and written under ``_table_lock``.

    ON A MESH the table is sharded WITH the user table, as published
    (:meth:`ServingEngine._place_seen`): shard ``s`` holds the runs and
    the ids of table rows ``[s * n_loc, (s + 1) * n_loc)`` — ``runs``
    ``int32[S * (n_loc + 1)]``, every shard's own ``indptr`` from 0
    (spare rows: empty runs), ``indices`` every shard's ids padded to
    one common length plus the longest pad of spare ids, both sharded by
    rows — so a history is held ONCE on the mesh, by the shard that
    holds its user's row (:func:`_mesh_history`), and ``lengths`` has one
    entry a table row.  Nothing grows there yet."""

    __slots__ = ("runs", "indices", "lengths", "pads", "room")

    def __init__(self, runs, indices, lengths, pads, room=None):
        self.runs, self.indices = runs, indices
        self.lengths, self.pads, self.room = lengths, pads, room

    def lengths_of(self, live):
        """How many ids each ticket of a batch loses to its user's
        history (a request by vector none), or ``None`` for a table that
        holds no history."""
        if self.lengths is None:
            return None
        return [int(self.lengths[t.payload])
                if isinstance(t.payload, (int, np.integer)) else 0
                for t in live]

    def pad_for(self, lengths):
        """The history pad of a batch whose tickets' histories are
        ``lengths`` long (:meth:`lengths_of`): its longest, up the
        ladder."""
        longest = max(lengths or (0,))
        return next(p for p in self.pads if p >= longest)


class _Append(NamedTuple):
    """What one publish adds to a grown table of histories
    (:meth:`ServingEngine._plan_append`): :func:`_append_runs`' ``plan``
    (on the host as planned; on the device — by itself or as the last
    rows of the publish's one array, :class:`_Ride` — when it is
    written), the full runs to move first as ``(old, new, width)``, the
    touched users with their runs' starts, room and lengths afterwards,
    where the free room then begins, the bytes sent."""

    args: object
    moves: list
    users: np.ndarray
    start: np.ndarray
    cap: np.ndarray
    lengths: np.ndarray
    free: int
    sent: int


class _Ride:
    """What of one ``publish_update`` rides its ONE host array
    (``PUBLISH_SENT``), where the caller holds the fold's rows on the
    device and only integers are left to send: the user-table ``rows``
    (in the order of the device's rows, padded as they are), the
    segment's ``SegmentUpdate.sent`` and the history's ``plan``, each
    ``None`` where the publish has no such part — and ``sent``, the
    array on the device once :meth:`ServingEngine._send` has placed it:
    one placement a publish, made before ``_table_lock`` is taken."""

    rows = segment = plan = sent = None

    @property
    def wanted(self):
        """Whether a part that can only ride waits to be sent (the
        plan alone goes up by itself, as it did)."""
        return self.sent is None and (self.rows is not None
                                      or self.segment is not None)


class _Room:
    """The host's account of a grown table of histories (:class:`_Seen`):
    where each user's run starts and how many ids it has room for
    (``start``, ``cap``: one entry a row of the user table, spare rows
    included — a user appended to the table has an empty history until
    a publish gives it a run), where the free room begins (``free``) and
    where it ends (``size``; behind it the table has its longest pad of
    spare ids, so that no slice of a run is clamped)."""

    __slots__ = ("start", "cap", "free", "size")

    def __init__(self, start, cap, free, size):
        self.start, self.cap, self.free, self.size = start, cap, free, size


def history_pads(longest, grows=False):
    """The ladder of history pads for histories of up to ``longest``
    ids: 64, 512, 4096, ... (``core.ratings.pads_up_to`` from 64);
    ``grows``: with the rung above them that histories growing from
    there need (``core.ratings.growth_pads``: 8,192 above 4,096)."""
    longest = max(int(longest), MAX_EXCLUDE)
    return tuple(p for p in (growth_pads if grows else pads_up_to)(longest)
                 if p >= MAX_EXCLUDE)


def _part_rises(indptr, indices, n_items, lo, hi):
    """Whether ids ``[lo, hi)`` of a CSR (``indptr`` non-decreasing) are
    catalog ids and each exceeds the id before it in ``indices`` — ``lo -
    1``'s too: one id of overlap carries the comparison across parts —
    unless it is a row's first.  Compared in the ids' own integer type;
    the part's row starts are a slice of ``indptr``."""
    first = max(lo - 1, 0)
    part = indices[first:hi]
    if int(part.min()) < 0 or int(part.max()) >= n_items:
        return False
    rises = part[1:] > part[:-1]
    a, b = np.searchsorted(indptr, (first + 1, hi))
    rises[indptr[a:b] - (first + 1)] = True
    return bool(rises.all())


def cpu_mark():
    """``(thread CPU ns, wall ns)`` read together as a stamped span opens,
    or ``None`` while no profiler session records: the CPU clock is a
    system call, which the chip's sandboxed host answers in 7 us alone and
    in 30 us beside busy threads (``perf_counter`` in 0.15 us: PERF.md
    section 6, PR 36), so the stamps are taken only where a trace will
    hold them.  The wall reading comes second here and first in
    :func:`stamp_cpu`: the two slow calls lie outside the interval."""
    if not TraceAnnotation.is_enabled():
        return None
    return time.thread_time_ns(), time.perf_counter_ns()


def stamp_cpu(span, mark):
    """Close the CPU account that :func:`cpu_mark` opened on ``span``, a
    ``TraceAnnotation`` about to close: ``cpu_us``, the calling thread's
    own CPU time since the mark, beside ``wall_us``, the wall time of the
    same interval (the span's own duration less the two clock calls).
    Returns the CPU seconds, ``None`` for no mark.  Wall less CPU is time
    the thread held no processor: waiting for the interpreter, or blocked
    in a transfer (a phase that never blocks and reads 20 ms of wall on
    2 ms of CPU stood still with the machine)."""
    if mark is None:
        return None
    wall_ns = time.perf_counter_ns() - mark[1]
    cpu_ns = time.thread_time_ns() - mark[0]
    span.set_metadata(cpu_us=cpu_ns // 1000, wall_us=wall_ns // 1000)
    return 1e-9 * cpu_ns


class Stamped(TraceAnnotation):
    """A ``TraceAnnotation`` that carries its own CPU account
    (:func:`cpu_mark`, :func:`stamp_cpu`): one phase of the live write
    path (``obs.schema.LIVE_PHASE_SPAN_KEYS``), opened a BATCH of events
    and never an event — a microsecond with no profiler, and two
    readings of each clock while one records.  A phase that raises
    closes unstamped."""

    def __enter__(self):
        super().__enter__()
        self._mark = cpu_mark()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            stamp_cpu(self, self._mark)
        return super().__exit__(exc_type, exc, tb)


class NoModelPublished(RuntimeError):
    """A request arrived before the first :meth:`ServingEngine.publish`."""


@dataclasses.dataclass(slots=True)
class _Flown:
    """One dispatched batch on its way from :meth:`ServingEngine._begin`
    to :meth:`ServingEngine._finish`: the tickets still alive, the packed
    response on the device, and every stamp and count the first half
    took, so that the second reads nothing of the engine's state (the
    engine thread is a batch further by then)."""

    seq: int
    live: list
    resp_dev: object
    bucket: int
    rows: int
    path: str
    fell_back: bool
    t_stage: float
    t_locked: float
    t_dispatch: float
    last_wait: tuple        # the batcher's (idle_s, waiting, coalesce_s)
    closed_by: str
    head_wait: float
    in_flight: int
    handoff_wait: float
    upload_how: str         # ``call`` | ``put``, as the upload span's how
    t_launch: float         # the upload span had closed
    t_launched: float       # the scoring call had returned
    cpu_stage: float | None     # the engine thread's CPU seconds in it
    cpu_dispatch: float | None  # (None: no profiler was recording)
    t_flown: float          # the engine thread is done with the batch


class _Published:
    """One model generation; the engine swaps whole instances and never
    assigns to one.

    ``U`` holds ``n_users`` live rows and spare zero rows after them,
    which no request addresses (``submit`` checks ids against
    ``n_users``).  It is the one array that does not outlive its
    generation: the next row-write publish donates it to
    :func:`_scatter_users`, after which ``U`` of this instance is a
    deleted array (its ``shape`` still reads; its values raise).  So
    ``U`` goes to the device only under ``ServingEngine._table_lock``,
    read from the LIVE generation.  ``seq``, ``n_users``, ``rank``,
    ``V``/``valid`` and ``index`` stay readable for as long as the
    instance is held.  ``V``/``valid`` hold the ``n_items`` rows of the
    catalog on the device; on a mesh engine they and ``U`` are sharded
    by rows over the mesh and padded to whole shards (see the module
    docstring), and a fresh index shares ``V``/``valid`` with the engine.
    ``seen``: the users' histories published with this generation
    (:class:`_Seen`), or None.
    """

    __slots__ = ("seq", "U", "V", "valid", "index", "n_users", "rank",
                 "n_items", "seen")

    def __init__(self, seq, U, n_users, V, valid, index, n_items,
                 seen=None):
        self.seq = seq
        self.U = U
        self.V = V
        self.valid = valid
        self.index = index
        self.seen = seen
        self.n_users = int(n_users)
        self.n_items = int(n_items)
        self.rank = int(U.shape[1])


@jax.jit
def _select_packed(U, packed):
    """Per-slot query vectors — the published row for id-requests, the
    carried fold-in vector for row-requests — from the single-upload
    staging layout, an INT32 array: ``packed[:, :rank]`` the fold-in
    rows' f32 bits, ``packed[:, rank]`` user ids, ``packed[:, rank+1]``
    the row-mask —
    one host→device transfer carries all three.  Floats ride as integer
    bits, never ids as float bits: a small int viewed as f32 is a
    subnormal, and the TPU flushes subnormals to zero on any float op —
    every id would arrive as 0."""
    rank = U.shape[1]
    rows = jax.lax.bitcast_convert_type(packed[:, :rank], jnp.float32)
    ids = jnp.clip(packed[:, rank], 0, U.shape[0] - 1)
    rowmask = packed[:, rank + 1] != 0
    return jnp.where(rowmask[:, None], rows, jnp.take(U, ids, axis=0))


@jax.jit
def _pack_response(s, ix):
    """Pack ``(scores, indices)`` as ``[B, 2k]`` int32 (the scores' f32
    bits, see :func:`_select_packed` for why not the other way round) so
    the response comes back in ONE bulk device→host transfer;
    ``serve_batch`` slices numpy views back out per ticket."""
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(s.astype(jnp.float32), jnp.int32),
         ix.astype(jnp.int32)], axis=1)


def _select_seen(runs, indices, packed, rank, pad):
    """``(int32[B, pad], int32[B, MAX_EXCLUDE])``: what each slot of a
    staged batch is not to be answered with — the first ``pad`` ids of
    the published history of a by-id request's user (a batch rides a pad
    that holds its longest; a request by vector has none), and the
    request's own list as it rode the staging layout's last
    ``MAX_EXCLUDE`` columns; each padded with ``NOT_AN_ID``: the two
    lists ``ops.topk.excluded_mask`` takes.  ``runs``: where the
    histories lie in ``indices`` (:class:`_Seen`) — the CSR's ``indptr``
    as published, or ``(start, count)`` of histories that grow: what
    differs is where a run's first id and its count are read.
    Selected on the device, as :func:`_select_packed` selects the user
    rows: the histories never cross host→device after their publish."""
    with jax.named_scope(SERVE_EXCLUDE_SCOPE):
        grown = isinstance(runs, tuple)
        users = runs[0].shape[0] if grown else runs.shape[0] - 1
        ids = jnp.clip(packed[:, rank], 0, users - 1)
        first = jnp.take(runs[0] if grown else runs, ids)
        count = jnp.where(
            packed[:, rank + 1] != 0, 0,
            jnp.take(runs[1], ids) if grown
            else jnp.take(runs, ids + 1) - first)
        # a history is a contiguous run of the table: one slice of ``pad``
        # ids a row (the table ends in ``pad`` spare ids, so none is
        # clamped), not ``pad`` scalar gathers (0.23 ms a batch of 8 rows
        # of 4,096 on the v5e, and the whole 71 MB table fetched before
        # them: PERF.md section 6, PR 39)
        rows = jax.vmap(lambda at: jax.lax.dynamic_slice(
            indices, (at,), (pad,)))(first)
        j = jnp.arange(pad, dtype=jnp.int32)[None, :]
        history = jnp.where(j < count[:, None], rows, NOT_AN_ID)
        return history, packed[:, rank + 2:]


@functools.partial(jax.jit, static_argnames=("k", "shortlist_k", "pad"))
def _serve_int8_packed(U, Vq, sv, V, valid, delta, histories, packed, *, k,
                       shortlist_k, pad=None):
    """Whole int8 request path — select → shortlist and rescore
    (``serving.index.shortlist_rescore``) → pack — as one executable, so
    that :meth:`ServingEngine.warmup` can AOT-pin it: one program a
    bucket and shape of its arguments.  ``delta``: ``()``, or the
    index's delta segment and last catalog id ``(drows, dVq, dsv, dV,
    dvalid, last_id)`` — the one program a bucket an engine whose
    catalog moves runs, whatever the segment holds: its slots are fixed
    (:meth:`ServingEngine.warmup_live` pins it).  ``histories``: ``()``,
    or for a batch that excludes ``(runs, indices)`` of the published
    table, in either layout: the staging layout is then ``MAX_EXCLUDE``
    columns wider (the requests' own lists), the users' histories are
    taken from the table at history pad ``pad`` (:func:`_select_seen`),
    and the scoring takes both out (``ops.topk.excluded_mask``'s rule):
    one program a bucket and history pad."""
    Ub = _select_packed(U, packed)
    seen = (_select_seen(*histories, packed, U.shape[1], pad)
            if histories else None)
    s, ix = shortlist_rescore(
        Ub, Vq, sv, V, valid, k=k, shortlist_k=shortlist_k,
        delta=delta[:5], last_id=delta[5] if delta else None, seen=seen)
    return _pack_response(s, ix)


@functools.partial(jax.jit, static_argnames=("k", "item_chunk", "pad"))
def _serve_exact_packed(U, V, valid, histories, packed, *, k, item_chunk,
                        pad=None):
    """Whole exact request path — select → chunked top-k → pack — the
    fallback's one executable a bucket; ``histories`` and ``pad`` as
    :func:`_serve_int8_packed` takes them."""
    Ub = _select_packed(U, packed)
    seen = (_select_seen(*histories, packed, U.shape[1], pad)
            if histories else None)
    s, ix = chunked_topk_scores(Ub, V, valid, k, item_chunk=item_chunk,
                                seen=seen)
    return _pack_response(s, ix)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _append_runs(start, count, indices, plan):
    """The ids a publish adds to its users' histories, written IN PLACE
    into a grown table (:class:`_Seen`; all three arrays are donated, as
    the user table is to :func:`_scatter_users`, and in the same order
    on the device).  ``plan``, ``int32[5, pad]``, ONE upload (as five
    arrays it cost the publish 3.1 ms on the chip's host, as one 1.0:
    PERF.md section 5, PR 42): the touched
    ``users`` with their ``starts`` and ``counts``, and the ``ids`` with
    the positions ``at`` they go to — behind their users' runs, in room
    no count reaches yet.  Everything is padded up ``pad_for``'s ladder
    with entries outside the arrays (``mode='drop'``): few programs, and
    a publish sends O(ids appended).  The plan is the LAST five rows of
    what it is given: a publish that sends more in the same array
    (:class:`_Ride`) hands over all of it."""
    users, starts, counts, at, ids = plan[-SENT_PLAN:]
    with jax.named_scope(LIVE_HISTORY_SCOPE):
        return (start.at[users].set(starts, mode="drop"),
                count.at[users].set(counts, mode="drop"),
                indices.at[at].set(ids, mode="drop"))


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("width",))
def _move_run(indices, old, new, *, width):
    """A run that is full moved to the free room of a grown table, IN
    PLACE: the ``width`` ids from ``old`` (a history pad that holds the
    run; what it copies beyond the run lands in room nothing counts yet)
    written from ``new`` on.  One program a pad, O(run) on the device,
    two scalars from the host."""
    with jax.named_scope(LIVE_HISTORY_SCOPE):
        return jax.lax.dynamic_update_slice(
            indices, jax.lax.dynamic_slice(indices, (old,), (width,)),
            (new,))


@functools.partial(jax.jit, static_argnames=("size",))
def _spread_runs(indices, src, dst, *, size):
    """A table of ``size`` ids (``NOT_AN_ID`` where no run lies) with
    ``indices[src]`` at ``dst``: the histories laid out anew, each run
    with room behind it (:meth:`ServingEngine._lay_out`)."""
    return jnp.full((size,), NOT_AN_ID, jnp.int32).at[dst].set(
        jnp.take(indices, src), unique_indices=True,
        indices_are_sorted=True)       # the runs lie in the users' order


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_users(U, rows, vals):
    """``vals`` written at ``rows`` of the user table, IN PLACE: ``U``
    is donated, the result is the same buffer and the caller's ``U`` is
    deleted.  The device runs programs in dispatch order, so a batch
    dispatched before this call reads the old rows whole, one dispatched
    after it the new rows whole; the caller keeps the deleted handle
    away from the engine thread (``ServingEngine._table_lock``).
    ``rows`` are padded up ``pad_for``'s ladder with an out-of-range
    sentinel (``mode='drop'``), so the programs are few and only the
    touched payload crosses host→device.  ``rows`` may be a publish's
    whole host array (:class:`_Ride`, whose FIRST row they are) where
    ``vals`` lay on the device already: its first ``len(vals)`` entries
    are read."""
    with jax.named_scope("live.publish.scatter"):
        return U.at[rows.reshape(-1)[:vals.shape[0]]].set(vals, mode="drop")


@jax.jit
def _copy_table(table):
    """A second buffer with ``table``'s values, made on the device: how a
    landing's generation takes a table that already lies there (the
    fold-in server's, which goes on being written) without a second trip
    from the host.  Nothing is donated."""
    with jax.named_scope("live.landing.copy"):
        return jnp.array(table, copy=True)


def _ride_shapes(max_rows, mesh=None):
    """``(pad of the publish's one array, pad of a side's rows on the
    device)`` for every shape a write program meets in publishes of up to
    ``max_rows`` rows a side (:class:`_Ride`): the array is as wide as
    its widest part, and the history's plan may carry one id more than
    the batch has rows (those that waited for a row).  None on a mesh,
    where every side goes up from the host."""
    return [] if mesh is not None else [
        (pad, rows) for pad in pads_up_to(max_rows + 1)
        for rows in pads_up_to(max_rows) if rows <= pad]


def _same_rows(placed, rows, rank):
    """Whether ``placed`` — a caller's ``(rows, their values on the
    device)``, or ``None`` — holds exactly the table rows ``rows``
    (ascending, none twice), padded up ``pad_for``'s ladder as the
    programs that were run ahead expect."""
    if placed is None:
        return False
    mine, vals = placed
    return (vals.shape == (pad_for(len(rows)), rank)
            and len(mine) == len(rows)
            and np.array_equal(np.sort(mine), rows))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_items(V, valid, rows, vals, ok):
    """:func:`_scatter_users` for the engine's own catalog (what the
    exact fallback scores): ``vals`` and their ``ok`` bits written at
    ``rows`` of ``V`` and ``valid``, IN PLACE, both donated; appended
    items fall on spare rows.  Same order on the device, same lock on
    the host."""
    with jax.named_scope("live.publish.scatter_items"):
        return (V.at[rows].set(vals, mode="drop"),
                valid.at[rows].set(ok, mode="drop"))


def _mesh_spread(block, *, axis):
    """The staged batch on every shard, from the one shard that was
    given it: ``block`` is this shard's ``[B, width]`` rows of the
    ``[S * B, width]`` array :meth:`ServingEngine._place_one` assembled
    — the batch on the mesh's first device, zeros on the others — and
    the blocks' sum is the batch, bit for bit (``int32`` plus zeros):
    one all-reduce over ICI where the host made one placement a shard."""
    return jax.lax.psum(block, axis)


def _mesh_lookup(U, packed, *, me, axis):
    """:func:`_select_packed` against a user table sharded by rows,
    inside ``shard_map``: this shard holds table rows ``[me * n_loc,
    (me + 1) * n_loc)``, takes the rows it owns for the batch's ids
    (zeros for the others) and the shards' contributions are summed —
    one row and ``S - 1`` zeros a slot, so the sum is the row — after
    which every shard holds the ``[B, rank]`` queries.  A request by
    vector rides in ``packed`` as it does without a mesh."""
    n_loc, rank = U.shape
    rows = jax.lax.bitcast_convert_type(packed[:, :rank], jnp.float32)
    loc = packed[:, rank] - me * n_loc
    owned = (loc >= 0) & (loc < n_loc)
    mine = jnp.where(owned[:, None],
                     jnp.take(U, jnp.clip(loc, 0, n_loc - 1), axis=0), 0.0)
    rowmask = packed[:, rank + 1] != 0
    return jnp.where(rowmask[:, None], rows, jax.lax.psum(mine, axis))


def _mesh_history(runs, indices, packed, *, me, axis, rank, pad):
    """:func:`_select_seen` against histories sharded WITH the user
    table, inside ``shard_map``: this shard holds the runs and the ids of
    table rows ``[me * n_loc, (me + 1) * n_loc)`` (``runs``: its own
    ``indptr``, ``int32[n_loc + 1]`` from 0; :meth:`ServingEngine.
    _place_seen`), takes the first ``pad`` ids of the histories it owns
    for the batch's ids — a slot another shard owns is selected as a
    request by vector is, no id — and the shards' lists are summed: one
    list and ``S - 1`` empty ones a slot.  What is summed is ``id + 1``
    with 0 for no id (``NOT_AN_ID`` is the largest ``int32``: summed as
    it is it would overflow), so the sum is bit-exact, as
    :func:`_mesh_spread`'s is, and every shard ends with the same
    ``int32[B, pad]`` of LOGICAL ids padded with ``NOT_AN_ID``, beside
    the requests' own lists, which rode the staged batch."""
    n_loc = runs.shape[0] - 1
    loc = packed[:, rank] - me * n_loc
    owned = (loc >= 0) & (loc < n_loc)
    local = packed.at[:, rank].set(loc).at[:, rank + 1].set(
        jnp.where(owned, packed[:, rank + 1], 1))
    history, own = _select_seen(runs, indices, local, rank, pad)
    total = jax.lax.psum(
        jnp.where(history == NOT_AN_ID, 0, history + 1), axis)
    return jnp.where(total == 0, NOT_AN_ID, total - 1), own


def _mesh_queries(U, packed, *histories, me, axis, pad=None):
    """What stands before the scoring in both of a mesh engine's
    programs: the staged batch spread from the one shard that was given
    it, then the by-id lookup — every shard ends with the ``[B, rank]``
    queries — in the scope ``SERVE_MESH_SCOPES[0]``; and for a batch that
    excludes (``histories``: the sharded ``(runs, indices)``, ``pad``
    its history pad) the lists of ids its slots are not to be answered
    with (:func:`_mesh_history`), in ``SERVE_MESH_HISTORY_SCOPE`` beside
    it.  Returns ``(queries, lists or None)``."""
    with jax.named_scope(SERVE_MESH_SCOPES[0]):
        packed = _mesh_spread(packed, axis=axis)
        Ub = _mesh_lookup(U, packed, me=me, axis=axis)
    if not histories:
        return Ub, None
    with jax.named_scope(SERVE_MESH_HISTORY_SCOPE):
        return Ub, _mesh_history(*histories, packed, me=me, axis=axis,
                                 rank=U.shape[1], pad=pad)


@functools.lru_cache(maxsize=32)
def _build_mesh_exact(mesh, k, k_loc, ni_loc, item_chunk, pad=None):
    """A mesh engine's exact fallback, per shard: the lookup, the exact
    chunked scan of this shard's slice of the engine's own sharded
    catalog, the same merge.  Nothing of the catalog moves.  ``pad``: a
    batch that excludes — the sharded histories follow the staged batch,
    and the scan takes each shard's own columns out
    (``serving.index.shard_lists``), as the int8 program does."""
    P = jax.sharding.PartitionSpec
    head = 2 if pad is None else 4

    def serve_mesh_exact(*args):
        V, valid, last_id = args[head:]
        me = jax.lax.axis_index(AXIS)
        Ub, seen = _mesh_queries(*args[:head], me=me, axis=AXIS, pad=pad)
        with jax.named_scope(SERVE_MESH_SCOPES[1]):
            s, ix = chunked_topk_scores(
                Ub, V, valid, k_loc, item_chunk=item_chunk,
                seen=(None if pad is None
                      else shard_lists(seen, me * ni_loc, ni_loc)))
        with jax.named_scope(SERVE_MESH_SCOPES[2]):
            return _pack_response(*_shard_merge(
                s, ix.astype(jnp.int32) + me * ni_loc, last_id,
                axis=AXIS, k=k))

    return pins.built(jax.jit(shard_map(
        serve_mesh_exact, mesh=mesh,
        in_specs=(P(AXIS),) * (head + 2) + (P(),),
        out_specs=P(), check_vma=False)),
        _build_mesh_exact, mesh, k, k_loc, ni_loc, item_chunk, pad)


@functools.lru_cache(maxsize=8)
def _build_mesh_scatter(mesh):
    """:func:`_scatter_users` for a table sharded by rows: every shard
    is given the same ``(rows, vals)`` and writes the rows it owns into
    its own part, IN PLACE (the table is donated; the others fall on the
    out-of-range sentinel and are dropped).  No collective, no copy."""
    P = jax.sharding.PartitionSpec

    def scatter_users_mesh(U, rows, vals):
        n_loc = U.shape[0]
        loc = rows - jax.lax.axis_index(AXIS) * n_loc
        owned = (loc >= 0) & (loc < n_loc)
        with jax.named_scope("live.publish.scatter"):
            return U.at[jnp.where(owned, loc, n_loc)].set(vals, mode="drop")

    return jax.jit(shard_map(scatter_users_mesh, mesh=mesh,
                             in_specs=(P(AXIS), P(), P()),
                             out_specs=P(AXIS), check_vma=False),
                   donate_argnums=(0,))


@contextlib.contextmanager
def _lap(took, key):
    """The block's wall seconds added to ``took[key]``."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        took[key] = took.get(key, 0.0) + time.perf_counter() - t0


def _arrays_of(m):
    """Every device array generation ``m`` (:class:`_Published`) holds."""
    idx, seen = m.index, m.seen
    return [a for a in (
        m.U, m.V, m.valid,
        *((idx.V, idx.Vq, idx.sv, idx.valid, *(idx._seg or ()))
          if idx is not None else ()),
        *((*(seen.runs if isinstance(seen.runs, tuple) else (seen.runs,)),
           seen.indices) if seen is not None else ()))
        if isinstance(a, jax.Array)]


def _release(old, new):
    """Delete every array of generation ``old`` that ``new`` does not
    share, now and not when the last reference goes: what a landing
    leaves of the generation before it is nothing.  The device runs
    programs in dispatch order and keeps a deleted buffer until those
    dispatched against it have run, so a batch in flight reads it
    whole; no later batch can name it (the swap was made under
    ``ServingEngine._table_lock``)."""
    kept = {id(a) for a in _arrays_of(new)}
    for a in _arrays_of(old):
        if id(a) not in kept and not a.is_deleted():
            a.delete()


class ServingEngine:
    """Request-path serving over published ALS factors.

    ``k`` is the engine-wide top-k width (one compiled program per
    bucket); per-request ``k`` may be smaller and is trimmed at
    completion.  ``buckets`` are the padded batch shapes; keep the set
    small — each is one executable per (path, catalog shape).

    ``slo_s``: end-to-end latency objective; a completed request slower
    than this triggers a flight-recorder dump (``flight_record`` events
    carrying the last ``flight_capacity`` per-request traces).  None
    disables the breach trigger; shed and degraded dumps stay on.

    ``tenant``: multi-tenant attribution — when set, every serving.*
    metric this engine (and its batcher) writes carries a
    ``tenant=<name>`` label, and its ``serving_publish`` events and
    flight-recorder dumps carry a ``tenant`` field, so a breach in a
    shared process is attributable from the obs trail alone
    (tpu_als.tenancy; docs/tenancy.md).
    """

    def __init__(self, k=10, buckets=None, shortlist_k=64,
                 max_queue=1024, max_wait_s=0.002,
                 default_deadline_s=None, item_chunk=8192,
                 slo_s=None, flight_capacity=64, tenant=None,
                 mesh=None):
        if buckets is None:
            # bucket plan from the execution planner: a banked ladder
            # for this device/jax key wins, else DEFAULT_BUCKETS — and
            # with the planner off this IS DEFAULT_BUCKETS, unchanged
            from tpu_als import plan as _plan

            buckets = _plan.resolve_serving_buckets()
        self.k = int(k)
        self.shortlist_k = int(shortlist_k)
        self.item_chunk = int(item_chunk)
        self.slo_s = float(slo_s) if slo_s is not None else None
        self.tenant = str(tenant) if tenant is not None else None
        self._labels = {"tenant": self.tenant} if self.tenant else {}
        # tenant stamped structurally: every record this ring takes
        # carries it, so no record site can strand a dump unattributed
        self.flight = FlightRecorder(flight_capacity,
                                     labels=self._labels)
        # one record per BATCH beside the per-request ones: the engine
        # thread's cycle (the durations of its profiler spans, under
        # their names) for whoever runs no profiler; a request record's
        # ``batch`` names the batch record it rode
        self.batch_flight = FlightRecorder(
            flight_capacity, span_keys=SERVE_BATCH_SPAN_KEYS,
            labels=self._labels)
        self._batch_seq = 0
        self.batcher = MicroBatcher(
            buckets=buckets, max_queue=max_queue, max_wait_s=max_wait_s,
            default_deadline_s=default_deadline_s, labels=self._labels)
        self._model = None              # _Published; swapped atomically
        self._publish_lock = threading.Lock()
        # orders the host's two uses of the live user table: scoring
        # against it (held from reading self._model to the scoring
        # call's return) and donating it to a row write (held around
        # the donating call and the swap).  Taken after _publish_lock,
        # never before it
        self._table_lock = threading.Lock()
        self._cadence = None            # plan-resolved, on first use
        self._seq = 0
        self._thread = None             # the engine thread (_run)
        self._completer = None          # the completion thread
        self._stopping = threading.Event()
        # what the engine thread hands the completion thread, in
        # dispatch order; a slot is taken before a batch is dequeued and
        # given back as it completes, so at most MAX_IN_FLIGHT wait here
        # or are being read back.  ``_handed`` is written by the engine
        # thread alone, ``_completed`` by the completion thread alone:
        # their difference is the number in flight
        self._handoff = queue.SimpleQueue()
        self._slots = threading.BoundedSemaphore(MAX_IN_FLIGHT)
        self._handed = 0
        self._completed = 0
        self.mesh = mesh
        # what every shard is given whole: a publish's touched rows
        self._replicated = (None if mesh is None else
                            jax.sharding.NamedSharding(
                                mesh, jax.sharding.PartitionSpec()))
        # what the staged batch is placed as (_place_one): a block of
        # rows a shard
        self._by_rows = (None if mesh is None else
                         jax.sharding.NamedSharding(
                             mesh, jax.sharding.PartitionSpec(
                                 mesh.axis_names[0])))
        self._devices = None if mesh is None else list(mesh.devices.flat)
        # _place_one's: staged shape -> (the placed array's abstract
        # value, the blocks of zeros on every device but the first)
        self._idle_blocks = {}
        # (bucket, path) -> AOT executable; (bucket, path, history pad)
        # for the programs that exclude
        self._pinned = {}
        self._no_history = None         # _without_history's memo
        # of the last publish on a STARTED engine (a landing): its seq,
        # sizes, seconds by step and bytes placed / copied on the device
        self.last_landing = None
        compiles.install()
        self._last_id = None            # _last_item's: (n_items, handle)
        self._plans = {}                # _mesh_plan's memo

    def _place_catalog(self, Vh, validh, placed=None):
        """The host's catalog on the device, with zero rows up to
        ``len(validh)`` (the valid bits of the whole TABLE: false on the
        spare rows a live generation's catalog has): a chunk at a time
        into a zero table (``core.foldin.place_rows``, as the user table
        goes up: no 1.5 GB transfer in one piece ahead of a started
        engine's 8 KB request batches, and the table never twice on the
        device), or sharded by rows over the mesh
        (``serving.index.place_catalog``: a chunk at a time into each
        shard, never whole on one device).  ``placed``: the same table
        where the caller holds it on the device already, at that size —
        copied there (:func:`_copy_table`).  On a STARTED engine the
        table lies beside the live generation's until the swap."""
        if self.mesh is not None:
            return place_catalog(Vh, validh, self.mesh,
                                 max(self.shortlist_k, self.k))[:2]
        count_placed("catalog", validh.nbytes)
        if placed is not None and placed.shape == (len(validh),
                                                   Vh.shape[1]):
            return _copy_table(placed), jnp.asarray(validh)
        return (place_rows(Vh, capacity=len(validh), table="catalog"),
                jnp.asarray(validh))

    def _build_index(self, V, valid, n_items, sk, seq):
        """The candidate index of one generation over the catalog as
        :meth:`_place_catalog` placed it, sharded over the mesh when the
        engine has one: the one place that chooses."""
        if self.mesh is None:
            with phase("start.publish.index.place"):
                # the index's own copy (a host catalog; a device array
                # passes through), waited for: a placement returns with
                # the transfer in flight, and its seconds would be the
                # next program's
                if isinstance(V, np.ndarray):
                    count_placed("index", V.nbytes)
                V = jnp.asarray(V, dtype=jnp.float32).block_until_ready()
        with phase("start.publish.index.quantize"):
            if self.mesh is None:
                return Int8CandidateIndex(V, valid, shortlist_k=sk, seq=seq)
            return ShardedInt8Index(V, self.mesh, item_valid=valid,
                                    shortlist_k=sk, seq=seq,
                                    n_items=n_items)

    def _announce_mesh(self):
        """One ``serving_backend`` event per mesh engine, at its first
        publish; a mesh-less engine emits nothing."""
        if self.mesh is not None and self._seq == 0:
            obs.emit("serving_backend", backend="sharded",
                     n_shards=int(self.mesh.devices.size), **self._labels)

    # -- model lifecycle ----------------------------------------------
    def _place_users(self, prev, U, placed=None):
        """``(U on the device with spare rows, live rows, bytes sent)``:
        the whole table uploaded into a new one, a chunk at a time
        (``core.foldin.place_rows``: never twice on the device; with a
        mesh sharded by rows, shard ``s`` holding rows ``[s * n_loc, (s
        + 1) * n_loc)``, so the spare rows, which follow the live ones,
        lie on the last shards).  The capacity is the live generation's
        while the table fits it (same shapes, same programs),
        ``row_capacity`` of the table otherwise.  ``placed``: the same
        table where the caller holds it on the device already, at that
        capacity — copied there (:func:`_copy_table`), nothing sent."""
        n, rank = int(U.shape[0]), int(U.shape[1])
        cap = row_capacity(n)
        if prev is not None and prev.rank == rank \
                and n <= int(prev.U.shape[0]):
            cap = int(prev.U.shape[0])
        if placed is not None and self.mesh is None \
                and placed.shape == (cap, rank):
            return _copy_table(placed).block_until_ready(), n, 0
        # wait for it: what a publish allocates next (the catalog, its
        # index) is then allocated after the last chunk's buffer is freed
        return (place_rows(U, capacity=cap, mesh=self.mesh,
                           table="users").block_until_ready(),
                n, 4 * n * rank)

    def _update_users(self, prev, U, touched_users, placed, ride):
        """What a ``publish_update`` does to the user table: ``(how,
        users, live rows, bytes sent)``.  ``inplace``: the
        ``touched_users`` rows of ``U`` (and the rows appended since)
        are uploaded alone — O(touched) host work and traffic, no shape
        change — and ``users`` is the ``(rows, vals)`` that
        :func:`_scatter_users` writes into the live table, which the
        caller does under ``_table_lock``.  With ``placed``, the
        caller's ``(rows, their values on the device, padded)``, and
        where those are the rows this publish writes (and no mesh),
        nothing is uploaded here: the row numbers ride ``ride`` and
        ``users`` is ``(None, the device's rows)`` until
        :meth:`_send` has placed them.  ``carried``: no row to
        write, ``users`` is the live table.  ``replaced``: ``users`` is a
        new table from :meth:`_place_users`, where a row write cannot be
        (no row list, no live generation, another rank, a shrunken
        table, spare rows used up, a row outside the table)."""
        n, rank = int(U.shape[0]), int(U.shape[1])
        if (touched_users is not None and prev is not None
                and prev.rank == rank
                and prev.n_users <= n <= int(prev.U.shape[0])):
            rows = np.union1d(
                np.asarray(touched_users, dtype=np.int64).ravel(),
                np.arange(prev.n_users, n))
            if not rows.size:
                return "carried", prev.U, n, 0
            if 0 <= int(rows[0]) and int(rows[-1]) < n:
                if self.mesh is None and _same_rows(placed, rows, rank):
                    ride.rows = padded_rows(placed[0], placed[1].shape[0],
                                            prev.U)
                    return "inplace", (None, placed[1]), n, 0
                pad = pad_for(len(rows))
                # the sentinel lies outside the table: dropped
                rp = padded_rows(rows, pad, prev.U)
                vals = np.zeros((pad, rank), dtype=np.float32)
                vals[:len(rows)] = U[rows]
                return ("inplace", put((rp, vals), self._replicated),
                        n, rp.nbytes + vals.nbytes)
        if touched_users is not None and prev is not None:
            obs.emit("warning", what="serving.publish_update",
                     reason=f"user rows rejected ({n} users against "
                            f"{prev.n_users} live of {prev.U.shape[0]}), "
                            "user table re-placed whole")
        return ("replaced",) + self._place_users(prev, U)

    def _swap(self, how, users, seq, n_users, V, valid, index, n_items,
              host=None, items=None, seen=None, appended=None,
              landing=False, took=None):
        """Install the next generation, the one place that assigns
        ``_model`` (but for :meth:`_compact_live`, which installs the
        same generation compacted); returns ``how`` it got its user
        table.  ``users`` is
        that table, or with ``how == "inplace"`` the ``(rows, vals)`` to
        write into the live one first.  ``items``: the ``(rows, vals,
        ok)`` to write into the live generation's OWN catalog first
        (:func:`_scatter_items`; ``V``/``valid`` are then ignored).
        ``appended``: what :meth:`_plan_append` made of the ids this
        generation adds to the live one's histories, written behind
        their users' runs first (:meth:`_write_history`; ``seen`` is then
        ignored) — the rows and the ids of one publish go in under one
        hold of the lock and are one ``_Published``: no batch is scored
        from a row that knows a rating and a history that lacks it.  The
        donating calls and the swap
        are one step under ``_table_lock``: the engine thread reads the
        live generation and scores against its tables under the same
        lock, so it never holds a deleted one.  The calls are asynchronous
        and their uploads were made before: the lock is held for the
        dispatches alone.

        A row write that raises AFTER the donation took effect leaves no
        table at all.  With ``host``, the whole table the rows came
        from, the next generation is then placed anew from it, still
        under the lock (``"replaced"``, with a warning); without it the
        warning names the state — every batch fails until a ``publish``
        — and the error is raised.  ``landing``: a whole generation
        installed on a started engine (:meth:`publish`): the same two
        spans under the landing's names, their seconds added to
        ``took``."""
        # the wait for the lock and what is done under it are two phases
        # of a publish on the profiler's timeline (the second is how long
        # a batch's stage can be kept out): ``programs``, the donating
        # calls dispatched under this hold
        programs = (int(how == "inplace") + int(items is not None)
                    + (0 if appended is None else 1 + len(appended.moves)))
        took = {} if took is None else took
        with _lap(took, "lock_wait"), (
                Stamped("live.landing.lock_wait") if landing
                else Stamped("live.batch.publish.lock_wait")):
            self._table_lock.acquire()
        try:
            with _lap(took, "swap"), (
                    Stamped("live.landing.swap") if landing else
                    Stamped("live.batch.publish.writes",
                            programs=programs)):
                if how == "inplace":
                    table = self._model.U
                    try:
                        users = (_scatter_users if self.mesh is None else
                                 _build_mesh_scatter(self.mesh))(table,
                                                                 *users)
                    except Exception as e:
                        if not self._model.U.is_deleted():
                            raise       # nothing was donated: all whole
                        fate = ("re-placed whole" if host is not None else
                                "NO user table until the next publish")
                        obs.emit("warning", what="serving.publish_update",
                                 reason="row write failed after donating "
                                        "the user table "
                                        f"({type(e).__name__}: {e}): {fate}")
                        if host is None:
                            raise
                        # a deleted table still reads its shape: same
                        # capacity
                        how, users = "replaced", self._place_users(
                            self._model, host)[0]
                if items is not None:
                    V, valid = _scatter_items(self._model.V,
                                              self._model.valid, *items)
                if appended is not None:
                    seen = self._write_history(self._model.seen, appended)
                self._model = _Published(seq, users, n_users, V, valid,
                                         index, n_items, seen)
        finally:
            self._table_lock.release()
        return how

    def _compact_rows(self, index):
        """Rows in the delta segment from which a publish folds it back
        into the base: the planner cadence's ``max(compact_min_rows,
        compact_delta_frac * catalog)``."""
        cad = self._live_cadence()
        return max(cad["compact_min_rows"],
                   cad["compact_delta_frac"] * index.n_items)

    def _segment_slots(self, index, at_least=None):
        """Slots of the delta segment of an index this engine serves
        from: the compaction threshold (:meth:`_compact_rows`) plus one
        ``max_batch`` — the most a segment holds before a publish folds
        it back — or ``at_least`` where a caller asks for more, as a
        power of two (the segment's scores join the shortlist at its
        last stage, ``ops.topk.shortlist_topk``'s ``tail``: they are in
        no block, so any number does).  Resolved here, once: a segment
        has no other size while the engine serves from it."""
        rows = max(self._compact_rows(index)
                   + self._live_cadence()["max_batch"], at_least or 0)
        return max(_next_pow2(int(np.ceil(rows))), index.delta_slots)

    def _compact_live(self):
        """Fold the live generation's delta segment into its base
        arrays, IN PLACE (``Int8CandidateIndex.compact``: they are
        donated to the scatter), and install the same generation with
        the compacted index, one step under ``_table_lock`` like a row
        write: compaction changes no answer, so ``seq`` stays.  A batch
        dispatched before it reads the old arrays whole.  An index whose
        catalog has outgrown its spare rows is enlarged first (a copy,
        new shapes: the pinned programs go stale; warned)."""
        with TraceAnnotation("live.batch.publish.compact"), \
                self._table_lock:
            m = self._model
            if m.index.n_items > m.index.n_base:
                obs.emit("warning", what="serving.publish_update",
                         reason=f"{m.index.n_items} items against "
                                f"{m.index.n_base} catalog rows: spare "
                                "rows used up, base arrays copied larger")
            rows = m.index.delta_count
            try:
                index = m.index.compact(m.index.seq)
            except Exception as e:
                # the donation may have taken the base arrays: serve
                # exact until the next publish_update rebuilds the index
                obs.emit("warning", what="serving.publish_update",
                         reason="compaction failed, index dropped "
                                f"({type(e).__name__}: {e})")
                index = None
            self._model = _Published(m.seq, m.U, m.n_users, m.V, m.valid,
                                     index, m.n_items, m.seen)
        obs.emit("serving_compaction", seq=m.seq, rows=rows,
                 **self._labels)

    def _place_seen(self, user_seen, n_users, n_items, rows=None):
        """The users' histories of a publish on the device
        (:class:`_Seen`), checked first: CSR ``(indptr, indices)`` over
        catalog ids, one row a user, a row's ids ascending and none
        twice.  On a mesh (``rows``: the placed user table's) sharded
        with that table (:meth:`_shard_seen`)."""
        ids = len(user_seen[1])
        with phase("start.publish.histories.check", ids=ids,
                   parts=-(-ids // CHECK_PART)):
            indptr, indices, lengths = self._checked_seen(
                user_seen, n_users, n_items)
        pads = history_pads(lengths.max(initial=0))
        with phase("start.publish.histories.place"):
            if self.mesh is not None:
                return self._shard_seen(indptr, indices, lengths, pads,
                                        rows)
            # spare ids at the end: a slice of the longest pad from the
            # last user's first id stays inside the table
            # (``_select_seen``)
            host = (indptr.astype(np.int32),
                    np.concatenate([indices.astype(np.int32),
                                    np.full(pads[-1], NOT_AN_ID, np.int32)]))
            count_placed("histories", sum(a.nbytes for a in host))
            return _Seen(*jax.device_put(host), lengths.astype(np.int32),
                         pads)

    @staticmethod
    def _checked_seen(user_seen, n_users, n_items):
        """``(indptr, indices, lengths)`` of a publish's histories, or
        ``ValueError``: CSR over catalog ids, one row a user, a row's ids
        ascending and none twice.

        The ids are walked ONCE, in parts of ``CHECK_PART`` ids
        (:func:`_part_rises`) and as they are: no ``int64`` copy of them,
        no ``diff``, no array an id wide beyond a part's own comparisons
        (ids of no integer type — an empty list's ``float64`` — are cast
        once, and refused where the cast moved one).  The first fault
        ends the walk.  On ONE thread: on the chip's host a pool of eight
        took the mesh cell's 143 M ids in 0.18 s where one thread takes
        0.24 (the form before: 3.5; PERF.md section 6, PR 58) — nothing a
        start would notice, so there is none."""
        indptr, indices = (np.asarray(a) for a in user_seen)
        if indptr.shape != (n_users + 1,) or indptr[0] != 0 \
                or indptr[-1] != len(indices) \
                or len(indices) >= NOT_AN_ID:
            raise ValueError(
                f"user_seen: indptr of shape {indptr.shape} ending at "
                f"{indptr[-1] if len(indptr) else None} for {n_users} "
                f"users and {len(indices)} ids")
        lengths = np.diff(indptr)
        ok = bool((lengths >= 0).all())
        if ok and indices.dtype.kind not in "iu":
            given, indices = indices, indices.astype(np.int64)
            ok = bool((indices == given).all())
        if ok:
            ok = all(_part_rises(indptr, indices, n_items, lo,
                                 min(lo + CHECK_PART, len(indices)))
                     for lo in range(0, len(indices), CHECK_PART))
        if not ok:
            raise ValueError(
                "user_seen: every row holds catalog ids in "
                f"[0, {n_items}), ascending, none twice")
        return indptr, indices, lengths

    def _shard_seen(self, indptr, indices, lengths, pads, rows):
        """Checked histories sharded with a user table of ``rows`` rows
        (:class:`_Seen`, ON A MESH): each shard's runs and ids go to its
        own device alone — a history is never whole on another chip, nor
        all of them on the host a second time."""
        S, n_users = len(self._devices), len(lengths)
        n_loc = rows // S
        bounds = np.minimum(np.arange(S + 1) * n_loc, n_users)
        cuts = indptr[bounds]
        # the common length in whole granules of about a 32nd of a
        # shard's even share (65,536 ids at least), as a table's rows are
        # (``row_capacity``): WHO holds which history moves the fullest
        # shard by a fraction of a per cent, and a shape that moved with
        # it would compile every scoring program anew for every such
        # publish (47 s of the four-chip cell's start, a seed: PERF.md
        # section 6, PR 52)
        granule = max(1 << 16, _next_pow2(-(-len(indices) // S)) >> 5)
        width = (-(-int(np.diff(cuts).max()) // granule) * granule
                 + pads[-1])

        def runs_of(s):
            own = np.full(n_loc + 1, cuts[s + 1] - cuts[s], np.int32)
            mine = indptr[bounds[s]:bounds[s + 1] + 1] - cuts[s]
            own[:len(mine)] = mine
            return own

        def ids_of(s):
            own = np.full(width, NOT_AN_ID, np.int32)
            own[:cuts[s + 1] - cuts[s]] = indices[cuts[s]:cuts[s + 1]]
            return own

        def sharded(part, length):
            count_placed("histories", 4 * S * length)
            return jax.make_array_from_single_device_arrays(
                (S * length,), self._by_rows,
                [jax.device_put(part(s), d)
                 for s, d in enumerate(self._devices)])

        held = np.zeros(rows, np.int32)
        held[:n_users] = lengths
        return _Seen(sharded(runs_of, n_loc + 1), sharded(ids_of, width),
                     held, pads)

    def _lay_out(self, seen, rows, more=0):
        """``seen`` laid out anew as histories that GROW (:class:`_Seen`),
        for a user table of ``rows`` rows: every run with room behind it
        for an eighth more ids, 8 at least (``core.ratings.growth_room``),
        free room behind the last run (an eighth of the table, 65,536 ids
        at least, and ``more``), the ladder of pads with the rung above
        the longest history that its growth needs, and as many spare ids
        at the end.  The ids move on the device (:func:`_spread_runs`;
        the host sends where from and where to, 8 bytes an id, once);
        O(all the histories), so it is ``warmup_live``'s to call before
        the traffic — under it only where the room laid out here is used
        up, with a warning.

        The plan is made BY RUN: what the host builds an id wide is the
        two ``int32`` arrays it sends and one ``arange`` they share — no
        id's user, no place within its run, no gather (five ``int64``
        arrays an id wide until PR 58)."""
        held_ids = int(seen.lengths.sum())
        with phase("start.warmup_histories.plan", ids=held_ids):
            n = len(seen.lengths)
            lengths = np.zeros(rows, np.int32)
            lengths[:n] = seen.lengths
            cap = lengths + growth_room(lengths)
            cap[n:] = 0     # a spare row's user gets a run with its first id
            start = np.zeros(rows, np.int64)
            np.cumsum(cap[:-1], out=start[1:])
            held = int(start[-1] + cap[-1])
            size = held + max(1 << 16, held >> 3) + int(more)
            pads = history_pads(lengths.max(initial=0), grows=True)
            if size + pads[-1] >= NOT_AN_ID:
                raise ValueError(f"{held} ids of history with room to "
                                 "grow: more than int32 positions hold")
            # an id's place is its place among the ids packed run after
            # run plus what its RUN lies off its packed start: one
            # ``repeat`` of a per-run offset a side (none where the table
            # is as published: its runs lie packed), nothing else an id
            # wide, int32 throughout (every place lies below ``size``)
            packed = np.cumsum(seen.lengths) - seen.lengths
            at = np.arange(held_ids, dtype=np.int32)

            def places(run_starts):
                off = np.repeat((run_starts - packed).astype(np.int32),
                                seen.lengths)
                return np.add(at, off, out=off)

            src = at if seen.room is None else places(seen.room.start[:n])
            dst = places(start[:n])
            runs = (start.astype(np.int32), lengths.copy())
        with phase("start.warmup_histories.place"):
            count_placed("histories",
                         sum(a.nbytes for a in runs + (src, dst)))
            dev, moves = jax.block_until_ready(
                (jax.device_put(runs), jax.device_put((src, dst))))
        with phase("start.first_run"):
            indices = _spread_runs(seen.indices, *moves,
                                   size=size + pads[-1]).block_until_ready()
        return _Seen(dev, indices, lengths, pads,
                     _Room(start, cap, held, size))

    def _append_history(self, n_users, n_items, appended):
        """The history half of a ``publish_update`` on a generation that
        holds histories, up to the write (which is :meth:`_swap`'s): the
        plan of :meth:`_plan_append` for ``appended`` (``None``: no id,
        the table carried as it is; ids below ``n_items``, the catalog's
        size as this publish leaves it), inside the span
        ``live.batch.publish.history`` and counted; the plan is still
        on the host (:meth:`_place_plan` sends it, alone or riding the
        publish's one array).  Where the table has
        no room for the plan — nobody laid it out to grow, or the room
        is used up — it is laid out anew first (:meth:`_lay_out`: O(all
        the histories), shapes change and the pinned programs go stale;
        warned)."""
        with TraceAnnotation("live.batch.publish.history") as span:
            mark = cpu_mark()
            m = self._model
            if appended is None:
                appended = ((), ())
            plan = (None if m.seen.room is None else self._plan_append(
                m.seen, n_users, n_items, appended))
            if plan is None:
                obs.emit("warning", what="serving.publish_update",
                         reason="the histories have no room for this "
                                "publish as they are laid out (warmup_live "
                                "lays them out to grow): laid out anew")
                longest = int(m.seen.lengths.max(initial=0))
                with self._table_lock:
                    m = self._model
                    self._model = _Published(
                        m.seq, m.U, m.n_users, m.V, m.valid, m.index,
                        m.n_items, self._lay_out(
                            m.seen, max(n_users, int(m.U.shape[0])),
                            more=8 * (longest + len(appended[0]))))
                plan = self._plan_append(self._model.seen, n_users,
                                         n_items, appended)
                if plan is None:
                    raise ValueError(
                        "seen_appended: more ids for one user than a "
                        "table laid out anew has room for")
            ids, moves = len(np.ravel(appended[0])), len(plan.moves)
            span.set_metadata(ids=ids, users=len(plan.users),
                              relocated=moves)
            stamp_cpu(span, mark)
        obs.counter("live.history_appended_ids", ids, **self._labels)
        obs.counter("live.history_relocations", moves, **self._labels)
        return plan

    def _plan_append(self, seen, n_users, n_items, appended):
        """What appending ``appended`` — ``(users, items)``: rows of the
        user table and the catalog ids their ratings add, any order, a
        user any number of times — to the grown table ``seen`` takes
        (:class:`_Append`, for :meth:`_write_history`), or ``None``
        where the table has no room
        for it as it is laid out (too few rows, the free room used up,
        a history past the longest pad).  O(ids appended) on the host
        and on the link; writes nothing."""
        users = np.asarray(appended[0], dtype=np.int64).ravel()
        items = np.asarray(appended[1], dtype=np.int64).ravel()
        if users.shape != items.shape or (users.size and not (
                0 <= users.min() and users.max() < n_users
                and 0 <= items.min() and items.max() < n_items)):
            raise ValueError(
                f"seen_appended: {users.size} users and {items.size} items, "
                f"to lie in [0, {n_users}) and [0, {n_items})")
        room = seen.room
        if n_users > len(room.start):
            return None
        order = np.argsort(users, kind="stable")
        users, items = users[order], items[order]
        uniq, first, added = np.unique(users, return_index=True,
                                       return_counts=True)
        had = seen.lengths[uniq].astype(np.int64)
        start, cap, free = room.start[uniq], room.cap[uniq], room.free
        moves = []
        for j in np.flatnonzero(had + added > cap):
            # the run is full (a new user's: not there yet): to the free
            # room, with room to grow again
            need = int(had[j] + added[j])
            cap[j] = need + int(growth_room(need))
            if had[j]:
                moves.append((int(start[j]), free,
                              rung_for(int(had[j]), seen.pads)))
            start[j], free = free, free + int(cap[j])
        if free > room.size or (had + added).max(initial=0) > seen.pads[-1]:
            return None
        n = len(users)
        plan = self._no_append(seen, pad_for(n))
        plan[0, :len(uniq)], plan[1, :len(uniq)] = uniq, start
        plan[2, :len(uniq)] = had + added
        plan[3, :n] = (np.repeat(start + had, added) + np.arange(n)
                       - np.repeat(first, added))
        plan[4, :n] = items
        return _Append(plan, moves, uniq, start, cap, had + added, free,
                       sent=plan.nbytes + 8 * len(moves))

    def _send(self, ride, seen, pad=0):
        """``ride``'s parts as ONE ``int32[PUBLISH_SENT, pad]`` on the
        device (``pad``: the widest part's, or wider for whoever runs
        the programs ahead; a part's padding entries lie outside its
        arrays — ``seen``'s, for the plan — and a part the publish lacks
        is never read): the one placement of a publish whose rows lie on
        the device."""
        parts = [a for a in (ride.rows, ride.segment, ride.plan)
                 if a is not None]
        with Stamped("live.batch.publish.ride") as span:
            sent = np.zeros(
                (PUBLISH_SENT, max(pad, *(a.shape[-1] for a in parts))),
                np.int32)
            if ride.rows is not None:
                sent[0, :len(ride.rows)] = ride.rows
            if ride.segment is not None:
                sent[SENT_SEGMENT:SENT_SEGMENT + SEGMENT_SENT,
                     :ride.segment.shape[1]] = ride.segment
            if ride.plan is not None:
                sent[-SENT_PLAN:] = self._no_append(seen, sent.shape[1])
                sent[-SENT_PLAN:, :ride.plan.shape[1]] = ride.plan
            ride.sent = put(sent)
            span.set_metadata(bytes=sent.nbytes)
        return ride.sent

    def _place_plan(self, appended, ride):
        """``appended`` (:meth:`_append_history`) with its plan on the
        device: the publish's one array where there is one, else
        uploaded by itself; ``sent`` is what it added to the link."""
        if ride.sent is None:
            return appended._replace(args=put(appended.args))
        return appended._replace(
            args=ride.sent,
            sent=appended.sent + 4 * SENT_PLAN * (
                ride.sent.shape[1] - appended.args.shape[1]))

    @staticmethod
    def _no_append(seen, pad):
        """:func:`_append_runs`' ``plan`` of ``pad`` entries that writes
        nothing: every user and every position outside its array."""
        plan = np.zeros((5, pad), np.int32)
        plan[0] = len(seen.room.start)
        plan[3] = seen.room.size + seen.pads[-1]
        return plan

    def _write_history(self, seen, plan):
        """The next generation's histories: ``plan``
        (:meth:`_plan_append`) written into the live one's IN PLACE —
        the full runs moved, then the ids and their users' starts and
        counts, all donated and in dispatch order — and the host's
        account brought up to it.  Under ``_table_lock``
        (:meth:`_swap`); the calls are asynchronous."""
        indices, room = seen.indices, seen.room
        try:
            for old, new, width in plan.moves:
                indices = _move_run(indices, old, new, width=width)
            runs = _append_runs(*seen.runs, indices, plan.args)
        except Exception as e:
            obs.emit("warning", what="serving.publish_update",
                     reason="history write failed "
                            f"({type(e).__name__}: {e}): NO histories "
                            "until the next publish, every batch fails")
            raise
        room.start[plan.users], room.cap[plan.users] = plan.start, plan.cap
        room.free = plan.free
        seen.lengths[plan.users] = plan.lengths
        return _Seen(runs[:2], runs[2], seen.lengths, seen.pads, room)

    def _without_history(self, m):
        """The empty table of histories: what a batch of a generation
        ``m`` that published none rides when a request brings a list of
        its own (on a mesh sharded like a real one, for ``m``'s user
        table)."""
        rows = None if self.mesh is None else int(m.U.shape[0])
        if self._no_history is None or self._no_history[0] != rows:
            pads = history_pads(0)
            if self.mesh is None:
                seen = _Seen(
                    *jax.device_put((np.zeros(2, np.int32),
                                     np.full(pads[-1], NOT_AN_ID,
                                             np.int32))),
                    None, pads)
            else:
                seen = self._shard_seen(
                    np.zeros(1, np.int64), np.empty(0, np.int32),
                    np.empty(0, np.int32), pads, rows)
                seen.lengths = None
            self._no_history = (rows, seen)
        return self._no_history[1]

    def publish(self, U, V, item_valid=None, quantize=True, user_seen=None,
                *, placed=None, release=False):
        """Swap in a new model generation atomically.

        ``quantize=True`` builds the int8 candidate index for the new
        catalog (skipped when the catalog is smaller than ``k`` — the
        exact pass is already minimal there); ``quantize=False`` keeps
        serving exact until the next quantized publish (the old index,
        if any, is carried but detected as stale and never used).
        Returns the publish sequence number.

        ``user_seen``: the users' histories, CSR over catalog ids
        (``indptr int64[n_users + 1]``, ``indices int32[nnz]``; a row's
        ids ascending, none twice).  They are placed on the device once,
        with the generation, and swapped with it; from then on a request
        by user id is answered WITHOUT the ids of that user's history
        (and without its own ``exclude`` list: :meth:`submit`), by the
        rule ``ops.topk.excluded_mask`` states, on the int8 path and on
        the exact fallback alike.  ``None`` publishes none: the engine
        then compiles and runs what it did before it knew of histories.
        With a mesh the histories are sharded with the user table — a
        history lies once on the mesh, on the chip that holds its user's
        row (:class:`_Seen`) — the owning shard hands a batch its lists
        inside the scoring program (:func:`_mesh_history`) and every
        shard masks the ids it owns among its own columns
        (``serving.index.shard_lists``).
        The histories published here lie run after run with no room
        between them; :meth:`warmup_live` lays them out to GROW, after
        which :meth:`publish_update` appends to them
        (``seen_appended``), also while its catalog moves
        (``touched_items``).

        **On a STARTED engine** (a refit LANDS: ``LiveUpdater.land``) the
        new generation is built whole BESIDE the live one — user table,
        catalog, the index's own float32 catalog and its int8 rows: 5.2
        GB more at 1.7 M users and 1.5 M items of rank 256, held from the
        first placement to the swap, while every request is answered
        from the live generation — at the LIVE generation's capacities:
        the user table's spare rows, and where the live catalog has spare
        rows and its index a segment (:meth:`warmup_live`) the same rows
        and a fresh, EMPTY segment of the same slots
        (``Int8CandidateIndex.over``), so every pinned program takes the
        new tables as they are and nothing compiles
        (:meth:`warmup_landing` runs the programs this path runs ahead of
        the traffic).  It is installed by one :meth:`_swap`; a request
        dequeued before it is answered wholly from the old generation,
        one after it wholly from the new.  ``release`` then deletes what
        the swap replaced (every array of the old generation that the new
        one does not share): nothing of it is left on the device once the
        batches in flight have run.  ``placed``: ``(user table | None,
        catalog | None)`` where the caller holds the same tables on the
        device already, at those capacities (``FoldInServer.
        device_tables``): the generation's tables are then COPIES made on
        the device (the caller goes on writing its own) and nothing of
        them crosses host→device.  The steps are on the profiler's
        timeline as ``live.landing.users`` / ``.catalog`` / ``.index`` /
        ``.lock_wait`` / ``.swap`` / ``.release``
        (``obs.schema.LIVE_LANDING_SPAN_KEYS``; a start's are start
        phases) and their seconds in ``last_landing``.
        """
        started = self._thread is not None
        took = {}
        with (contextlib.nullcontext() if started
              else phase("start.publish")):
            t0 = time.perf_counter()
            mode = faults.check("serving.publish")
            Vh = np.asarray(V, dtype=np.float32)
            Ni, rank = int(Vh.shape[0]), int(Vh.shape[1])
            prev, sent0 = self._model, placed_bytes()
            Ud, Vd = placed if placed is not None else (None, None)
            with _lap(took, "users"), (
                    Stamped("live.landing.users") if started
                    else phase("start.publish.users")):
                U, n_users, _ = self._place_users(prev, U, Ud)
            # bytes of whole tables this generation took from tables on
            # the device (``placed``; the index's own catalog)
            copied = (int(U.nbytes) if self.mesh is None and Ud is not None
                      and Ud.shape == U.shape else 0)
            seen = None
            if user_seen is not None:
                # behind the table they are sharded with, on a mesh
                with phase("start.publish.histories"):
                    seen = self._place_seen(user_seen, n_users, Ni,
                                            rows=int(U.shape[0]))
            # the LIVE catalog's rows where it has spare ones and this
            # one fits them: same shapes, same programs
            rows = Ni
            if (self.mesh is None and prev is not None
                    and prev.rank == rank
                    and prev.n_items < int(prev.V.shape[0])
                    and Ni <= int(prev.V.shape[0])):
                rows = int(prev.V.shape[0])
            validh = np.zeros(rows, dtype=bool)
            validh[:Ni] = (True if item_valid is None
                           else np.asarray(item_valid, dtype=bool).ravel())
            self._announce_mesh()
            with _lap(took, "catalog"), (
                    Stamped("live.landing.catalog") if started
                    else phase("start.publish.catalog")):
                V, valid = jax.block_until_ready(
                    self._place_catalog(Vh, validh, Vd))
            if (self.mesh is None and Vd is not None
                    and Vd.shape == V.shape):
                copied += int(V.nbytes)
            with self._publish_lock:
                seq = self._seq + 1
                sk = min(max(self.shortlist_k, self.k), Ni)
                index = None
                live = prev.index if prev is not None else None
                slots = (live.delta_slots if live is not None
                         and live.seq == prev.seq else 0)
                if quantize and sk >= self.k and Ni > 0:
                    with _lap(took, "index"), (
                            Stamped("live.landing.index") if started
                            else phase("start.publish.index")):
                        if self.mesh is None and (rows > Ni or slots):
                            # at the live index's shapes: a copy of the
                            # table for the index's own (its compaction
                            # donates it), quantized whole, an empty
                            # segment
                            index = Int8CandidateIndex.over(
                                _copy_table(V), validh, Ni, sk, seq,
                                slots).block_until_ready()
                            copied += int(V.nbytes)
                        else:
                            # without a mesh the index uploads a copy of
                            # its own from the host's catalog, as it
                            # always has (the device then holds V twice:
                            # PERF.md section 7); with one it shares the
                            # engine's sharded table
                            index = self._build_index(
                                *((Vh, validh) if self.mesh is None
                                  else (V, valid)), Ni, sk, seq)
                    if mode == "corrupt":
                        # injected torn publish: quantization died mid-swap,
                        # so the fresh index is never published.  The
                        # previous generation's index is carried (stale by
                        # seq, detected on the score path) or the publish
                        # goes out index-less — no in-place seq mutation
                        # either way.
                        index = (self._model.index
                                 if self._model is not None else None)
                elif self._model is not None:
                    index = self._model.index      # carried, now stale
                self._swap("replaced", U, seq, n_users, V, valid, index, Ni,
                           seen=seen, landing=started, took=took)
                self._seq = seq
                if release and prev is not None:
                    with _lap(took, "release"), \
                            Stamped("live.landing.release"):
                        _release(prev, self._model)
            if started:
                self.last_landing = {
                    "seq": seq, "users": n_users, "items": Ni,
                    "seconds": took,
                    "placed_bytes": placed_bytes() - sent0,
                    "copied_bytes": copied}
            fresh = index is not None and index.seq == seq
            obs.counter("serving.publishes", **self._labels)
            obs.histogram("serving.publish_seconds",
                          time.perf_counter() - t0,
                          mode="full" if fresh else "none", **self._labels)
            obs.emit("serving_publish", seq=seq, items=Ni, quantized=fresh,
                     mode="full" if fresh else "none", delta_rows=0,
                     **self._labels)
            return seq

    def _write_catalog(self, Vh, rows, item_valid, seq, placed, ride):
        """The item side of a ``publish_update`` with a live index:
        ``rows`` of the host's catalog ``Vh`` (touched and appended, in
        order) uploaded alone and written into the index's delta segment
        — folded into the base first where it has no room for them —
        and handed on, on the device, for the engine's own table.  With
        ``placed``, the caller's ``(rows, their values on the device,
        padded)``, and where those are the rows this publish writes, no
        row is uploaded: the segment is written from the device's rows,
        and what its write takes from the host rides ``ride``, which is
        sent here (:meth:`_send`).
        Returns ``(index, the (rows, vals, ok) that _swap writes in
        place or None where that table cannot take them, bytes sent,
        whether a compaction ran)``; a row outside the catalog raises
        ``ValueError``."""
        prev, Ni = self._model, int(Vh.shape[0])
        cur = prev.index
        if int(rows[-1]) >= Ni:
            raise ValueError(f"touched row {int(rows[-1])} outside "
                             f"the catalog [0, {Ni})")
        vls = (np.ones(len(rows), dtype=bool)
               if item_valid is None else item_valid[rows])
        slots, compacted = self._segment_slots(cur), False
        if cur.delta_count and (cur.slots_needed(rows)
                                > slots - cur.delta_count):
            self._compact_live()
            prev, compacted = self._model, True
            cur = prev.index
            if cur is None:
                raise ValueError("the index was lost in its compaction")
        if cur.delta_slots < slots:     # an engine nobody warmed up
            cur = cur.reserve(slots=slots)
        if self.mesh is None and _same_rows(placed, rows, prev.rank):
            update, _ = cur.plan_update(rows, vls)
            ride.segment = update.sent(
                placed[1].shape[0], np.searchsorted(update.ids, placed[0]))
            index = cur.write_update(
                update, self._send(ride, prev.seen), placed[1],
                at=SENT_SEGMENT, seq=seq)
            sent = 4 * SEGMENT_SENT * ride.sent.shape[1]
        else:
            index = cur.with_updates(
                rows, np.ascontiguousarray(Vh[rows], dtype=np.float32),
                valid_rows=vls, seq=seq)
            sent = segment_write_bytes(len(rows), prev.rank)
        items = None
        cap = int(prev.V.shape[0])
        if self.mesh is None and prev.n_items <= Ni <= cap:
            # the engine's own catalog takes the same rows, as they
            # already lie on the device (padding ids fall outside it)
            items = index.written
        elif self.mesh is None:
            obs.emit("warning", what="serving.publish_update",
                     reason=f"{Ni} items against {cap} catalog rows: "
                            "spare rows used up, the engine's catalog "
                            "re-placed whole")
        return index, items, sent, compacted

    def publish_update(self, U, V, *, touched_items=None,
                       touched_users=None, item_valid=None, trace=None,
                       seen_appended=None, device_rows=None):
        """Incremental publish after a fold-in: O(touched rows), not
        O(catalog).  Returns ``(seq, mode)``.

        ``touched_users``: rows of ``U`` that changed since the live
        publish (user fold-in); rows beyond the live user count are
        treated as appended.  The caller guarantees every OTHER row of
        ``U`` is unchanged: only the named rows are read from ``U``
        (which may be a view of a larger host buffer) and uploaded
        (``live.publish_h2d_bytes`` counts what every publish sends)
        and written into the device's table in place: the live
        generation's table is donated to the write, so whoever kept an
        earlier generation's ``U`` finds it deleted.  Without the list
        the whole of ``U`` is uploaded into a new table.
        ``serving.user_table_writes{how=inplace|replaced|carried}`` and
        ``users=`` on the ``serving_publish`` event say which it was.

        ``trace``: the causal-trace contexts (``obs.tracing``) of the
        rating events this publish makes visible; their trace ids are
        stamped onto the ``serving_publish`` event (``trace_ids``) so
        the trail records which trace(s) produced each seq.

        ``touched_items``: logical catalog rows of ``V`` that changed
        since the live publish (item fold-in); rows beyond the previous
        catalog size are treated as appended automatically, so a pure
        catalog-growth publish may pass ``touched_items=None``.  The
        caller guarantees every OTHER row of ``V`` is unchanged — the
        engine layers only the named/appended rows over the live index
        (``Int8CandidateIndex.with_updates``).  Modes:

        - ``retag``  — nothing in the catalog changed (user-only
          fold-in): the live index and the device's catalog are carried,
          zero quantization, nothing of ``V`` uploaded;
        - ``delta``  — touched/appended rows uploaded alone, quantized
          on the device into the delta segment and written in place
          into the engine's own catalog (``live.catalog_h2d_bytes``
          counts what went up);
        - ``compact``— the same, and the segment was folded into the
          base arrays in place (:meth:`_compact_live`): it had crossed
          the planner-resolved threshold, or had no room for the rows;
        - ``full``   — no usable live index (first publish, stale or
          exact-mode predecessor, catalog shrank, or a malformed
          update) → ordinary full rebuild;
        - ``none``   — catalog too small to index; serving stays exact.

        ``serving.catalog_writes{how=carried|delta|compact|replaced}``
        and ``catalog=`` on the ``serving_publish`` event say what
        became of the catalog.

        ``seen_appended``: on a generation that holds users' histories
        (``publish(user_seen=...)``), ``(users, items)`` — rows of ``U``
        and the catalog ids that the ratings folded into this publish
        add to those users' histories (a rating of an item its user had
        rated before adds none; the engine checks no pair against the
        history, and an id that stands twice is excluded once).  They
        are written behind their users' runs on the device, in place,
        and swapped in WITH the rows (:meth:`_swap`): a request
        dequeued after this publish is answered from the new row and
        without the new id, one before it from the old row with the old
        history, none from one of each.  O(ids appended) on the host and
        on the link (``live.history_h2d_bytes``,
        ``live.history_appended_ids``; the span
        ``live.batch.publish.history``), no array changes shape; a user
        whose run is full is moved to free room first
        (``live.history_relocations``).  A user appended to the table
        starts with an empty history.  The catalog of such a generation
        moves like any other (``touched_items``, appended rows): the
        user rows, the catalog rows and the ids of ONE publish are one
        generation, and an id may name an item the same publish appends
        (ids are checked against the catalog as this publish leaves it;
        ``live.history_segment_ids`` counts those that name an item the
        index holds in its segment).  ``seen_appended`` on a generation
        that holds no histories raises ``NotImplementedError``; so it
        does on a mesh, where histories are held as published and a
        publish carries them as they are (a user appended to the table
        has none).  The
        histories are laid out to grow by :meth:`warmup_live` /
        :meth:`warmup_histories`; on an engine nobody warmed up the first
        such publish does it, under the traffic, with a warning.

        ``device_rows``: for a caller that still holds the rows it
        names ON THE DEVICE (``FoldInServer.last_rows``: a fold's own
        result), ``{"users": (rows, vals), "items": (rows, vals)}``,
        either or both — ``vals`` a device array ``[pad, rank]`` up
        ``pad_for``'s ladder, its first ``len(rows)`` rows the new
        values of table rows ``rows`` (any order, none twice), the same
        bits as ``U[rows]`` / ``V[rows]``.  Where a side's ``rows`` are
        exactly the rows this publish writes on that side, they are
        written from the device — into the user table, the segment
        (quantized there) and the engine's catalog — and nothing of
        them crosses host→device again: all that goes up is ONE
        ``int32[PUBLISH_SENT, pad]`` (row numbers, slots and valid
        bits, the history's plan; :class:`_Ride`), placed before
        ``_table_lock`` is taken.  A side without them, or whose rows
        differ (users appended in between, a fold that took several
        calls), and every publish on a mesh, goes up from the host as
        above.  Same generation, same bits either way.
        """
        prev = self._model
        if seen_appended is not None and (prev is None
                                          or prev.seen is None):
            raise NotImplementedError(
                "seen_appended on a generation that holds no histories: "
                "publish(..., user_seen=...) first")
        if seen_appended is not None and self.mesh is not None:
            raise NotImplementedError(
                "seen_appended on a mesh engine: its histories lie as "
                "published, sharded with the user table; what is missing "
                "is the grown layout's shard-local write (each shard "
                "appending behind the runs it owns, as the row write of "
                "_build_mesh_scatter goes to the owning shard)")
        t0 = time.perf_counter()
        # keep a host handle: the delta path gathers only the touched
        # rows, and doing that in numpy costs O(touched) with no
        # shape-varying device executable (a jnp gather would compile
        # per distinct row-count — a recompile on every publish)
        Vh = (V if isinstance(V, np.ndarray)
              else np.asarray(V, dtype=np.float32))
        Ni = int(Vh.shape[0])
        if item_valid is not None:
            item_valid = np.asarray(item_valid, dtype=bool)
        self._announce_mesh()
        touched = (np.empty(0, dtype=np.int64) if touched_items is None
                   else np.unique(np.asarray(touched_items,
                                             dtype=np.int64).ravel()))
        placed, ride = device_rows or {}, _Ride()
        with self._publish_lock:
            seq = self._seq + 1
            prev = self._model
            with Stamped("live.batch.publish.users"):
                how, users, n_users, h2d = self._update_users(
                    prev, U, touched_users, placed.get("users"), ride)
            # planned against the catalog as THIS publish leaves it: an
            # id it appends may name an item it appends
            # (a mesh's histories do not grow: carried as they are, and
            # only with the table they are sharded with)
            carried = (prev.seen if prev is not None
                       and self.mesh is not None else None)
            if carried is not None and how == "replaced" \
                    and users.shape[0] != prev.U.shape[0]:
                raise NotImplementedError(
                    "a user table re-placed at another size under "
                    "histories on a mesh (they are sharded by its rows): "
                    "publish(..., user_seen=...) anew")
            appended = (None if prev is None or prev.seen is None
                        or carried is not None
                        else self._append_history(n_users, Ni,
                                                  seen_appended))
            if appended is not None:
                ride.plan = appended.args
            with Stamped("live.batch.publish.catalog"):
                cur = prev.index if prev is not None else None
                fresh = (cur is not None and cur.seq == prev.seq
                         and cur.n_items <= Ni)
                rows = (np.union1d(touched, np.arange(cur.n_items, Ni))
                        if fresh else touched)
                # what becomes of the catalog: ``carried`` as it is, or the
                # touched rows written (``delta``), or uploaded whole
                # (``replaced``); ``items``: the rows for the engine's own
                # table, written in place by ``_swap``
                catalog, items, index, mode, sent = "replaced", None, None, \
                    "full", 0
                compacted = False
                if (prev is not None and not touched.size
                        and item_valid is None and prev.n_items == Ni):
                    # nothing of the catalog changed: the device's copy stays
                    V, valid, catalog = prev.V, prev.valid, "carried"
                    if fresh:
                        index, mode = cur.retag(seq), "retag"
                elif fresh and not rows.size:
                    # a validity mask alone, no row named: the index is
                    # carried as it is (the caller's guarantee)
                    index, mode = cur.retag(seq), "retag"
                elif fresh:
                    try:
                        index, items, sent, compacted = self._write_catalog(
                            Vh, rows, item_valid, seq, placed.get("items"),
                            ride)
                        mode, catalog, prev = "delta", "delta", self._model
                        V, valid = prev.V, prev.valid
                    except ValueError as e:
                        obs.emit("warning", what="serving.publish_update",
                                 reason=f"delta rejected, full rebuild: {e}")
                if catalog != "carried" and items is None:
                    # no row write to be had (no live index to take the
                    # rows, a mesh, spare rows used up): the whole catalog
                    valid_h = (np.ones(Ni, dtype=bool) if item_valid is None
                               else item_valid)
                    V, valid = self._place_catalog(Vh, valid_h)
                    sent += Vh.nbytes + valid_h.nbytes
                if index is None:
                    sk = min(max(self.shortlist_k, self.k), Ni)
                    if sk >= self.k and Ni > 0:
                        index = self._build_index(V, valid, Ni, sk, seq)
                    else:
                        mode = "none"
            # last: every step above may raise or take long (an index
            # build), and from the row write on the old table is gone
            with Stamped("live.batch.publish.send"):
                in_segment = (0 if seen_appended is None or index is None
                              else int(np.isin(seen_appended[1],
                                               index.d_rows).sum()))
                if ride.wanted:
                    self._send(ride, self._model.seen)
                if ride.sent is not None:
                    # the user table's counter takes what no other part
                    # accounts for: the rows' own row and the unread ones
                    h2d += 4 * ride.sent.shape[1] * (
                        PUBLISH_SENT
                        - (SEGMENT_SENT if ride.segment is not None else 0)
                        - (SENT_PLAN if ride.plan is not None else 0))
                if ride.rows is not None:
                    users = (ride.sent, users[1])
                if appended is not None:
                    appended = self._place_plan(appended, ride)
            how = self._swap(how, users, seq, n_users, V, valid, index, Ni,
                             host=U, items=items, seen=carried,
                             appended=appended)
            self._seq = seq
            if (mode == "delta"
                    and index.delta_count >= self._compact_rows(index)):
                # past the cadence's threshold: folded into the base
                # now, so that the next publish finds an empty segment
                self._compact_live()
                index, compacted = self._model.index, True
            if compacted:
                mode = catalog = "compact"
        with Stamped("live.batch.publish.after"):
            obs.counter("serving.publishes", **self._labels)
            obs.counter("serving.user_table_writes", how=how,
                        **self._labels)
            obs.counter("serving.catalog_writes", how=catalog,
                        **self._labels)
            obs.counter("live.publish_h2d_bytes", h2d, **self._labels)
            obs.counter("live.catalog_h2d_bytes", sent, **self._labels)
            if appended is not None:
                obs.counter("live.history_segment_ids", in_segment,
                            **self._labels)
                obs.counter("live.history_h2d_bytes", appended.sent,
                            **self._labels)
            obs.histogram("serving.publish_seconds",
                          time.perf_counter() - t0, mode=mode,
                          **self._labels)
            linked = ({"trace_ids": sorted({c.trace_id for c in trace
                                            if c is not None})}
                      if trace else {})
            obs.emit("serving_publish", seq=seq, items=Ni,
                     quantized=bool(index is not None), mode=mode,
                     delta_rows=(index.delta_count
                                 if index is not None else 0),
                     users=how, catalog=catalog, **linked, **self._labels)
        return seq, mode

    def _live_cadence(self):
        if self._cadence is None:
            from tpu_als import plan as _plan

            self._cadence = _plan.resolve_live_cadence()
        return self._cadence

    @property
    def published_seq(self):
        m = self._model
        return m.seq if m is not None else 0

    @property
    def published_index(self):
        """The live generation's candidate index (None before the first
        publish or while serving exact)."""
        m = self._model
        return m.index if m is not None else None

    def user_rows(self, rows):
        """The float32 rows the live generation serves for user table
        ``rows``, read back to the host — O(len(rows)) off the device,
        under ``_table_lock`` (the table may be donated to a row write at
        any other time): for whoever checks a publish or a landing
        against what it meant to install, as ``published_index.rows``
        does for the catalog.  Compiles a gather per count: not for a
        request's path."""
        rows = np.asarray(rows, dtype=np.int32).ravel()
        with self._table_lock:
            m = self._model
            if m is None:
                raise NoModelPublished("publish(U, V) first")
            return np.asarray(jnp.take(m.U, rows, axis=0))

    def warmup(self):
        """Compile every (bucket, path) scoring executable now, against
        the published model — first-request latency must not carry a
        compile.  Records no metrics (a warmup sample in the latency
        histograms would poison the SLO tail serve-bench reports).

        This PINS the steady-state packed executables per bucket (a
        ``stages.Compiled`` each, by :meth:`_pin`: LOADED where the
        compile cache's directory holds the executable under the pin's
        key, which a start with a warm cache does without tracing or
        lowering anything; lowered, compiled and written there where it
        does not; plain AOT ``lower().compile()`` where no compile cache
        is configured), so the hot path calls a compiled program
        directly instead of going through jit-cache dispatch; a publish
        that changes array shapes invalidates a pin (the serve path
        falls back to the jit call and drops it) — re-run warmup to
        restore.  With a mesh the pinned programs are the sharded ones
        (``serving.index._build_sharded_int8``, :func:`_build_mesh_exact`), one a
        bucket and path (and history pad) like the others, and each int8
        one is announced by a ``serving_mesh_plan`` event.  For a generation that holds
        users' histories (``publish(user_seen=...)``) the pinned
        programs are the ones that exclude (:meth:`_warm_exclusion`: the
        int8 program at every history pad, the exact one at the
        longest), each announced by a ``serving_exclusion`` event, and
        the programs without histories are not compiled: no batch of
        such a generation runs them.

        Holds ``_table_lock`` throughout, as whoever hands the live user
        table to the device must: no row write donates the table
        meanwhile, and the engine thread dispatches nothing — warm up
        before the traffic.  (The lock is taken HERE and not by a
        wrapper around the method: one more Python frame between the
        caller and ``lower()`` cost the six lowerings 0.33 s on the
        chip's host; PERF.md section 6, PR 31.  Since PR 51 that is a
        cold start's cost alone.)
        """
        with phase("start.warmup"), self._table_lock:
            m = self._model
            if m is None:
                raise NoModelPublished("publish(U, V) before warmup")
            self._pinned.clear()
            for B in self.batcher.buckets:
                if m.seen is not None:
                    # every batch of this generation excludes: the
                    # programs without histories would never run
                    self._warm_exclusion(m, B)
                    continue
                proto = self._proto(B, m.rank)
                idx = m.index
                if idx is not None and idx.seq == m.seq:
                    self._emit_shortlist(B, idx)
                    self._pin((B, self._int8_pin(idx)),
                              self._int8_call(m, idx, proto))
                    if self.mesh is not None:
                        obs.emit("serving_mesh_plan", bucket=B,
                                 **self._mesh_plan(m, idx, B),
                                 **self._labels)
                # the exact path backs every fallback: always warm
                self._pin((B, "exact"), self._exact_call(m, proto))

    def _pin(self, key, call, run=False):
        """Pin ``call`` — ``(function, arguments, static arguments)`` as
        :meth:`_int8_call` / :meth:`_exact_call` give them — under
        ``key`` = ``(bucket, path[, history pad])``: the one place a
        scoring program is pinned (:func:`serving.pins.pin` loads it or
        compiles it), counted by ``serving.pins{source}`` and announced
        by a ``serving_pin`` event.  ``run``: the pinned program is run
        once on ``call``'s arguments, loaded or compiled (a program's
        first execution takes up to seconds: none is left to the
        traffic); the event's ``seconds`` are the pin's alone."""
        fn, args, statics = call
        with phase("start.pin"):
            t0 = time.perf_counter()
            c, source, nbytes, split = pins.pin(fn, args, statics)
            seconds = time.perf_counter() - t0
            self._pinned[key] = c
            obs.counter("serving.pins", source=source, **self._labels)
            obs.emit("serving_pin", bucket=key[0], path=key[1],
                     pad=key[2] if len(key) > 2 else None, source=source,
                     seconds=seconds, bytes=nbytes, split=split,
                     **self._labels)
        if run:
            with phase("start.first_run"):
                c(*args).block_until_ready()

    def _warm_exclusion(self, m, B):
        """Pin what a generation with histories runs for bucket ``B``,
        and run each once (a program's first execution takes up to
        seconds, and there are several a bucket: none is left to the
        traffic): the int8 program at every history pad of its ladder
        (given the index's segment where it has one, and pinned under
        :meth:`_int8_pin`'s name then: ``(B, "int8_delta", pad)``),
        the exact fallback at the longest alone (a fallback batch rides
        that one whatever its histories: :meth:`_dispatch`); one
        ``serving_exclusion`` event each, from the plan the program's
        mask was traced with."""
        wide = self._proto(B, m.rank, wide=True)
        idx = m.index
        shards = 1 if self.mesh is None else len(self._devices)
        if idx is not None and idx.seq == m.seq:
            for pad in m.seen.pads:
                self._pin((B, self._int8_pin(idx), pad),
                          self._int8_call(m, idx, wide, m.seen, pad),
                          run=True)
                self._emit_shortlist(B, idx, history_pad=pad, **(
                    {"delta_rows": idx.delta_slots} if idx.delta_slots
                    else {}))
                # (a shard masks its own columns)
                cols = int(idx.Vq.shape[0]) // shards
                self._emit_exclusion(B, "int8", pad, cols,
                                     mask_block(cols))
                if self.mesh is not None:
                    obs.emit("serving_mesh_plan", bucket=B,
                             **self._mesh_plan(m, idx, B, pad),
                             **self._labels)
        pad = m.seen.pads[-1]
        self._pin((B, "exact", pad), self._exact_call(m, wide, m.seen, pad),
                  run=True)
        cols, chunk = self._scan_columns(m)
        self._emit_exclusion(B, "exact", pad, -(-cols // chunk) * chunk,
                             chunk)

    def _emit_exclusion(self, bucket, path, pad, columns, block):
        obs.emit("serving_exclusion", bucket=bucket, path=path,
                 history_pad=pad, request_pad=MAX_EXCLUDE,
                 **exclusion_plan(bucket, pad + MAX_EXCLUDE, columns,
                                  block)._asdict(), **self._labels)

    def _proto(self, B, rank, wide=False):
        """An empty staged batch of bucket ``B`` (``wide``: of a batch
        that excludes), placed where a real one is: what ``lower()``
        reads the input sharding from (without a mesh the default
        device's, where the pinned program's call places the host array;
        with one, :meth:`_place_one`'s)."""
        st = self._staged((), B, rank, wide)
        return jax.device_put(st) if self.mesh is None else \
            self._place_one(st)

    def _place_one(self, st):
        """The staged batch as a mesh program's argument, by ONE
        host→device transfer: the ``[S * B, width]`` array sharded by
        rows whose first block, on the mesh's first device, is ``st``
        and whose other blocks are zeros that lie on the other devices
        since the first batch of this shape (every batch reads them,
        none writes them) — assembled by one call of the runtime's
        batched placement, which transfers what is on the host and takes
        what is on its device already as it is.  The program's first
        operation sums the blocks (:func:`_mesh_spread`), so every shard
        sees the ``[B, width]`` bits a replicated argument gave it.  (A
        host array handed to the sharded program's call is placed by
        Python's ``shard_args``, a transfer a shard on the calling
        thread: 0.53 ms of the engine thread a batch on four chips,
        PERF.md section 5, Since PR 44.)"""
        held = self._idle_blocks.get(st.shape)
        if held is None:
            held = self._idle_blocks[st.shape] = (
                jax.core.ShapedArray(
                    (len(self._devices) * st.shape[0], st.shape[1]),
                    st.dtype),
                [jax.device_put(np.zeros_like(st), d)
                 for d in self._devices[1:]])
        aval, zeros = held
        return batched_device_put(aval, self._by_rows, [st, *zeros],
                                  self._devices)

    def _int8_call(self, m, idx, packed, seen=None, pad=None):
        """``(jitted function, arguments, static arguments)`` of the
        int8 request path for one staged batch, scored by ``idx`` as it
        stands — with a delta segment (whatever it holds: its slots fix
        the shapes) or without one: the one program :meth:`warmup` /
        :meth:`warmup_live` pins (under :meth:`_int8_pin`) and
        :meth:`_dispatch` runs.  ``seen`` (with its history ``pad``):
        the batch excludes, and ``packed`` is the wide layout."""
        if self.mesh is not None:
            k_loc, sk_loc = idx.shard_widths(self.k)
            return (_build_sharded_int8(
                self.mesh, self.k, k_loc, sk_loc, idx.ni_loc,
                bool(idx.delta_slots), _mesh_queries, _pack_response,
                "serve_mesh_int8", pad),
                (m.U, packed,
                 *(() if seen is None else (seen.runs, seen.indices)),
                 *idx.score_args()), {})
        return (_serve_int8_packed,
                (m.U, idx.Vq, idx.sv, idx.V, idx.valid,
                 (*idx._seg, idx._last_id()) if idx.delta_slots else (),
                 () if seen is None else (seen.runs, seen.indices), packed),
                dict(k=self.k, shortlist_k=idx.shortlist_k, pad=pad))

    @staticmethod
    def _int8_pin(idx):
        """The name :meth:`_int8_call`'s program is pinned under."""
        return "int8_delta" if idx.delta_slots else "int8"

    def _exact_call(self, m, packed, seen=None, pad=None):
        """The same of the exact fallback, against the engine's own
        catalog handle: per shard with a mesh, nothing uploaded but the
        staged batch (the last catalog id is on the device already:
        :meth:`_last_item`)."""
        ni_loc, chunk = self._scan_columns(m)
        if self.mesh is None:
            return (_serve_exact_packed,
                    (m.U, m.V, m.valid,
                     () if seen is None else (seen.runs, seen.indices),
                     packed),
                    dict(k=self.k, pad=pad, item_chunk=chunk))
        return (_build_mesh_exact(self.mesh, self.k, min(self.k, ni_loc),
                                  ni_loc, chunk, pad),
                (m.U, packed,
                 *(() if seen is None else (seen.runs, seen.indices)),
                 m.V, m.valid, self._last_item(m.n_items)), {})

    def _scan_columns(self, m):
        """``(catalog rows the exact scan walks on one device, its
        chunk)``: the whole table, or with a mesh one shard's slice."""
        cols = int(m.V.shape[0]) // (1 if self.mesh is None
                                     else len(self._devices))
        return cols, min(self.item_chunk, max(cols, 1))

    def _last_item(self, n_items):
        """The last catalog id, replicated over the mesh (the exact
        fallback clamps its answers to it): uploaded once per catalog
        size, as ``ShardedInt8Index._last_id`` is for the int8 path, not
        once a fallback batch."""
        if self._last_id is None or self._last_id[0] != n_items:
            self._last_id = (n_items, jax.device_put(
                np.int32(n_items - 1), self._replicated))
        return self._last_id[1]

    def _mesh_plan(self, m, idx, bucket, pad=None):
        """What one batch of ``bucket`` rows costs the mesh, scored by
        ``idx`` (``None``: by the exact fallback; ``pad``: a batch that
        excludes, at that history pad): the fields of a
        ``serving_mesh_plan`` event, kept per (bucket, shapes) — the
        engine thread looks ``exchange_bytes`` and ``history_bytes`` up
        for every batch."""
        S = int(self.mesh.devices.size)
        ni_loc = int(m.V.shape[0]) // S if idx is None else idx.ni_loc
        k_loc = (min(self.k, ni_loc) if idx is None
                 else idx.shard_widths(self.k)[0])
        key = (bucket, ni_loc, k_loc, m.U.shape, pad)
        plan = self._plans.get(key)
        if plan is None:
            wide = 0 if pad is None else MAX_EXCLUDE
            plan = self._plans[key] = dict(
                shards=S, items_per_shard=ni_loc,
                users_per_shard=int(m.U.shape[0]) // S, k_loc=k_loc,
                placements=1,
                spread_bytes=mesh_spread_bytes(S, bucket, m.rank, wide),
                exchange_bytes=mesh_exchange_bytes(S, bucket, m.rank,
                                                   k_loc, wide),
                history_pad=pad,
                history_bytes=(0 if pad is None
                               else mesh_history_bytes(S, bucket, pad)))
        return plan

    def warmup_publish(self, max_rows=LIVE_PADS[-1]):
        """Compile AND run the user-row writes ``publish_update(
        touched_users=...)`` makes, for up to ``max_rows`` rows a publish
        (padded 8 / 64 / 512 ...; from the host's rows and from rows on
        the device, ``device_rows``), on the published table itself: each
        run writes nothing (every row the out-of-range sentinel), and its
        result, the same buffer with the same values, is installed as
        the live generation's table, since the write deleted the handle
        it was given.  ``LiveUpdater.start`` calls it."""
        with phase("start.warmup_publish"), self._publish_lock, \
                phase("start.first_run"):
            m = self._model
            if m is None:
                raise NoModelPublished("publish(U, V) before warmup")
            for pad in pads_up_to(max_rows):
                nothing = padded_rows((), pad, m.U)
                self._swap(
                    "inplace",
                    put((nothing, np.zeros((pad, m.rank), np.float32)),
                        self._replicated),
                    m.seq, m.n_users, m.V, m.valid, m.index, m.n_items,
                    seen=m.seen)
            # and from rows on the device, their numbers riding a
            # publish's one array, as wide or wider
            for pad, rows in _ride_shapes(max_rows, self.mesh):
                ride = _Ride()
                ride.rows = padded_rows((), rows, m.U)
                self._swap(
                    "inplace",
                    (self._send(ride, None, pad),
                     jnp.zeros((rows, m.rank), jnp.float32)),
                    m.seq, m.n_users, m.V, m.valid, m.index, m.n_items,
                    seen=m.seen)
            self._model.U.block_until_ready()

    def warmup_landing(self):
        """Run, on the published generation and ahead of the traffic,
        the programs a LANDING runs (:meth:`publish` on a started
        engine) that no start has run: the copy of a table on the device
        (:func:`_copy_table`, at the user table's shape and the
        catalog's) and the quantization of the whole catalog TABLE, spare
        rows and all (``Int8CandidateIndex.over``; at a start the live
        rows alone are quantized and the spare ones padded on).  Each
        result is dropped: one table's worth of room, a program at a
        time.  ``LiveUpdater.start`` calls it, after the other warm-ups,
        where the updater was told refits will land (``refits=True``).
        Nothing on a mesh engine (a landing there is ROADMAP R12)."""
        with phase("start.warmup_landing"), self._publish_lock, \
                phase("start.first_run"):
            m = self._model
            if m is None:
                raise NoModelPublished("publish(U, V) before warmup")
            if self.mesh is not None:
                return
            for table in (m.U, m.V):
                _copy_table(table).block_until_ready()
            if m.index is not None and m.index.seq == m.seq:
                m.index.prewarm_over()

    def warmup_live(self, max_delta_rows=None, max_rows=LIVE_PADS[-1]):
        """Make the published generation ready for a catalog that moves
        (``publish_update(touched_items=...)``), before any live
        traffic, so that no item publish changes a shape, compiles or
        runs a program for the first time under it:

        - the catalog gets SPARE ROWS (``core.ratings.row_capacity``, the
          user table's rule; the quantized rows in whole shortlist
          blocks of them) in the index's base arrays and in the
          engine's own table — one copy of each on the device, here —
          and the index its delta segment, at its one size
          (:meth:`_segment_slots`; ``max_delta_rows`` asks for at least
          that many);
        - the scoring program "with a segment" is pinned for every
          bucket AND run once, the exact fallback pinned again at the
          new shapes; the delta-free pins are dropped (an index with a
          segment never runs them);
        - the three write programs are run on the live arrays, writing
          nothing: the engine's catalog row write and the segment's, at
          every padded size up to ``max_rows`` rows a publish, and the
          compaction (each donates what it is given; the results, the
          same buffers with the same values, are installed).

        ``LiveUpdater.start`` calls it when ``fold_items`` is on.  Cheap
        no-op when the model serves exact.  ``seq`` does not move.

        A generation that holds users' histories
        (``publish(user_seen=...)``) is made ready for its HISTORIES to
        grow as well (:meth:`warmup_histories`, which an updater that
        folds no items calls alone), after the catalog: the programs
        pinned and run are then the ones that exclude AND score the
        segment, one a bucket and history pad of the grown ladder under
        ``(bucket, "int8_delta", pad)``, and the programs without
        histories are not compiled (no batch of such a generation runs
        them).
        """
        with phase("start.warmup_live"), self._publish_lock, \
                self._table_lock:
            m = self._model
            if m is None:
                raise NoModelPublished("publish(U, V) before warmup")
            idx = m.index
            if idx is None or idx.seq != m.seq:
                if m.seen is not None:
                    self._warm_histories(m, max_rows)
                return
            with phase("start.warmup_live.reserve"):
                rows = (idx.n_base if idx.n_base > idx.n_items
                        else row_capacity(idx.n_items))
                idx = idx.reserve(rows, self._segment_slots(idx,
                                                            max_delta_rows))
                # installed at once: the smaller base arrays go before the
                # engine's own table is copied larger (one table's worth
                # of room at a time)
                m = self._model = _Published(m.seq, m.U, m.n_users, m.V,
                                             m.valid, idx, m.n_items,
                                             m.seen)
                V, valid = m.V, m.valid
                if self.mesh is None and int(V.shape[0]) < idx.n_base:
                    more = idx.n_base - int(V.shape[0])
                    V = jnp.pad(V, ((0, more), (0, 0)))
                    valid = jnp.pad(valid, (0, more))
                jax.block_until_ready((V, valid))
                idx.block_until_ready()
            with phase("start.first_run"):
                if self.mesh is None:
                    for pad in pads_up_to(max_rows):
                        # every row the out-of-range sentinel: nothing
                        # written
                        V, valid = _scatter_items(V, valid, *put((
                            padded_rows((), pad, V),
                            np.zeros((pad, m.rank), np.float32),
                            np.zeros(pad, bool))))
                idx = idx.prewarm(max_rows)
                # the segment's write from rows on the device, what it
                # takes from the host riding a publish's one array
                nothing = idx.plan_update(())[0]
                for pad, rows in _ride_shapes(max_rows, self.mesh):
                    ride = _Ride()
                    ride.segment = nothing.sent(rows)
                    idx = idx.write_update(
                        nothing, self._send(ride, None, pad),
                        jnp.zeros((rows, m.rank), jnp.float32),
                        at=SENT_SEGMENT)
                jax.block_until_ready((V, valid))
                idx.block_until_ready()
            m = self._model = _Published(m.seq, m.U, m.n_users, V, valid,
                                         idx, m.n_items, m.seen)
            if m.seen is not None:
                # every batch of this generation excludes: what is pinned
                # is the programs that do, given the segment.  (Room for
                # more ids than rows: a publish appends its batch's ids
                # AND those that waited for their item's row)
                return self._warm_histories(m, max_rows + 1)
            for B in self.batcher.buckets:
                proto = self._proto(B, m.rank)
                self._pinned.pop((B, "int8"), None)
                self._pin((B, self._int8_pin(idx)),
                          self._int8_call(m, idx, proto), run=True)
                self._emit_shortlist(B, idx, delta_rows=idx.delta_slots)
                self._pin((B, "exact"), self._exact_call(m, proto))

    def warmup_histories(self, max_rows=LIVE_PADS[-1]):
        """Make a generation that holds users' histories
        (``publish(user_seen=...)``) ready for HISTORIES that grow
        (``publish_update(seen_appended=...)``) and for nothing else —
        its catalog gets neither spare rows nor a segment; what
        ``LiveUpdater.start`` calls where ``fold_items`` is off — before
        any live traffic: the histories are laid out with room behind
        every run and free room at the end (:meth:`_lay_out`, once), the
        programs that exclude are pinned AND run for every bucket and
        history pad of the grown ladder, in place of whatever
        :meth:`warmup` had pinned (:meth:`_warm_exclusion`), and the
        write programs are run on the live table, writing nothing:
        :func:`_append_runs` at every padded size up to ``max_rows`` ids
        a publish, :func:`_move_run` at every history pad.  A generation
        without histories: nothing to do."""
        with self._publish_lock, self._table_lock:
            m = self._model
            if m is None:
                raise NoModelPublished("publish(U, V) before warmup")
            if m.seen is not None:
                self._warm_histories(m, max_rows)

    def _warm_histories(self, m, max_rows):
        """:meth:`warmup_histories`, under its locks.  On a mesh the
        histories stay as published (nothing grows there yet:
        ``publish_update`` refuses ``seen_appended``), and this pins and
        runs the programs that exclude — given the segment, where the
        index has one — and nothing else."""
        with phase("start.warmup_histories"):
            if self.mesh is None:
                seen = m.seen
                if seen.room is None:
                    seen = self._lay_out(seen, int(m.U.shape[0]))
                runs, indices, room = seen.runs, seen.indices, seen.room
                with phase("start.first_run"):
                    for pad in pads_up_to(max_rows):
                        # the plan by itself, and as the last rows of a
                        # publish's one array
                        ride = _Ride()
                        ride.plan = self._no_append(seen, pad)
                        for plan in (put(ride.plan),
                                     self._send(ride, seen)):
                            *runs, indices = _append_runs(*runs, indices,
                                                          plan)
                    for width in seen.pads:     # each run onto itself
                        indices = _move_run(indices, 0, 0, width=width)
                    indices.block_until_ready()
                m = self._model = _Published(
                    m.seq, m.U, m.n_users, m.V, m.valid, m.index,
                    m.n_items, _Seen(tuple(runs), indices, seen.lengths,
                                     seen.pads, room))
            self._pinned.clear()
            for B in self.batcher.buckets:
                self._warm_exclusion(m, B)

    @property
    def holds_histories(self):
        """Whether the live generation was published with its users'
        histories (``publish(user_seen=...)``)."""
        m = self._model
        return m is not None and m.seen is not None

    @staticmethod
    def _emit_shortlist(bucket, index, **extra):
        """One ``serving_shortlist`` event for a scoring program that
        was just compiled: the selection its shortlist runs, from the
        same ``ops.topk.shortlist_plan`` the program was traced with
        (``bucket`` rows: they decide ``blocks_layout``)."""
        obs.emit("serving_shortlist", bucket=bucket,
                 path=("int8_sharded" if isinstance(index, ShardedInt8Index)
                       else "int8"),
                 **index.shortlist_plan(rows=bucket)._asdict(), **extra)

    def _run_pinned(self, key, fn, args, statics):
        """Dispatch through the pinned executable when one is live
        for ``key``; a pin invalidated by a shape-changing publish is
        dropped and the ordinary jit call (compiled once, cached; after
        a LOADED pin also traced and lowered once, which no warm-up of
        this process did) takes over until the next :meth:`warmup`.
        Either takes the staged batch as :meth:`_dispatch` hands it: the
        host array without a mesh, the placed one with."""
        c = self._pinned.get(key)
        if c is not None:
            try:
                return c(*args)
            except Exception:
                self._pinned.pop(key, None)
        return fn(*args, **statics)

    # -- request path -------------------------------------------------
    def submit(self, payload, k=None, deadline_s=None, exclude=None):
        """Admit one request; returns its ticket (see ``Ticket.result``).

        ``payload``: int user index into the published user table, or a
        rank-length f32 vector (fold-in row).  ``exclude``: catalog ids
        the request is not to be answered with, at most ``MAX_EXCLUDE``
        (64) of them: a request by id is answered without the ids of its
        user's published history (``publish(user_seen=...)``) AND
        without these, a request by vector without these
        (``ops.topk.excluded_mask`` states the rule).  Raises
        ``Overloaded`` when shedding, ``NoModelPublished`` before the
        first publish, ``ValueError`` on a malformed payload or list (a
        longer one is refused, not cut).
        """
        t_enter = time.perf_counter()
        m = self._model
        if m is None:
            raise NoModelPublished("publish(U, V) before submitting")
        if k is not None and not 0 < k <= self.k:
            raise ValueError(f"per-request k={k} must be in 1..{self.k} "
                             "(the engine's compiled top-k width)")
        if isinstance(payload, (int, np.integer)):
            if not 0 <= payload < m.n_users:
                raise ValueError(f"user index {payload} outside the "
                                 f"published table [0, {m.n_users})")
        else:
            payload = np.asarray(payload, dtype=np.float32)
            if payload.shape != (m.rank,):
                raise ValueError(
                    f"fold-in payload shape {payload.shape} != "
                    f"({m.rank},) (the published rank)")
        if exclude is not None:
            exclude = self._checked_exclude(m, exclude)
        # root span BEFORE enqueue: the consumer thread may dequeue the
        # ticket the instant submit releases the lock, so the context
        # must already ride it (None when tracing is disarmed — the
        # whole chain no-ops off that None)
        ctx = tracing.start_trace(
            "serve.admit", tenant=self.tenant,
            seconds=time.perf_counter() - t_enter)
        try:
            t = self.batcher.submit(payload, k=k, deadline_s=deadline_s,
                                    trace=ctx, exclude=exclude)
        except Overloaded:
            # a shed never queues: its trace is the admission span plus
            # a queue hop with status="shed" (refusals are traced)
            tracing.record_span(ctx, "serve.queue", status="shed",
                                seconds=0.0)
            self.flight.record(
                "shed", {"admission": time.perf_counter() - t_enter},
                trace_id=(ctx.trace_id if ctx is not None else None))
            self.flight.dump("shed")
            self.batch_flight.dump("shed")
            raise
        t.t_admit = time.perf_counter() - t_enter
        obs.counter("serving.requests", **self._labels)
        return t

    def _checked_exclude(self, m, exclude):
        """A request's own list as it will ride the staging layout: the
        distinct ids, int32, or the error ``submit`` raises."""
        ids = np.unique(np.asarray(exclude).ravel())
        if ids.size and (ids.dtype.kind not in "iu" or ids[0] < 0
                         or ids[-1] >= m.n_items):
            raise ValueError(f"exclude holds ids outside the published "
                             f"catalog [0, {m.n_items})")
        if ids.size > MAX_EXCLUDE:
            raise ValueError(
                f"exclude holds {ids.size} ids, a request may bring "
                f"{MAX_EXCLUDE}: a longer list belongs to the user's "
                "history (publish(user_seen=...))")
        return ids.astype(np.int32)

    def recommend(self, payload, k=None, deadline_s=None, timeout=None,
                  exclude=None):
        """Submit + block: returns ``(scores, indices)`` for one request."""
        return self.submit(payload, k=k, deadline_s=deadline_s,
                           exclude=exclude).result(timeout)

    # -- engine loop --------------------------------------------------
    def start(self):
        if self._thread is not None:
            raise RuntimeError("engine already started")
        self._stopping.clear()
        self._thread = threading.Thread(
            target=self._run, name="tpu-als-serving", daemon=True)
        self._completer = threading.Thread(
            target=self._run_completions, name="tpu-als-serving-readback",
            daemon=True)
        self._completer.start()
        self._thread.start()
        compiles.traffic(True)
        return self

    def stop(self, drain_timeout_s=5.0):
        """Close admission, answer what is queued and what is in flight,
        join both threads, all inside ``drain_timeout_s``."""
        self.batcher.close()
        self._stopping.set()
        deadline = time.monotonic() + drain_timeout_s
        for thread in (self._thread, self._completer):
            if thread is not None:
                thread.join(max(0.0, deadline - time.monotonic()))
        if self._thread is not None:
            compiles.traffic(False)
        self._thread = self._completer = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def _run(self):
        """The engine thread: a slot → ``next_batch`` → stage →
        ``_dispatch`` → hand over → a slot.  It never waits for an
        answer, and with a slot in hand it never waits for company:
        what is queued is popped at once.  With ``MAX_IN_FLIGHT``
        batches handed over and not yet completed it waits for the
        older to complete BEFORE it pops the next, so what arrives
        meanwhile rides that next batch."""
        try:
            while True:
                handoff_wait = 0.0
                if not self._slots.acquire(blocking=False):
                    t_full = time.perf_counter()
                    with TraceAnnotation("pipe.slot_wait",
                                         seq=self._batch_seq + 1):
                        self._slots.acquire()
                    handoff_wait = time.perf_counter() - t_full
                # the slot is the signal that the device can take a
                # batch: nothing is held back for company
                batch = self.batcher.next_batch(timeout=0.1,
                                                coalesce=False)
                if batch is None:
                    self._slots.release()
                    if self._stopping.is_set():
                        return
                    continue
                seq = self._batch_seq = self._batch_seq + 1
                try:
                    with TraceAnnotation("serve.batch", seq=seq) as whole:
                        flown = self._begin(batch, seq, whole, handoff_wait)
                except BaseException as e:  # noqa: BLE001 — tickets must resolve
                    self._fail(batch, e)
                    flown = None
                if flown is None:
                    self._slots.release()   # nothing went to the device
                else:
                    self._handed += 1
                    self._handoff.put(flown)
        finally:
            self._handoff.put(None)         # after the last batch handed over

    def _run_completions(self):
        """The completion thread: takes what the engine thread handed
        over, in dispatch order, reads each response back and completes
        its tickets.  It touches neither ``_table_lock`` nor a table."""
        while True:
            t_idle = time.perf_counter()
            flown = self._handoff.get()
            if flown is None:
                return
            t_taken = time.perf_counter()
            try:
                self._finish(flown, t_taken, t_taken - t_idle)
            except BaseException as e:  # noqa: BLE001 — tickets must resolve
                self._fail(flown.live, e)
            finally:
                self._completed += 1
                self._slots.release()

    def _fail(self, batch, e):
        """Fail the tickets of ``batch`` that no one has answered with
        ``e``, each with its trace hop and its flight record; the loop
        that calls this goes on to the next batch."""
        for t in batch:
            if not t.done():
                t.fail(e)
                if t.trace is not None:
                    t.trace = tracing.record_span(
                        t.trace, "serve.score", status="failed",
                        error=type(e).__name__)
                self.flight.record(
                    "failed",
                    {"admission": t.t_admit,
                     "queue_wait": (t.t_dequeue - t.t_submit
                                    if t.t_dequeue else None)},
                    error=type(e).__name__,
                    trace_id=(t.trace.trace_id
                              if t.trace is not None else None))
        if not isinstance(e, faults.InjectedFault):
            obs.emit("warning", what="serving.batch",
                     reason=f"{type(e).__name__}: {e}")

    def serve_batch(self, batch):
        """Score one dequeued micro-batch and complete its tickets, both
        halves on the caller's thread: :meth:`_begin`, then
        :meth:`_finish`, the two the engine's threads run.

        Public so tests and synchronous callers can drive the engine
        without the background threads.  The phases are disjoint spans
        on the profiler's timeline (``obs.schema.SERVE_BATCH_SPAN_KEYS``;
        ``serve.batch`` carries ``seq``, ``bucket``, ``rows``, ``path``),
        all four inside ``serve.batch`` here, with the started engine's
        stats and dispatch's two child spans (no ``pipe.slot_wait``: the
        caller's thread takes no slot), and their durations go into one
        ``batch_flight`` record.
        """
        seq = self._batch_seq = self._batch_seq + 1
        with TraceAnnotation("serve.batch", seq=seq) as whole:
            flown = self._begin(batch, seq, whole)
            if flown is not None:
                self._finish(flown, flown.t_flown)

    def _begin(self, batch, seq, whole, handoff_wait=0.0):
        """A batch's first half, stage + dispatch, inside the caller's
        ``serve.batch`` span ``whole``: the :class:`_Flown` batch to
        :meth:`_finish`, or ``None`` where every ticket had expired."""
        t_stage = time.perf_counter()
        # the live generation is read AFTER the dequeue and its user
        # table goes to the scoring call under one hold of the lock:
        # a row write donates that table, and may only between two
        # batches' dispatches (what is dispatched reads it whole).
        # The wait for the lock is inside the stage span, as it is
        # inside the record's ``stage``, and alone in ``lock_wait``
        # (a ``with`` cannot span the two phases from inside the
        # first; an ExitStack did, for 25 us a batch on the chip's
        # host: PERF.md section 6, PR 31)
        held = False
        try:
            with TraceAnnotation("serve.batch.stage", seq=seq) as span:
                mark = cpu_mark()
                held = self._table_lock.acquire()
                t_locked = time.perf_counter()
                # what the record keeps as ``lock_wait``, on the span
                span.set_metadata(
                    lock_wait_us=int(1e6 * (t_locked - t_stage)))
                live = self._expire(batch, t_locked)
                if not live:
                    return None
                # raise-mode -> the caller fails all
                mode = faults.check("serving.score")
                m = self._model
                n = len(live)
                for t in live:      # the generation that answers them
                    t.seq = m.seq
                B = bucket_for(n, self.batcher.buckets)
                # a batch excludes where its generation holds histories
                # or one of its requests brings a list: the wide layout,
                # and the history pad that holds its longest
                seen, pad, histories = m.seen, None, None
                if seen is None and any(t.exclude is not None
                                        for t in live):
                    seen = self._without_history(m)
                if seen is not None:
                    # read under the lock: a publish writes the lengths
                    histories = seen.lengths_of(live)
                    pad = seen.pad_for(histories)
                    span.set_metadata(excluded=pad)
                st = self._staged(live, B, m.rank, wide=seen is not None)
                cpu_stage = stamp_cpu(span, mark)
            t_dispatch = time.perf_counter()
            with TraceAnnotation("serve.batch.dispatch", seq=seq) as span:
                mark = cpu_mark()
                # each counter has one writer: the difference needs no lock
                in_flight = self._handed - self._completed
                (resp_dev, path, fell_back, upload_how, t_launch,
                 t_launched) = self._dispatch(m, st, B, mode, seq, seen,
                                              pad)
                if fell_back:
                    obs.counter("serving.fallback_exact", n,
                                **self._labels)
                whole.set_metadata(bucket=B, rows=n, path=path)
                cpu_dispatch = stamp_cpu(span, mark)
        finally:
            if held:
                self._table_lock.release()
        # the registry's lock is taken with the table's given back: a
        # publisher waiting for the table waits for no histogram
        obs.histogram("serving.batch_rows", n, **self._labels)
        obs.counter("serving.batch_overlap", in_flight=in_flight,
                    **self._labels)
        if seen is not None:
            self._count_excluded(live, histories, B)
        # the batcher's account of this dequeue is taken now: by the
        # time the batch completes the engine thread has dequeued again
        return _Flown(seq, live, resp_dev, B, n, path, fell_back,
                      t_stage, t_locked, t_dispatch, self.batcher.last_wait,
                      self.batcher.closed_by, self.batcher.head_wait,
                      in_flight, handoff_wait, upload_how, t_launch,
                      t_launched,
                      cpu_stage, cpu_dispatch, time.perf_counter())

    def _finish(self, flown, t_readback, idle_s=0.0):
        """A batch's second half, readback + complete, from
        ``t_readback`` (when the caller took the batch up; ``idle_s``:
        how long it had waited for one).  Reads nothing but ``flown``."""
        seq, live, path = flown.seq, flown.live, flown.path
        with TraceAnnotation("serve.batch.readback", seq=seq) as span:
            mark = cpu_mark()
            # ONE bulk device→host transfer; tickets complete with
            # numpy views sliced from this buffer (which snapshots an
            # immutable device array — the views stay valid after
            # slot reuse)
            resp = np.asarray(flown.resp_dev)
            kw = resp.shape[1] // 2
            scores = resp[:, :kw].view(np.float32)  # same-itemsize view
            indices = resp[:, kw:]
            cpu_readback = stamp_cpu(span, mark)
        t_complete = time.perf_counter()
        score_s = t_complete - flown.t_dispatch
        with TraceAnnotation("serve.batch.complete", seq=seq) as span:
            mark = cpu_mark()
            obs.histogram("serving.score_seconds", score_s, path=path,
                          **self._labels)
            e2es = []
            for j, t in enumerate(live):
                kk = min(t.k or self.k, kw)
                t.complete((scores[j, :kk], indices[j, :kk]))
                e2es.append(t.t_done - t.t_submit)
                if t.trace is not None:
                    t.trace = tracing.record_span(
                        t.trace, "serve.score", seconds=score_s,
                        path=path, batch=seq)
                self.flight.record(
                    "ok",
                    {"admission": t.t_admit,
                     "queue_wait": (t.t_dequeue - t.t_submit
                                    if t.t_dequeue else None),
                     "score": score_s,
                     "respond": t.t_done - t_complete},
                    e2e_seconds=e2es[-1], path=path, batch=seq,
                    trace_id=(t.trace.trace_id
                              if t.trace is not None else None))
            obs.histogram_many("serving.e2e_seconds", e2es,
                               **self._labels)
            trigger = None
            if self.slo_s is not None and max(e2es) > self.slo_s:
                trigger = "slo_breach"
            elif flown.fell_back:
                trigger = "degraded"
            if trigger:
                self.flight.dump(trigger)
            cpu_complete = stamp_cpu(span, mark)
        t_end = time.perf_counter()
        idle_queue_s, waiting, coalesce_s = flown.last_wait
        # ``serve.batch`` is the batch's whole life, stage to the last
        # ticket's bookkeeping: with the halves on two threads it is
        # longer than its four phases by the wait for the completion
        # thread (the hop, or the batch before still being completed)
        self.batch_flight.record(
            "ok",
            dict(zip(SERVE_BATCH_SPAN_KEYS,
                     (idle_queue_s, coalesce_s, t_end - flown.t_stage,
                      flown.t_dispatch - flown.t_stage,
                      flown.t_flown - flown.t_dispatch,
                      t_complete - t_readback, t_end - t_complete))),
            path=path, batch=seq, t0=flown.t_stage, bucket=flown.bucket,
            rows=flown.rows, waiting=waiting, closed_by=flown.closed_by,
            head_wait=flown.head_wait,
            lock_wait=flown.t_locked - flown.t_stage,
            in_flight=flown.in_flight, handoff_wait=flown.handoff_wait,
            completion_idle=idle_s,
            upload=flown.t_launch - flown.t_dispatch,
            upload_how=flown.upload_how,
            launch=flown.t_launched - flown.t_launch,
            cpu={"stage": flown.cpu_stage, "dispatch": flown.cpu_dispatch,
                 "readback": cpu_readback, "complete": cpu_complete})
        if trigger:
            self.batch_flight.dump(trigger)

    def _expire(self, batch, now):
        """The tickets of ``batch`` still worth scoring; the others are
        failed with ``DeadlineExceeded``, counted and recorded."""
        live = []
        for t in batch:
            if t.deadline is None or now <= t.deadline:
                live.append(t)
                continue
            obs.counter("serving.expired", **self._labels)
            if t.trace is not None:
                t.trace = tracing.record_span(
                    t.trace, "serve.expired", status="expired",
                    seconds=now - t.t_submit)
            self.flight.record(
                "expired",
                {"admission": t.t_admit,
                 "queue_wait": (t.t_dequeue - t.t_submit
                                if t.t_dequeue else None)},
                e2e_seconds=now - t.t_submit,
                trace_id=(t.trace.trace_id
                          if t.trace is not None else None))
            t.fail(DeadlineExceeded(
                "deadline passed while queued "
                f"({now - t.t_submit:.4f}s since submit)"))
        return live

    def _count_excluded(self, live, histories, B):
        """The counters of one batch that excludes: ids taken out a
        request, by where they came from (``histories``: the lengths of
        its by-id requests' histories, ``_Seen.lengths_of``), and what
        the requests' own lists added to the batch's one upload."""
        if histories is not None:
            obs.histogram_many("serving.excluded_ids", histories,
                               source="history", **self._labels)
        obs.histogram_many(
            "serving.excluded_ids",
            [0 if t.exclude is None else len(t.exclude) for t in live],
            source="request", **self._labels)
        obs.counter("serving.exclusion_upload_bytes", 4 * B * MAX_EXCLUDE,
                    **self._labels)

    @staticmethod
    def _staged(live, B, rank, wide=False):
        """Single-upload staging: one int32 ``[B, rank+2]`` array
        carries the rows' f32 bits, ids and the row-mask — the payload
        is the only host→device transfer a batch makes.  A NEW array
        every batch, zeroed (pad slots score user 0, unread): the upload
        may read the host's buffer after the call has returned (on
        the CPU the device array IS that buffer), and the next batch of
        this bucket is staged while this one is still in flight.
        ``wide``: a batch that excludes carries ``MAX_EXCLUDE`` more
        columns, each request's own list of ids padded with
        ``NOT_AN_ID`` (a pad slot is marked a request by vector: it
        takes no history)."""
        st = np.zeros((B, rank + 2 + (MAX_EXCLUDE if wide else 0)),
                      dtype=np.int32)
        rows = st[:, :rank].view(np.float32)    # same-itemsize view
        if wide:
            st[:, rank + 2:] = NOT_AN_ID
            st[len(live):, rank + 1] = 1
        for j, t in enumerate(live):
            if isinstance(t.payload, (int, np.integer)):
                st[j, rank] = t.payload
            else:
                rows[j] = t.payload
                st[j, rank + 1] = 1
            if wide and t.exclude is not None:
                st[j, rank + 2:rank + 2 + len(t.exclude)] = t.exclude
        return st

    def _dispatch(self, m, st, B, mode, seq, seen=None, pad=None):
        """Call the scorer the live model selects on the staged batch.
        Without a mesh the batch rides the call as the host array it is:
        the program's own argument handling places it on the default
        device, ONE trip into the runtime a batch, no upload call of its
        own (``how`` = ``call``).  With a mesh it is placed first, by
        one transfer to the mesh's first device (:meth:`_place_one`,
        ``how`` = ``put_one``), and the call takes an array that is on
        its devices already: a host array would be placed a shard at a
        time by Python, inside the call.
        Two child spans of the caller's ``serve.batch.dispatch``:
        ``upload`` around what the host does for the upload apart from
        the call — the mesh's one placement, the scorer chosen and its
        argument tuple built around the batch — and ``launch`` around
        the call (without a mesh the transfer inside it).  Returns
        ``(packed response on the device, path, fell back to exact, how
        the batch was uploaded, when the upload span had closed, when
        the call had returned)`` as soon as the call returns.  ``seen``, ``pad``: the batch
        excludes — the program of its history pad; on the exact fallback
        the longest pad's, the one :meth:`warmup` pins."""
        index = m.index
        how = "call" if self.mesh is None else "put_one"
        with TraceAnnotation("serve.batch.dispatch.upload", seq=seq,
                             bytes=st.nbytes, how=how):
            if self.mesh is not None:
                st = self._place_one(st)
            use_index = (index is not None and index.seq == m.seq
                         and mode != "corrupt")
            fell_back = index is not None and not use_index
            if not use_index:
                path, pin = "exact", "exact"
                if seen is not None:
                    pad = seen.pads[-1]
                fn, args, statics = self._exact_call(m, st, seen, pad)
            else:
                path = "int8" if self.mesh is None else "int8_sharded"
                pin = self._int8_pin(index)
                fn, args, statics = self._int8_call(m, index, st, seen,
                                                    pad)
            key = (B, pin) if seen is None else (B, pin, pad)
        t_launch = time.perf_counter()
        with TraceAnnotation("serve.batch.dispatch.launch",
                             seq=seq) as span:
            if self.mesh is not None:
                plan = self._mesh_plan(m, index if use_index else None, B,
                                       pad)
                obs.counter("serving.mesh_exchange_bytes",
                            plan["exchange_bytes"], **self._labels)
                if seen is not None:
                    obs.counter("serving.mesh_history_bytes",
                                plan["history_bytes"], **self._labels)
            resp_dev = self._run_pinned(key, fn, args, statics)
            # a pin that failed was dropped inside the call
            span.set_metadata(program="jit_" + fn.__name__, pin=pin,
                              pinned=int(key in self._pinned))
        return resp_dev, path, fell_back, how, t_launch, time.perf_counter()
