"""Background update loop: rating events → fold-in → incremental publish.

One thread owns the whole arrival-to-servable path so its latency is a
single measurable quantity:

1. **Admit.**  ``submit(user, item, rating)`` appends to a bounded
   queue; at capacity it sheds with the serving batcher's own typed
   :class:`~tpu_als.serving.batcher.Overloaded` (``live.shed`` counts
   it) — producers see the identical backpressure contract the request
   path uses.
2. **Accumulate.**  The loop gathers up to ``max_batch`` events or
   until the oldest has waited ``max_wait_ms`` (planner-resolved
   cadence, ``plan.resolve_live_cadence``), whichever first — the
   fold-in kernel's fixed cost amortizes over the batch.
3. **Quarantine.**  Poisoned events (non-finite or out-of-range
   ratings, ``core.ratings.invalid_rating_mask``) are dropped before
   they can reach the factors, through the SAME obs contract streaming
   ingest uses: one ``ingest_quarantined`` event + the
   ``ingest.quarantined_rows`` counter.
4. **Fold.**  ``FoldInServer.update`` solves the touched user rows;
   with ``fold_items`` ``update_items`` then solves the touched item
   rows, USERS FIRST: an item's fold regresses on the user factors as
   the same batch's user fold left them (a new user's rating counts at
   once), a user's on the catalog as the batch before left it.  A
   rating of an item without a factor is kept in its user's history and
   enters that user's next fold after the item has one
   (``stream/microbatch.py``); no admitted rating is dropped from a
   history.
5. **Publish.**  ``ServingEngine.publish_update`` swaps the new
   generation in atomically (on an engine that holds its users'
   histories with the ids the batch's ratings add to them,
   ``FoldInServer.last_appended``: what was just rated leaves that
   user's answers with the publish that folds it in; with
   ``fold_items`` the catalog rows go in the same publish, and an id
   whose item has no row yet joins with the publish that gives it one:
   ``_joining``) — retag for user-only batches, an
   O(touched) delta re-quantization for item batches — never a full
   O(catalog) rebuild while the live index is healthy.  The touched
   user rows are written into the device's table in place (the table
   is donated to the write: no copy of it, on the host or on the
   device); the generation before loses its user table to the new one.
   The touched and appended item rows go the same way: uploaded alone,
   into the index's delta segment and the engine's own catalog, the
   segment folded into the base arrays in place when it fills.

The thread's cycle is on the profiler's timeline as ``TraceAnnotation``
spans, always on, the write path's counterpart of the engine thread's
(``obs.schema.LIVE_BATCH_SPAN_KEYS``): ``live.idle``,
``live.batch.coalesce``, and ``live.batch`` (stats ``seq``, ``events``,
``users``, ``new_users``, ``width``, ``mode``, ``placements``) around
``live.batch.foldin`` and ``live.batch.publish``.  With ``fold_items``
also (``obs.schema.LIVE_ITEM_SPAN_KEYS``) ``live.batch.foldin.users``
and ``live.batch.foldin.items`` inside the fold,
``live.batch.publish.compact`` (the engine's) inside a publish that
compacts, and the stats ``items``, ``new_items``, ``segment_rows``.
On an engine that holds its users' histories also
(``obs.schema.LIVE_HISTORY_SPAN_KEYS``) ``live.batch.publish.history``
(the engine's: the appended ids planned and uploaded; stats ``ids``,
``users``, ``relocated``) inside every publish.
Every fold writes ``live.batch.foldin.readback`` around its program's
call and the blocking read of its rows
(``obs.schema.LIVE_FOLDIN_SPAN_KEYS``, stream/microbatch.py), and
``live.batch``, ``.foldin`` and ``.publish`` carry ``cpu_us`` beside
``wall_us`` while a profiler session records: the thread's own CPU time
inside the span (``serving.engine.cpu_mark`` / ``stamp_cpu``; the batch
record's ``foldin_cpu``, ``publish_cpu``).  Since ISSUE 54 the batch is
tiled by phase (``obs.schema.LIVE_PHASE_SPAN_KEYS``, each a
``serving.engine.Stamped`` span with the same two stats, opened a batch
and never an event): ``live.batch.prepare`` and ``live.batch.record``
around the two phases here, ``live.batch.publish.join`` ahead of the
engine's call, the fold's phases in stream/microbatch.py and the
publish's in serving/engine.py.  A trace reader keys on those names
(benchmark/live_phase_spans.py).

**A refit lands** (:meth:`LiveUpdater.land`).  A deployment refits —
``ALS.fit`` on a schedule — and hands the new model to the RUNNING
updater: ``snapshot = updater.mark()`` when the refit's data is cut (an
admission count: the point in the event stream the data ends at), the
fit, ``updater.land(model, snapshot)`` when it is done.  The landing
runs on the caller's thread; the loop stops BETWEEN two batches for it
(events are admitted meanwhile and wait in the queue), the fold-in
server takes the refit as its base and folds the catch-up — every
entity with an event admitted after the snapshot, users first, then
items, over all its kept ratings (``FoldInServer.land``) — and the
engine installs refit rows, catch-up rows and a fresh index as ONE
generation, at the live generation's capacities, and releases the one
before (``ServingEngine.publish(placed=, release=True)``).  On the
profiler's timeline ``live.landing`` (stats ``seq``, ``users``,
``items``, ``catchup_events``, ``catchup_users``, ``catchup_items``)
(opened once the loop stands: a ``live.`` span never overlaps a
``live.batch``) around ``.pause`` (stat ``waited_us``: the wait for the
loop), the server's ``.tables`` / ``.place`` / ``.catchup``
(``.catchup.call`` a fold), the engine's ``.users`` / ``.catalog`` /
``.index`` / ``.lock_wait`` / ``.swap`` / ``.release`` and ``.record``
(``obs.schema.LIVE_LANDING_SPAN_KEYS``); the same seconds, the bytes
and the programs compiled (0 after ``start`` with ``refits=True``) in
``landings``, one record a landing.

Freshness (``live.freshness_seconds``) is per EVENT, arrival →
publish-visible, so the histogram's p99 is exactly the SLO quantity:
how stale can a rating be before it influences recommendations.  A
breach emits ``live_freshness_breach`` and dumps the updater's flight
ring (queue_wait/quarantine/foldin/publish spans per batch), so the
trail says WHERE the budget went — queued behind a slow fold-in, or a
compaction-heavy publish.
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from tpu_als import obs
from tpu_als.core.foldin import placements
from tpu_als.core.ratings import invalid_rating_mask
from tpu_als.obs import compiles, tracing
from tpu_als.obs.phases import (
    device_bytes_in_use,
    device_peak_bytes,
    phase,
    placed_bytes,
)
from tpu_als.obs.trace import FlightRecorder
from tpu_als.resilience import faults
from tpu_als.serving.batcher import Overloaded
from tpu_als.serving.engine import Stamped, cpu_mark, stamp_cpu

# the per-batch span breakdown the updater's flight ring carries
# (source of truth in the stdlib-only schema module, where the jax-free
# static check pins it against the record's structural field names)
LIVE_SPAN_KEYS = obs.schema.LIVE_SPAN_KEYS


class LiveUpdater:
    """Continuous fold-in → publish over a :class:`FoldInServer` and a
    :class:`ServingEngine`.

    ``foldin`` wraps the model whose factors are updated; every publish
    pushes that model's current U/V into ``engine``.  ``fold_items``
    additionally solves the ITEM side of each batch (new/updated items
    become recommendable; their rows ride the index's delta segment).
    ``slo_s`` is the arrival → servable objective; None disables the
    breach trigger but freshness is always measured.

    ``tenant`` (default: the engine's own tenant) labels every live.*
    metric this loop writes and tags its events/flight records, so a
    freshness breach in a multi-tenant process names its tenant from
    the obs trail alone (docs/tenancy.md).

    ``refits``: refits will LAND on this updater (:meth:`land`):
    ``start`` then also runs the programs a landing runs
    (``ServingEngine.warmup_landing``), so that none compiles under
    traffic.  A landing on an updater started without it works and
    compiles there, once (the compile ledger warns).
    """

    def __init__(self, engine, foldin, *, max_queue=4096,
                 max_batch=None, max_wait_ms=None, slo_s=None,
                 fold_items=False, flight_capacity=64, tenant=None,
                 refits=False):
        from tpu_als import plan as _plan

        cad = _plan.resolve_live_cadence()
        self.engine = engine
        self.foldin = foldin
        if tenant is None:
            tenant = getattr(engine, "tenant", None)
        self.tenant = str(tenant) if tenant is not None else None
        self._labels = {"tenant": self.tenant} if self.tenant else {}
        self.max_queue = int(max_queue)
        self.max_batch = int(max_batch if max_batch is not None
                             else cad["max_batch"])
        self.max_wait_s = float(max_wait_ms if max_wait_ms is not None
                                else cad["max_wait_ms"]) / 1e3
        self.slo_s = float(slo_s) if slo_s is not None else None
        self.fold_items = bool(fold_items)
        self.refits = bool(refits)
        self.flight = FlightRecorder(flight_capacity,
                                     span_keys=LIVE_SPAN_KEYS,
                                     labels=self._labels)
        self._queue = []
        # (user ids, item ids) rated but not yet in the engine's
        # histories: a side of the pair has no row yet (_joining)
        self._not_joined = (np.empty(0, np.int64), np.empty(0, np.int64))
        self._batch_seq = 0
        self._cond = threading.Condition()
        self._closed = False
        self._thread = None
        # events admitted (the next one's place in the stream) and events
        # the loop has popped: a batch's events are [_taken, _taken + n)
        self._admitted = 0
        self._taken = 0
        # the snapshots handed out and not landed yet (:meth:`mark`), and
        # while there is one, a folded batch's (places in the stream,
        # users, items)
        self._marks = []
        self._log = []
        # None | "wanted" (a landing waits for the loop to stand between
        # two batches) | "granted" (it stands) | "refused" (it ended)
        self._lander = None
        # one record a landing (:meth:`land`), oldest first
        self.landings = []

    # -- producer side ------------------------------------------------
    def submit(self, user, item, rating):
        """Admit one rating event (original user/item ids).  Raises
        :class:`Overloaded` when the queue is at capacity — the same
        typed shed the serving batcher raises, so producers share one
        backpressure contract.  Each admitted event is stamped with a
        root causal-trace context (``obs.tracing``; None disarmed) the
        loop carries through coalescing -> fold-in -> publish ->
        visibility, so a freshness breach is explainable per event."""
        t_arrival = time.perf_counter()
        with self._cond:
            if self._closed:
                raise RuntimeError("LiveUpdater is stopped")
            if len(self._queue) >= self.max_queue:
                obs.counter("live.shed", **self._labels)
                tracing.start_trace("live.admit", tenant=self.tenant,
                                    status="shed")
                raise Overloaded(
                    f"live update queue at capacity ({self.max_queue})")
            ctx = tracing.start_trace("live.admit", tenant=self.tenant)
            self._queue.append((user, item, float(rating), t_arrival,
                                ctx))
            self._admitted += 1
            self._cond.notify()

    @property
    def queue_depth(self):
        with self._cond:
            return len(self._queue)

    # -- lifecycle ----------------------------------------------------
    def start(self):
        """Have the engine run the row writes this updater's publishes
        will make (up to ``max_batch`` users a publish; with
        ``fold_items`` as many items, into a catalog with spare rows and
        a segment of fixed size; on an engine that holds its users'
        histories as many ids appended to them, into a table laid out to
        grow: ``ServingEngine.warmup_live`` / ``warmup_histories``), so
        that none compiles or loads under traffic, then start the
        loop."""
        if self._thread is not None:
            raise RuntimeError("updater already started")
        with phase("start.updater"):
            self.engine.warmup_publish(self.max_batch)
            if self.fold_items:
                # (the histories too, where the engine holds them)
                self.engine.warmup_live(max_rows=self.max_batch)
            elif self._histories:
                self.engine.warmup_histories(max_rows=self.max_batch)
            if self.refits:
                self.engine.warmup_landing()
        self._thread = threading.Thread(
            target=self._run, name="tpu-als-live", daemon=True)
        self._thread.start()
        return self

    @property
    def _histories(self):
        """Whether the engine answers without what its users have rated
        (``publish(user_seen=...)``): every publish then hands it the
        ids its ratings add to their users' histories."""
        return getattr(self.engine, "holds_histories", False)

    def _joining(self):
        """``(user rows, catalog ids)`` this publish adds to its users'
        histories on the engine: the pairs the last user fold added
        (``FoldInServer.last_appended``) and those kept from earlier
        batches, both sides of which have a row NOW, after the batch's
        folds — so an id joins its user's history in the publish that
        first makes its item servable (a new item folded in this batch:
        this one), whether or not the user's row could use the rating
        yet.  A pair one side of which still has no row (a rating with
        both sides unknown) is kept for the publish that gives it one."""
        m = self.foldin.model
        who, what = (np.concatenate([kept, new]) for kept, new in zip(
            self._not_joined, self.foldin.last_appended))
        rows, ids = m._user_map.to_dense(who), m._item_map.to_dense(what)
        ok = (rows >= 0) & (ids >= 0)
        self._not_joined = (who[~ok], what[~ok])
        return rows[ok], ids[ok]

    def stop(self, drain_timeout_s=10.0):
        """Close admission, drain the queue, join the loop."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(drain_timeout_s)
            self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- a refit lands ------------------------------------------------
    def mark(self):
        """The point in the event stream a refit's data ends at: how
        many events have been admitted so far.  Take it when the refit's
        data is cut — every event admitted before it is the refit's to
        know, every one after it is folded onto the refit again when it
        lands — and hand it to :meth:`land` with the fitted model.  From
        now until that landing the loop keeps who each batch touched
        (O(events), no factors)."""
        with self._cond:
            self._marks.append(self._admitted)
            return self._admitted

    def land(self, refit, snapshot):
        """A refit LANDS on the running updater: ``refit`` (an
        ``ALSModel``: a whole new fit's ``(U', V')`` with their ids)
        becomes the model that serves and that the folds regress on,
        with the events admitted since ``snapshot`` (:meth:`mark`)
        folded onto it again — ONE generation, swapped in while the
        engine answers.

        On the caller's thread, never the engine's.  The loop stops
        between two batches (events are admitted meanwhile; none is
        shed unless ``max_queue`` of them arrive during the landing) and
        goes on afterwards, folding against the landed tables and
        nothing else:

        1. the fold-in server takes the refit's rows into both host
           tables, releases its two tables on the device and places them
           anew, and folds the catch-up — every entity a batch touched
           at or after ``snapshot``, and every entity new since (the
           refit has no row for it), users first, then items, each over
           ALL its kept ratings (``FoldInServer.land``).  An entity with
           no event after the snapshot keeps the refit's row, bit for
           bit;
        2. the engine builds the generation beside the live one — user
           table and catalog as copies, made on the device, of the
           server's tables as the catch-up left them, a fresh index with
           an empty segment — at the live capacities, installs it by one
           swap and deletes the generation before
           (``ServingEngine.publish(placed=, release=True)``).

        At 1.7 M users and 1.5 M items of rank 256 the device holds
        8.1 GB between landings and 13.4 GB at the swap.  Not yet: an
        engine that holds its users' histories, implicit feedback, a
        mesh (``NotImplementedError``; ROADMAP R11, R12).  Returns the
        landing's record (also appended to ``landings``): ``seq``, the
        sizes, ``seconds`` by step, ``placed_bytes`` / ``copied_bytes``,
        ``peak_bytes``, ``programs`` (compiled meanwhile: 0 after a
        ``start`` with ``refits``) and ``catchup`` — ``{"users": (ids,
        rows), "items": (ids, rows)}``, the rows the catch-up folded."""
        if self._histories or getattr(self.engine, "mesh", None) is not None:
            raise NotImplementedError(
                "a landing on an engine that holds its users' histories "
                "(the grown layout laid out anew) or on a mesh: ROADMAP "
                "R11, R12")
        # the wait for the loop lies OUTSIDE the span: the batch the loop
        # is finishing is still on the timeline, and a ``live.`` span that
        # overlapped it would read as its child; ``.pause`` carries the
        # wait as a stat
        t0 = time.perf_counter()
        self._pause()
        try:
            with TraceAnnotation("live.landing") as whole:
                with Stamped("live.landing.pause", waited_us=int(
                        1e6 * (time.perf_counter() - t0))):
                    pass
                record = self._land(refit, snapshot, whole, t0)
        finally:
            with self._cond:
                self._lander = None
                self._cond.notify_all()
        return record

    def _pause(self):
        """Return once the loop stands between two batches, held there
        until ``_lander`` is cleared."""
        with self._cond:
            if self._thread is None or self._closed:
                raise RuntimeError("LiveUpdater is not running")
            if self._lander is not None:
                raise RuntimeError("a landing is under way")
            self._lander = "wanted"
            self._cond.notify_all()
            while self._lander == "wanted":
                self._cond.wait()
            if self._lander != "granted":
                self._lander = None
                raise RuntimeError("LiveUpdater stopped before the landing")

    def _land(self, refit, snapshot, whole, t0):
        """:meth:`land` with the loop held."""
        if snapshot not in self._marks:
            raise ValueError(f"{snapshot} is no snapshot this updater "
                             "handed out (LiveUpdater.mark) and has not "
                             "landed yet")
        t_paused = time.perf_counter()
        ledger, m = compiles.install(), self.foldin.model
        compiled, sent0 = ledger.now(), placed_bytes()
        # who the batches folded at or after the snapshot: (users, items)
        since = [(users[at >= snapshot], items[at >= snapshot])
                 for at, users, items in self._log]
        users, items = (np.unique(np.concatenate(
            [batch[side] for batch in since] or [np.empty(0, np.int64)]))
            for side in (0, 1))
        events = sum(len(batch[0]) for batch in since)
        t = time.perf_counter()
        did = self.foldin.land(refit, users,
                               items if self.fold_items else ())
        took = {"pause": t_paused - t0, "server": time.perf_counter() - t}
        took.update(did.pop("seconds"))
        seq = self.engine.publish(
            m._U, m._V, placed=self.foldin.device_tables(), release=True)
        made = self.engine.last_landing or {}
        took.update(made.get("seconds", {}))
        with Stamped("live.landing.record"):
            self._marks.remove(snapshot)
            oldest = min(self._marks, default=None)
            self._log = [] if oldest is None else [
                e for e in self._log if e[0][-1] >= oldest]
            caught = {side: did.pop(side) for side in ("users", "items")}
            took["whole"] = time.perf_counter() - t0
            record = {
                "seq": seq, "snapshot": snapshot,
                "users": len(m._user_map), "items": len(m._item_map),
                "catchup_events": events,
                "catchup_users": len(caught["users"][0]),
                "catchup_items": len(caught["items"][0]),
                "rounds": did["rounds"], "calls": did["calls"],
                "programs": ledger.since(compiled)["programs"],
                "placed_bytes": placed_bytes() - sent0,
                "copied_bytes": made.get("copied_bytes", 0),
                "bytes_in_use": device_bytes_in_use(),
                "peak_bytes": device_peak_bytes(),
                "seconds": took, "t_start": t0,
                "t_done": time.perf_counter(), "catchup": caught}
            whole.set_metadata(**{k: record[k] for k in (
                "seq", "users", "items", "catchup_events", "catchup_users",
                "catchup_items")})
            obs.counter("live.landings", **self._labels)
            obs.counter("live.landing.catchup_events", events,
                        **self._labels)
            obs.counter("live.landing.bytes_placed", record["placed_bytes"],
                        **self._labels)
            obs.gauge("live.landing.peak_bytes", record["peak_bytes"],
                      **self._labels)
            obs.emit("live_landing", **{k: record[k] for k in (
                "seq", "snapshot", "users", "items", "catchup_events",
                "catchup_users", "catchup_items", "programs",
                "placed_bytes", "peak_bytes")},
                seconds=round(took["whole"], 6), **self._labels)
            self.landings.append(record)
        return record

    # -- update loop --------------------------------------------------
    def _next_batch(self):
        """Block for the first event, then accumulate until ``max_batch``
        or the oldest event has waited ``max_wait_s``.  Returns None on
        an idle timeout (the loop re-checks for shutdown); a closed,
        non-empty queue drains immediately (no wait)."""
        with self._cond:
            if not self._queue:
                if self._closed:
                    return None
                with TraceAnnotation("live.idle"):
                    self._cond.wait(0.05)
                if not self._queue:
                    return None
            t_oldest = self._queue[0][3]
            with TraceAnnotation("live.batch.coalesce",
                                 waiting=len(self._queue)):
                while (len(self._queue) < self.max_batch
                       and not self._closed and self._lander is None):
                    left = self.max_wait_s - (time.perf_counter()
                                              - t_oldest)
                    if left <= 0:
                        break
                    self._cond.wait(left)
                batch = self._queue[:self.max_batch]
                del self._queue[:self.max_batch]
            obs.gauge("live.queue_depth", len(self._queue),
                      **self._labels)
            return batch

    def _run(self):
        while True:
            with self._cond:
                if self._lander == "wanted":
                    # between two batches: the landing's, until it is done
                    self._lander = "granted"
                    self._cond.notify_all()
                    while self._lander == "granted":
                        self._cond.wait()
            batch = self._next_batch()
            if batch is None:
                with self._cond:
                    if self._closed and not self._queue:
                        if self._lander == "wanted":
                            self._lander = "refused"
                            self._cond.notify_all()
                        return
                continue
            self._batch_seq += 1
            first, self._taken = self._taken, self._taken + len(batch)
            try:
                with TraceAnnotation("live.batch",
                                     seq=self._batch_seq) as whole:
                    mark = cpu_mark()
                    self._process(batch, whole, first)
                    stamp_cpu(whole, mark)
            except BaseException as e:  # noqa: BLE001 — loop must survive
                if not isinstance(e, faults.InjectedFault):
                    obs.emit("warning", what="live.update",
                             reason=f"{type(e).__name__}: {e}")

    def _process(self, batch, whole, first=0):
        """Fold one popped batch in and publish it; ``whole`` is the
        ``live.batch`` span around the call, which takes the batch's
        sizes as its stats; ``first``: the place of its first event in
        the stream of admitted events."""
        t0, placed_before = time.perf_counter(), placements()
        with Stamped("live.batch.prepare"):
            users, items, ratings, arrivals, ctxs = map(list, zip(*batch))
            users, items = np.asarray(users), np.asarray(items)
            ratings = np.asarray(ratings, dtype=np.float32)
            arrivals = np.asarray(arrivals)
            places = first + np.arange(len(batch))
            # chain the queue hop per event (its own wait, not the batch's)
            ctxs = [tracing.record_span(c, "live.queue", seconds=t0 - a)
                    if c is not None else None
                    for c, a in zip(ctxs, arrivals)]
            queue_wait = t0 - float(arrivals.min())

            # quarantine BEFORE the factors can see a poisoned value — the
            # streaming-ingest contract, same event + counter vocabulary
            bad = invalid_rating_mask(ratings)
            n_bad = int(bad.sum())
            if n_bad:
                nonfinite = int((~np.isfinite(ratings)).sum())
                obs.counter("ingest.quarantined_rows", n_bad)
                obs.emit("ingest_quarantined", path="live", rows=n_bad,
                         reasons={"nonfinite": nonfinite,
                                  "out_of_range": n_bad - nonfinite},
                         **self._labels)
                keep = ~bad
                for c, dropped in zip(ctxs, bad):
                    # a poisoned event's trail ENDS at quarantine — status
                    # says so; the trace is complete, not dropped
                    if dropped and c is not None:
                        tracing.record_span(c, "live.quarantine",
                                            status="quarantined")
                users, items = users[keep], items[keep]
                ratings, arrivals = ratings[keep], arrivals[keep]
                places = places[keep]
                ctxs = [c for c, k in zip(ctxs, keep) if k]
            quarantine_s = time.perf_counter() - t0
            if len(ratings) == 0:
                self.flight.record(
                    "quarantined",
                    {"queue_wait": queue_wait, "quarantine": quarantine_s})
                return
            if self._marks:
                # a refit is being made: who was touched after its
                # snapshot is what its landing folds again
                self._log.append((places, users, items))

        p = self.foldin.model._params
        frame = {p["userCol"]: users, p["itemCol"]: items,
                 p["ratingCol"]: ratings}
        tf = time.perf_counter()
        m = self.foldin.model
        users_before, items_before = len(m._user_map), len(m._item_map)
        touched_item_rows = None
        with TraceAnnotation("live.batch.foldin") as span:
            mark = cpu_mark()
            # users first: the item fold regresses on their rows
            with (TraceAnnotation("live.batch.foldin.users")
                  if self.fold_items else contextlib.nullcontext()):
                touched_users = self.foldin.update(frame)
            width = self.foldin.stats[-1][3] if len(touched_users) else 0
            if self.fold_items:
                with TraceAnnotation("live.batch.foldin.items"):
                    touched_item_rows = m._item_map.to_dense(
                        self.foldin.update_items(frame))
            foldin_cpu = stamp_cpu(span, mark)
        foldin_s = time.perf_counter() - tf
        ctxs = [tracing.record_span(c, "live.foldin", seconds=foldin_s)
                if c is not None else None for c in ctxs]

        tp = time.perf_counter()
        with TraceAnnotation("live.batch.publish") as span:
            mark = cpu_mark()
            with Stamped("live.batch.publish.join"):
                # the rows the fold moved, and nothing else of either
                # table; with them, where the engine keeps the users'
                # histories, the ids these ratings add to them (one
                # publish, one generation)
                grown, parts = {}, {}
                if self._histories:
                    grown["seen_appended"] = self._joining()
                    parts["history_ids"] = len(grown["seen_appended"][0])
                if self.fold_items:
                    parts["items"] = len(touched_item_rows)
                if parts:
                    span.set_metadata(**parts)
                # and the same rows where the folds left them on the
                # device: the engine writes its tables from there, not
                # from the host's copies (no keyword where no fold left
                # any: an engine that knows none is asked nothing new)
                held = {side: self.foldin.last_rows[side]
                        for side in ("users", "items")[:1 + self.fold_items]
                        if self.foldin.last_rows[side] is not None}
                if held:
                    grown["device_rows"] = held
                touched_user_rows = m._user_map.to_dense(touched_users)
            seq, mode = self.engine.publish_update(
                m._U, m._V, touched_items=touched_item_rows,
                touched_users=touched_user_rows, trace=ctxs, **grown)
            publish_cpu = stamp_cpu(span, mark)
        publish_s = time.perf_counter() - tp
        # the batch's bookkeeping: counters, the span's stats, the
        # freshness samples, the flight record
        with Stamped("live.batch.record"):
            sizes = {}
            if self.fold_items:
                index = self.engine.published_index
                sizes = {"items": len(touched_item_rows),
                         "new_items": len(m._item_map) - items_before,
                         "segment_rows": (index.delta_count
                                          if index is not None else 0)}
                obs.counter("live.items_appended", sizes["new_items"],
                            **self._labels)
                did = self.foldin.last_items
                obs.counter("live.items_folded", did["first"], kind="first",
                            **self._labels)
                obs.counter("live.items_folded", did["again"], kind="again",
                            **self._labels)
                obs.counter("live.items_left_to_refit", did["left_to_refit"],
                            **self._labels)
                obs.gauge("live.events_waiting", self.foldin.events_waiting,
                          **self._labels)
            # host→device placements this thread made for the batch, beside
            # its programs' calls (``core.foldin.put``)
            made = placements() - placed_before
            obs.counter("live.host_placements", made, **self._labels)
            whole.set_metadata(
                events=len(ratings), users=len(touched_users),
                new_users=len(m._user_map) - users_before,
                width=width, mode=mode, placements=made, **sizes)
            ctxs = [tracing.record_span(c, "live.publish",
                                        seconds=publish_s, seq=seq,
                                        mode=mode)
                    if c is not None else None for c in ctxs]

            done = time.perf_counter()
            fresh = done - arrivals
            obs.histogram_many("live.freshness_seconds", fresh.tolist(),
                               **self._labels)
            for fr, c in zip(fresh, ctxs):
                # the terminal hop: this event's publish seq is now visible
                # to the score path; its seconds ARE the freshness sample
                if c is not None:
                    tracing.record_span(c, "live.visible", seconds=float(fr),
                                        seq=seq)
            worst, worst_ctx = float(fresh.max()), ctxs[int(fresh.argmax())]
            touched = len(touched_users) + (
                len(touched_item_rows) if touched_item_rows is not None
                else 0)
            obs.emit("live_update", seq=seq, events=len(ratings),
                     touched=touched, mode=mode, **self._labels)
            self.flight.record(
                "ok",
                {"queue_wait": queue_wait, "quarantine": quarantine_s,
                 "foldin": foldin_s, "publish": publish_s},
                e2e_seconds=worst, seq=seq, mode=mode,
                # for whoever runs no profiler: how many events became
                # visible, and when on perf_counter's clock (the engine's
                # batch records carry their ``t0`` the same way)
                events=len(ratings), t_done=done, **sizes,
                # the thread's own CPU seconds in the two phases, beside
                # their wall seconds above (None: no profiler recorded):
                # what is missing it spent waiting, for the interpreter or
                # for the device
                foldin_cpu=foldin_cpu, publish_cpu=publish_cpu,
                trace_ids=sorted({c.trace_id for c in ctxs
                                  if c is not None}) or None)
            if self.slo_s is not None and worst > self.slo_s:
                obs.emit("live_freshness_breach", seq=seq,
                         freshness_seconds=worst, slo_s=self.slo_s,
                         trace_id=(worst_ctx.trace_id
                                   if worst_ctx is not None else None),
                         **self._labels)
                self.flight.dump("freshness_breach")
