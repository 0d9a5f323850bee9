"""Streaming micro-batch driver: serve fold-in updates without a refit.

The capability the reference stack lacks (Spark MLlib requires a full refit
for new ratings — SURVEY.md §3.5), promised by the north-star (BASELINE.json
configs[3]: "hourly micro-batches of new ratings → incremental user-factor
jit update").  The server wraps a fitted ALSModel; each ``update`` call:

1. groups the batch by touched user and puts each user's events behind the
   rating history the server keeps of that user (optional) — kept in the
   form the fold program takes it, the fixed side's TABLE ROWS and the
   stars, so a fold maps its events' ids and never a history's
   (``_Ratings``),
2. pads touched-user rows and widths up a short ladder (8, 64, 512, ...:
   ``core.ratings.pad_for``) so that the set of compiled programs is small
   and ``prewarm`` can run all of them before the stream starts,
3. runs the jitted fold-in kernel against the fixed item factors,
4. writes the new rows into the model (brand-new users into spare rows).

Every step costs what the batch touches, never the table: the model's
factor arrays are views of buffers with spare rows
(``core.ratings.row_capacity``), its id maps take appended ids without a
re-sort, and the fixed side lives on the device once, padded to the same
capacity, so an append changes no array's shape.

Item factors stay fixed during USER fold-ins (the standard fold-in
contract); the symmetric ``update_items`` folds new/updated ITEMS against
the fixed user factors, so both directions of catalog growth are served
between refits — and when a refit is done, :meth:`FoldInServer.land`
takes it as the new base (both tables replaced, the kept ratings kept)
and folds the events since its snapshot onto it again
(``LiveUpdater.land`` is what a deployment calls).  Each direction's
fixed side is a table of the server's
own on the device, placed once (the catalog at construction, the user
table when the first item fold — or ``prewarm`` of the item side — asks
for it): a fold's write-back also writes the rows it moved into that
table IN PLACE (``core.foldin.write_rows``), so the other direction's
next fold reads them, and no batch uploads a table.

**Under implicit feedback** (``implicitPrefs``: Hu, Koren and Volinsky's
rule, ``ops.solve.normal_eq_implicit``) a fold also reads ``F^T F`` of its
WHOLE fixed table.  The server keeps that Gram matrix on the device
beside each fixed table — ``V^T V`` from construction, ``U^T U`` once the
item side is placed — computed whole only where the table itself is
placed whole (``core.foldin.whole_yty``, true float32: at start, and
after the spare rows ran out) and from then on MOVED by the rows each
write-back writes, ``G + new^T new - old^T old`` inside the row write's
own program (``core.foldin.write_rows(yty=)``): a user fold moves
``U^T U``, an item fold ``V^T V``, O(touched rows) a batch and never the
table.  ``foldin.yty_rows`` / ``foldin.yty_full`` count both; an explicit
server keeps no Gram matrix and runs the plain row writes.

**A rating whose other side has no factor yet** (a new item at its
user's fold, a new user at its item's fold) is kept in the history, not
dropped: a fold regresses on those of the entity's ratings whose other
side has a factor WHEN IT RUNS, so a held rating enters the entity's
first fold after the other side got one.  An entity none of whose
ratings can be used gets no factor (it is not appended) and its ratings
wait; ``events_waiting`` counts them.  (Without ``keep_history`` there
is nowhere to keep one: it is dropped, as before.)

**A server that knows its users' histories** (``base_history``: the
ratings behind the factors, resident beside them) folds a user over ALL of
that user's ratings — the resident ones and, behind them in arrival order,
the run's — which is the fold-in contract (Spark's, ``implicit``'s
``recalculate_user``): without it a returning customer with 2,000 ratings
who rates one more item gets a factor fitted to that one rating.  A fold
then is as wide as its longest history, so the widths' ladder reaches the
rung above the longest resident one (``core.ratings.growth_pads``), a call
gathers at most ``FOLD_ELEMENTS`` ratings (a batch of many long histories
goes in several), and a batch costs the host and the link what its touched
users' histories hold, never all the histories — and of that only slice
copies: a touched user's resident run IS table rows and is copied as it
lies, once, at that user's first event.  Such a server also keeps
ONE rating a user and item (Amazon keeps one review a customer and
product): an event on an item its user has rated already — in the
resident history or earlier in the run — replaces that rating's stars and
adds no id; ``last_appended`` names the pairs of the last ``update`` that
did add one, for whoever keeps the users' histories elsewhere
(``ServingEngine.publish_update(seen_appended=...)``).  A server without a
base history cannot know what was rated before the run and keeps every
event as a rating of its own, as it always has.

**The item side of such a server** obeys the same contract: a fold is over
ALL of the entity's ratings, or it does not happen.  The resident ratings
lie by user, and a hot item's are hundreds of thousands: folding it over
the run's handful of events would replace a factor fitted to all of them by
one fitted to five, and folding it over all of them is a gather of hundreds
of megabytes an event.  So ``update_items`` folds an item none of whose
ratings is resident — a new id, or a catalog row no resident rating names:
the run's events are all its ratings (``implicit``'s ``partial_fit_items``
for items new to the model) — and leaves an item with resident ratings its
factor until the refit (``live.items_left_to_refit`` counts its events,
which still enter their users' folds and histories).  Which is which the
server reads from its base history, once, when the item side is first
asked for.

**A fold on the profiler's timeline** (``obs.schema.
LIVE_PHASE_SPAN_KEYS``, ``LIVE_FOLDIN_SPAN_KEYS``; each with its
``side``): ``live.batch.foldin.group``, ``.history`` (the events' ids
mapped once, the events behind their entities' rows), ``.map`` (only the
ids a history still holds WITHOUT a row, and only once the map has grown:
``mapped`` beside ``ratings``, the counter ``foldin.ids_mapped``),
``.pack`` (slice copies, one a call), ``.readback`` around ``.call``,
``.write_back`` — spans a FOLD, never an event; outside a profiler
session a microsecond each.
"""

from __future__ import annotations

import collections
import contextlib
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from tpu_als import obs
from tpu_als.core.foldin import (
    fold_in,
    place_rows,
    planes,
    solve_path,
    whole_yty,
    write_placed_rows,
    write_rows,
)
from tpu_als.core.ratings import (
    LIVE_PADS,
    growth_pads,
    growth_room,
    pad_for,
    pads_up_to,
    row_capacity,
    rung_for,
)
from tpu_als.obs.phases import phase
from tpu_als.serving.engine import Stamped
from tpu_als.utils.frame import as_frame


# ratings one call of the fold-in program gathers at most, at the widths
# only a resident base history reaches (above LIVE_PADS): 0.5 GB of
# rank-256 float32 rows, and the Gram build holds it more than once
FOLD_ELEMENTS = 1 << 19

# entities one call of the fold-in program takes in a landing's catch-up
# (``FoldInServer.land``): the widest shape ``prewarm`` runs.  A call is a
# program no scoring batch can overtake on the device
CATCHUP_ROWS = LIVE_PADS[-1]

# the server's table on the device -> the ``side`` its Gram matrix's
# counters and start phase carry (the TABLE's: ``_Ud`` is the user table)
_YTY_SIDE = {"_V": "item", "_Ud": "user"}


class FoldInServer:
    """Incremental user-factor updates against a fitted model.

    ``base_history``: the users' resident rating histories, ``(indptr,
    indices, stars)`` — CSR over the model's dense user rows (row ``u`` is
    user ``model._user_map.to_original(u)``; fewer rows than users: the
    others have none), ``indices`` dense catalog rows, a row's none twice,
    ``stars`` the ratings.  A user's fold is then over the resident
    ratings AND the events, and an event on an item the user has already
    rated replaces that rating (module docstring).  The arrays are read,
    never written or copied whole: a touched user's run is copied into the
    server's own history at that user's first event, as the table rows it
    is (:meth:`history_of` reads a history back as original ids).  Needs
    ``keep_history``."""

    def __init__(self, model, keep_history=True, stats_window=512,
                 base_history=None):
        self.model = model
        self.keep_history = keep_history
        if base_history is not None and not keep_history:
            raise ValueError("base_history needs keep_history: the events "
                             "go behind the resident ratings")
        self._base = base_history
        # (user ids, item ids) of the last ``update``'s events that added
        # an id to their user's history (all of them without a base)
        self.last_appended = (np.empty(0, np.int64), np.empty(0, np.int64))
        # of the last ``update_items``: items folded for the first time
        # and again, events of items it left to the refit (module
        # docstring: the item side of a server with a base history)
        self.last_items = {"first": 0, "again": 0, "left_to_refit": 0}
        # of the last ``update`` ("users") and ``update_items`` ("items"):
        # ``(table rows, their new factors ON THE DEVICE)`` — the fold
        # program's own result, padded up the rows' ladder, its first
        # ``len(rows)`` rows those of ``rows`` — for whoever writes the
        # same rows into another table on the device
        # (``ServingEngine.publish_update(device_rows=...)``); ``None``
        # where nothing was folded or the fold took several calls
        self.last_rows = {"users": None, "items": None}
        # original id -> its ratings as the fold program takes them
        # (``_Ratings``: the fixed side's TABLE ROWS and the stars, in
        # arrival order; ``history_of`` gives the original ids)
        self._history = {}
        self._item_history = {}
        # side -> {original id: how many of its ratings its last fold
        # could use}
        self._used = {"user": {}, "item": {}}
        # side -> {original id without a factor: ratings held for it}
        self._waiting = {"user": {}, "item": {}}
        p = model._params
        self._reg = float(p.get("regParam", 0.1))
        self._implicit = bool(p.get("implicitPrefs", False))
        self._alpha = float(p.get("alpha", 1.0))
        self._nonnegative = bool(p.get("nonnegative", False))
        # with a base history, by dense catalog row: whether a resident
        # rating names it (made when the item side is first asked for)
        self._rated_before = None
        self._bufs = {}     # "_U" / "_V" -> the buffer the model's is a view of
        self._Ud = None
        # implicit: "_V" / "_Ud" -> that table's Gram matrix on the device,
        # moved by every write of the table (module docstring)
        self._yty = {}
        # what of a start takes time, phase by phase
        # (``obs.schema.START_PHASES``)
        with phase("start.foldin_server"):
            # the widths' ladder: up the plain one (8, 64, 512, ...)
            # without a base history, with the rung above the longest
            # resident one's rung where histories grow from there
            with phase("start.foldin_server.history"):
                self._widths = () if base_history is None else growth_pads(
                    int(np.diff(base_history[0]).max(initial=0)))
            with phase("start.foldin_server.reserve"):
                self._reserve(items_side=False)
            # each fold direction's fixed side on the device, placed
            # once: the catalog now, the user table when an item fold
            # first needs it (a user-only server never holds one)
            with phase("start.foldin_server.place"):
                self._V = self._place("_V")
            self._whole_yty("_V", "start")
        # (batch_size, touched_users, latency_seconds, padded width) —
        # bounded: a
        # long-lived live pipeline folds in forever, and the durable
        # record is the registered obs histograms, not this ring
        self.stats = collections.deque(maxlen=int(stats_window))

    def _capacity(self, fac_attr, rows=0):
        """Rows the buffers of this table have, on the host and on the
        device alike: what was reserved, or ``row_capacity`` of the table
        (of ``rows`` more, where that no longer holds them)."""
        fac, buf = getattr(self.model, fac_attr), self._bufs.get(fac_attr)
        if buf is not None and fac.base is buf and len(fac) + rows <= len(buf):
            return len(buf)
        cap = row_capacity(len(fac))
        return cap if len(fac) + rows <= cap else row_capacity(len(fac) + rows)

    def _place(self, fac_attr, growth=0):
        """The fixed side of a fold on the device, with spare zero rows
        (``growth``: that many doublings more).  The kernel only GATHERS
        its rows (by dense ids below the live count) and, on the implicit
        path, reads ``F^T F`` — the Gram matrix the server keeps beside
        the table (:meth:`_whole_yty` wherever this places one, moved by
        the row writes in between); zero rows change neither — so
        entities appended to it later change no shape and compile
        nothing."""
        return place_rows(
            getattr(self.model, fac_attr),
            capacity=self._capacity(fac_attr) << growth,
            table="fold_fixed").block_until_ready()

    def _whole_yty(self, dev_attr, when):
        """Implicit feedback: the Gram matrix of the server's table
        ``dev_attr`` (``"_V"`` | ``"_Ud"``) computed WHOLE, O(table) —
        only where that table was just placed whole (``when``: ``start``
        | ``placed``, module docstring)."""
        if not self._implicit:
            return
        side = _YTY_SIDE[dev_attr]
        with (phase("start.foldin_server.yty", side=side)
              if when == "start" else contextlib.nullcontext()):
            self._yty[dev_attr] = whole_yty(
                getattr(self, dev_attr)).block_until_ready()
        obs.counter("foldin.yty_full", side=side, when=when)

    def yty(self, items_side=False):
        """The Gram matrix ``F^T F`` a fold of this direction reads, as
        the server keeps it on the device (``items_side``: ``U^T U``, else
        ``V^T V``); ``None`` under explicit feedback, and for a table not
        placed yet."""
        return self._yty.get("_Ud" if items_side else "_V")

    def _reserve(self, items_side, rows=0):
        """Make the model's factor table of this side a writable view of a
        buffer that holds ``rows`` more: one copy of the table here (and
        again whenever the spare rows run out), none per batch."""
        m = self.model
        fac_attr = "_V" if items_side else "_U"
        fac, cap = getattr(m, fac_attr), self._capacity(fac_attr, rows)
        buf = self._bufs.get(fac_attr)
        if buf is not None and fac.base is buf and cap == len(buf):
            return
        buf = np.zeros((cap, fac.shape[1]), dtype=fac.dtype)
        buf[:len(fac)] = fac
        self._bufs[fac_attr] = buf
        setattr(m, fac_attr, buf[:len(fac)])
        (m._item_map if items_side else m._user_map).reserve(cap)

    def _rows_at(self, width):
        """Entities one call of the program takes at this padded width:
        any number up to the widths every server has always run
        (``LIVE_PADS``), beyond them what keeps the gather under
        ``FOLD_ELEMENTS`` ratings, down the rows' ladder (8 at least)."""
        if width <= LIVE_PADS[-1]:
            return None
        return max(p for p in pads_up_to(max(8, FOLD_ELEMENTS // width))
                   if p == 8 or p * width <= FOLD_ELEMENTS)

    def prewarm(self, rows=LIVE_PADS, widths=None,
                sides=("user",), growth=0):
        """Compile AND run the fold-in program of every padded shape up to
        ``max(rows)`` entities of ``max(widths)`` ratings a batch
        (``widths``: by default up to 512, with a base history up to the
        rung above the longest resident one; the shapes no call takes —
        more rows than ``_rows_at`` allows a width — are left out).

        ``update`` pads a batch up the ladder 8, 64, 512, ..., so the
        programs are few — but a shape's first call still pays its compile
        and its first execution, which a latency SLO cannot absorb (p95
        11x p50 on the first 30 batches).  Serving deployments call this
        once at startup; entries are cached per process.  One
        ``foldin_solve_path`` event per program says how it solves.

        ``sides`` picks the fold directions ("user" solves against the
        item table, "item" against the user table — a live pipeline with
        ``fold_items`` needs both).  ``growth`` also runs against the
        fixed table with that many doublings of its capacity: a stream
        that appends entities past the spare rows re-places it, and that
        program should be paid for here, not mid-stream.  Shapes shared
        between sides (equal capacities) hit the same jit-cache entry.
        """
        m = self.model
        rows = pads_up_to(max(rows))
        widths = (pads_up_to(max(widths)) if widths is not None
                  else self._widths or LIVE_PADS)
        for side in sides:
            with phase("start.prewarm", side=side):
                with phase("start.prewarm.reserve", side=side):
                    # the id maps sort their ids at first use: now, not
                    # mid-stream
                    (m._user_map if side == "user"
                     else m._item_map).to_dense([0])
                fixed = self._fixed(items_side=side == "item")
                with phase("start.prewarm.programs", side=side):
                    self._prewarm_folds(side, fixed, rows, widths, growth)
        if self._Ud is not None:
            # both directions fold: each one's write-back also writes
            # its rows into the other's fixed table; those programs now
            # (from the host's rows and from the fold's on the device:
            # one program, both forms of its call)
            none = np.empty((0, m._U.shape[1]), np.float32)
            with phase("start.prewarm", side="both"), \
                    phase("start.prewarm.writes"):
                for n in rows:
                    placed = jnp.zeros((n, m._U.shape[1]), jnp.float32)
                    for dev_attr in ("_V", "_Ud"):
                        self._write(dev_attr, [], none, pad=n)
                        self._write(dev_attr, [], none, placed=placed)
                jax.block_until_ready((self._V, self._Ud, self._yty))

    def _prewarm_folds(self, side, fixed, rows, widths, growth):
        """:meth:`prewarm`'s ladder for one side: the fold-in program of
        every padded shape compiled (or fetched) and run against
        ``fixed``, and against a table ``growth`` doublings larger (its
        whole-table Gram program too, which a re-placement at that size
        would run)."""
        YtY = self.yty(items_side=side == "item")
        for g in range(int(growth) + 1):
            F = (fixed if g == 0
                 else self._place("_V" if side == "user" else "_U", g))
            if g and self._implicit:
                whole_yty(F).block_until_ready()
                obs.counter("foldin.yty_full", when="start", side=_YTY_SIDE[
                    "_V" if side == "user" else "_Ud"])
            for n in rows:
                _, path, why = solve_path(F.shape[1], n, self._nonnegative)
                for w in widths:
                    if n > (self._rows_at(w) or n):
                        continue
                    # host arrays, as ``_fold_batch`` hands them over
                    self._fold(F, planes(np.zeros((3, n, w), np.int32)),
                               YtY).block_until_ready()
                    obs.emit("foldin_solve_path", side=side,
                             rank=int(F.shape[1]), rows=n, width=w,
                             path=path, reason=why)

    def update(self, batch):
        """Process one micro-batch frame (userCol/itemCol/ratingCol of the
        model).  Returns the original ids of the users whose factors moved.
        """
        return self._fold_batch(batch, items_side=False)

    def update_items(self, batch):
        """Symmetric fold-in for ITEMS: solve new/updated item factors
        against the (fixed) user factors — a brand-new item with a few
        ratings from known users becomes recommendable without a refit.
        The reference stack requires a full refit here too (SURVEY §3.5).

        A rating by a user the model does not hold cannot be regressed
        on yet: it is kept and enters the item's first fold after the
        user has a factor (module docstring; fold the batch's users
        first, ``update``, and a new user's rating counts at once).
        The write-back also writes the moved rows into the catalog the
        USER fold-ins read on the device, so they see the new items.
        Returns the original ids of the items whose factors moved.
        """
        return self._fold_batch(batch, items_side=True)

    @property
    def events_waiting(self):
        """Ratings held in a history for a side whose other entity has
        no factor yet, one per rating and side."""
        return sum(sum(w.values()) for w in self._waiting.values())

    def _fixed(self, items_side):
        """The fixed side of a fold of this direction, on the device."""
        if not items_side:
            return self._V
        if self._Ud is None:
            # item folds begin: the catalog gets its spare rows on the
            # host too (one copy, here and not under the first append)
            with phase("start.prewarm.reserve", side="item"):
                self._reserve(items_side=True)
                if self._base is not None:
                    self._rated_before = np.bincount(
                        self._base[1], minlength=len(self.model._V)) > 0
            with phase("start.prewarm.place", side="item"):
                self._Ud = self._place("_U")
            self._whole_yty("_Ud", "start")
        return self._Ud

    def _to_refit(self, items):
        """Which of the events' ``items`` (original ids) the item side
        of a server with a base history leaves alone: those a resident
        rating names (module docstring)."""
        self._fixed(items_side=True)
        row = self.model._item_map.to_dense(items)
        known = (row >= 0) & (row < len(self._rated_before))
        return known & self._rated_before[np.where(known, row, 0)]

    def _fold_batch(self, batch, items_side):
        """ONE shared mechanics path for both directions — history
        merge, known-side filter, per-entity grouping, ladder padding,
        solve, write-back — parameterized by which side is being solved,
        so a fix to any of it cannot apply to one direction only."""
        t0 = time.perf_counter()
        side = "item" if items_side else "user"
        # the fold's host work by phase on the profiler's timeline
        # (obs.schema.LIVE_PHASE_SPAN_KEYS), each with its ``side``
        sides = side + "s"
        with Stamped("live.batch.foldin.group", side=sides):
            frame = as_frame(batch)
            m = self.model
            p = m._params
            self.last_rows[sides] = None
            if items_side:
                solved_raw = np.asarray(frame[p["itemCol"]])
                fixed_raw = np.asarray(frame[p["userCol"]])
                fixed_map, history = m._user_map, self._item_history
            else:
                solved_raw = np.asarray(frame[p["userCol"]])
                fixed_raw = np.asarray(frame[p["itemCol"]])
                fixed_map, history = m._item_map, self._history
            r = np.asarray(frame[p["ratingCol"]], dtype=np.float32)
            if not items_side:
                self.last_appended = (solved_raw[:0], fixed_raw[:0])
            else:
                self.last_items = dict.fromkeys(self.last_items, 0)
                if self._base is not None and len(solved_raw):
                    # an item with resident ratings keeps its factor until
                    # the refit: a fold here could not be over all of them
                    left = self._to_refit(solved_raw)
                    self.last_items["left_to_refit"] = int(left.sum())
                    solved_raw, fixed_raw, r = (
                        a[~left] for a in (solved_raw, fixed_raw, r))
            if len(solved_raw) == 0:
                return np.array([], dtype=np.int64)

            # group the events by entity, each entity's in arrival order,
            # and put them behind the entity's history
            touched, entity = np.unique(solved_raw, return_inverse=True)
            by_entity = np.argsort(entity, kind="stable")
            # entity j's events: ``by_entity[cut[j]:cut[j + 1]]``
            cut = np.append(0, np.cumsum(
                np.bincount(entity, minlength=len(touched))))
            ids_by, stars_by = fixed_raw[by_entity], r[by_entity]
        with Stamped("live.batch.foldin.history", side=sides):
            used = self._used[side] if self.keep_history else {}
            # the events' own ids are ALL a fold maps (a history is kept
            # in table rows: ``_Ratings``); -1: no factor yet
            event_rows = fixed_map.to_dense(fixed_raw).astype(np.int32)
            grown, mapped = len(fixed_map), len(fixed_raw)
            no_row = event_rows < 0
            lacking = bool(no_row.any())
            # with a base history a user has ONE rating an item: an event
            # on an item already rated replaces it (``adds``: which events
            # add an id to their user's history)
            one_rating = self._base is not None and not items_side
            adds = np.ones(len(r), bool)
            if self.keep_history and lacking:
                # a rating whose other side has no factor yet waits for it
                held = self._waiting["user" if items_side else "item"]
                for e in fixed_raw[no_row].tolist():
                    held[e] = held.get(e, 0) + 1
            rows_by = event_rows[by_entity]
            # which entities' events hold an id without a row
            lacks = (np.logical_or.reduceat(no_row[by_entity],
                                            cut[:-1]).tolist()
                     if lacking else [False] * len(touched))
            hists, cut, entities = [], cut.tolist(), touched.tolist()
            # the users no event has touched yet start from their resident
            # runs: their table rows in ONE lookup (a lookup is a dozen
            # numpy calls whatever it looks up, and on a serving host the
            # calls are what a batch of five events pays for)
            fresh = ([e for e in entities if e not in history]
                     if one_rating else [])
            home = dict(zip(fresh, m._user_map.to_dense(fresh).tolist())
                        if fresh else ())
            for j, e in enumerate(entities):
                mine = slice(cut[j], cut[j + 1])
                hist = history.get(e) if self.keep_history else None
                if hist is None:
                    hist = self._resident(home[e]) if one_rating else None
                    if hist is None:
                        hist = _Ratings()
                    else:
                        # behind the model's factors: folded before
                        used[e] = hist.n
                    if self.keep_history:
                        history[e] = hist
                if one_rating:
                    old = hist.rate(rows_by[mine], ids_by[mine],
                                    stars_by[mine], grown, lacks[j])
                    if old:
                        adds[by_entity[mine][old]] = False
                else:
                    hist.extend(rows_by[mine], ids_by[mine], stars_by[mine],
                                grown, lacks[j])
                hists.append(hist)
            if not items_side:
                self.last_appended = (solved_raw[adds], fixed_raw[adds])

        # a fold regresses on the ratings whose other side has a factor
        # NOW (fixed-side entities never seen cannot contribute: no
        # factors to regress on); an entity with none is not folded
        with Stamped("live.batch.foldin.map", side=sides) as span:
            # no lookup of an id that has its row: only what a history
            # holds without one, and only where the map has grown since
            # that history last looked
            mapped += _look_again(
                [h for h in hists if h.unknown and h.looked < grown],
                fixed_map)
            span.set_metadata(ratings=sum(h.n for h in hists),
                              mapped=mapped)
            obs.counter("foldin.ids_mapped", mapped, side=side)
            usable = np.array([h.n - len(h.unknown) for h in hists])
            # ratings that enter a fold of this side for the first time (a
            # rating that replaces one enters in its place)
            entered = int((~no_row[~adds]).sum())
            for e, n_ok in zip(touched.tolist(), usable.tolist()):
                entered += n_ok - used.get(e, 0)
                used[e] = n_ok
            fold = usable > 0
            if not fold.any():
                return np.array([], dtype=np.int64)
            touched = touched[fold]
            hists = [h for h, f in zip(hists, fold.tolist()) if f]
            lens = usable[fold]

            F, YtY = self._fixed(items_side), self.yty(items_side)
            # pad rows and width up the ladder -> the programs prewarm
            # ran; one call, or where its gather would pass FOLD_ELEMENTS
            # several
            n = len(touched)
            x, widest, solved = (np.empty((n, F.shape[1]), np.float32), 0,
                                 [])
        for sel in self._calls(lens):
            with Stamped("live.batch.foldin.pack", side=sides):
                n_pad, w = pad_for(len(sel)), rung_for(int(lens[sel].max()),
                                                       self._widths)
                obs.histogram("foldin.history_width", w, side=side)
                widest = max(widest, w)
                # ids, stars and mask as planes of the ONE array the
                # program takes (``core.foldin.pack_rows``), a host
                # argument of its call: nothing is placed ahead of it;
                # each entity's usable ratings as slice copies
                rows = _packed((hists[i].usable() for i in sel.tolist()),
                               n_pad, w)
            # the fold's one wait for the device, on the profiler's
            # timeline (obs.schema.LIVE_FOLDIN_SPAN_KEYS): the call, which
            # carries the one array up (a phase of its own inside, until
            # the call returns), and the rows read back
            with TraceAnnotation("live.batch.foldin.readback", side=sides):
                with Stamped("live.batch.foldin.call", side=sides,
                             rows=n_pad, width=w, calls=len(solved) + 1):
                    solved.append(self._fold(F, rows, YtY))
                x[sel] = np.asarray(solved[-1])[:len(sel)]

        with Stamped("live.batch.foldin.write_back", side=sides):
            if items_side:
                first = int((m._item_map.to_dense(touched) < 0).sum())
                self.last_items.update(first=first, again=n - first)
            # one call: its result holds the batch's rows in ``touched``'s
            # order, and is what every table on the device is written from
            placed = solved[0] if len(solved) == 1 else None
            at = self._write_back(touched, x, items_side, placed)
            if placed is not None:
                self.last_rows[sides] = (at, placed)
            dt = time.perf_counter() - t0
            self.stats.append((entered, n, dt, widest))
            obs.counter("foldin.ratings", entered)
        return touched

    def _fold(self, F, rows, YtY):
        """The fold-in program on ``rows`` (``cols, vals, mask``) against
        ``F``, by this model's parameters: the one call ``_fold_batch``
        makes and ``prewarm`` runs ahead."""
        return fold_in(F, *rows, self._reg,
                       implicit_prefs=self._implicit, alpha=self._alpha,
                       nonnegative=self._nonnegative, YtY=YtY)

    def _resident(self, row):
        """The resident ratings of the user at table row ``row`` as the
        start of the server's own history of that user, copied as they
        lie — the run IS table rows — into buffers with room (``None``
        for a user the base history has no row for)."""
        indptr, indices, stars = self._base
        if not 0 <= row < len(indptr) - 1:
            return None
        lo, hi = int(indptr[row]), int(indptr[row + 1])
        n = hi - lo
        size = n + int(growth_room(n))
        return _Ratings(_resized(indices[lo:hi], n, size, np.int32),
                        _resized(stars[lo:hi], n, size, np.float32), n)

    def history_of(self, entity, items_side=False):
        """``(original ids of the other side, stars)`` of the ratings the
        server keeps of ``entity`` (an original id: a user's, with
        ``items_side`` an item's), in arrival order — copies; empty for
        an entity no event has touched."""
        m = self.model
        hist = (self._item_history if items_side
                else self._history).get(entity)
        ids = (m._user_map if items_side else m._item_map).ids
        if hist is None:
            return ids[:0].copy(), np.empty(0, np.float32)
        rows = hist.rows[:hist.n]
        out, known = np.empty(hist.n, ids.dtype), rows >= 0
        out[known] = ids[rows[known]]
        for at, original in hist.unknown.items():
            out[at] = original
        return out, hist.stars[:hist.n].copy()

    def _calls(self, lens):
        """The entities of one batch by call of the fold-in program, as
        index arrays into ``lens`` (their usable ratings): all in one
        where the padded batch is a shape :meth:`_rows_at` allows, else
        longest first, each call as many as its own width allows."""
        n = len(lens)
        most = self._rows_at(rung_for(int(lens.max()), self._widths))
        if most is None or pad_for(n) <= most:
            yield np.arange(n)
            return
        order, at = np.argsort(-lens, kind="stable"), 0
        while at < n:
            most = self._rows_at(rung_for(int(lens[order[at]]),
                                          self._widths)) or n
            yield order[at:at + most]
            at += most

    def _write_back(self, touched_raw_ids, new_rows, items_side=False,
                    placed=None):
        """New factor rows into the model's table, and into the server's
        own table of that side on the device where it holds one (the
        other direction's fixed side); entities the id map does not know
        take the next spare rows, in the order given, and what was held
        for them waits no longer.  ``placed``: the same rows where they
        still lie on the device, padded (the fold's own result) — the
        device's table is then written from them, the row numbers alone
        coming from the host.  Returns the table rows written."""
        m = self.model
        fac_attr = "_V" if items_side else "_U"
        emap = m._item_map if items_side else m._user_map
        dense = emap.to_dense(touched_raw_ids)
        new = dense < 0
        self._reserve(items_side, rows=int(new.sum()))
        if new.any():
            dense[new] = emap.append(touched_raw_ids[new])
            setattr(m, fac_attr, self._bufs[fac_attr][:len(emap)])
            held = self._waiting["item" if items_side else "user"]
            for e in touched_raw_ids[new].tolist():
                held.pop(e, None)
        getattr(m, fac_attr)[dense] = new_rows
        dev_attr = "_V" if items_side else "_Ud"
        table = getattr(self, dev_attr)
        if table is None:
            return dense
        if int(table.shape[0]) != len(self._bufs[fac_attr]):
            # spare rows used up: the table of the new capacity, whole
            # (and with it, implicit, its Gram matrix)
            setattr(self, dev_attr, self._place(fac_attr))
            self._whole_yty(dev_attr, "placed")
        else:
            self._write(dev_attr, dense, new_rows, placed=placed)
        return dense

    def _write(self, dev_attr, rows, vals, placed=None, pad=None):
        """``vals`` at ``rows`` of the server's table ``dev_attr`` on the
        device, in place (``placed``: from the same rows where they lie
        there already) — and, implicit, that table's Gram matrix moved by
        them in the same program."""
        table, yty = getattr(self, dev_attr), self._yty.get(dev_attr)
        out = (write_placed_rows(table, rows, placed, yty=yty)
               if placed is not None
               else write_rows(table, rows, vals, pad=pad, yty=yty))
        if yty is not None:
            out, self._yty[dev_attr] = out
            obs.counter("foldin.yty_rows", len(rows),
                        side=_YTY_SIDE[dev_attr])
        setattr(self, dev_attr, out)

    # -- a refit lands ---------------------------------------------------
    def device_tables(self):
        """``(user table | None, catalog)`` as the server holds them on
        the device (each the fixed side of one fold direction, spare rows
        and all; the user table ``None`` until item folds began): for
        whoever takes a copy of them there
        (``ServingEngine.publish(placed=...)``)."""
        return self._Ud, self._V

    def land(self, refit, users=(), items=()):
        """A REFIT lands: ``refit`` (an ``ALSModel``: the factors of a
        whole new fit, with their ids) becomes the base the server folds
        against, and the CATCH-UP is folded onto it.

        Both host tables take the refit's rows at the rows the live id
        maps give those ids (a refit whose ids are the live maps' first
        ids in their order is copied as it lies; an id the live maps do
        not hold is a ``ValueError``), the server's tables on the device
        are released and placed anew from them (a chunk at a time, at
        the capacities they had: same shapes, same programs), and the
        kept ratings (``_Ratings``: table rows, which an id keeps for
        good) stay as they are.  Then the catch-up, by the rule every
        batch follows — users first, then items: every entity of
        ``users`` / ``items`` (original ids: those with an event admitted
        after the refit's snapshot, which the caller knows) and every
        entity the live maps hold that the refit does not (new since the
        snapshot: it keeps its row NUMBER and is given the row again) is
        folded over ALL its kept ratings whose other side has a row in
        the landed tables when the fold runs — the refit's catalog for
        the users, the user table as the users' catch-up left it for the
        items.  Some of these ratings name an entity new since the
        snapshot, which has no row until its own side's catch-up gave it
        one: so the rounds go on, users then items, each folding again
        whoever can now use MORE of its ratings than its last fold here
        could, until a round folds nobody — every row of the landed
        generation then includes every kept rating it can, as the rows
        it replaces did.  An entity in neither set keeps the refit's row
        untouched.  Nothing counts into ``foldin.ratings`` (no rating
        enters a fold for the first time); what a later fold counts as
        entering is measured against what the catch-up could use.

        The caller (``LiveUpdater.land``) makes sure no fold runs
        meanwhile.  Returns ``{"users": (ids, rows), "items": (ids,
        rows), "rounds", "calls", "seconds"}``: the entities the catch-up
        folded with their new rows (copies), in the order they were
        folded, and the seconds of the three steps (``tables``,
        ``place``, ``catchup``)."""
        m, r = self.model, refit
        if not self.keep_history:
            raise ValueError("a landing needs keep_history: the catch-up "
                             "folds over the kept ratings")
        if self._base is not None or self._implicit:
            raise NotImplementedError(
                "a landing under resident histories or implicit feedback: "
                "the grown layout laid out anew / both Gram matrices "
                "recomputed (ROADMAP R11)")
        if int(r._U.shape[1]) != int(m._U.shape[1]):
            raise ValueError(f"the refit's rank {r._U.shape[1]} is not "
                             f"the live model's {m._U.shape[1]}")
        both = self._Ud is not None
        sides = (("user", "_U", m._user_map, r._user_map, r._U,
                  self._history),
                 ("item", "_V", m._item_map, r._item_map, r._V,
                  self._item_history))
        # side -> which table rows the refit gives; the rows its ids take
        # (None: the first ones, in order); who is folded again
        has, at, todo = {}, {}, {}
        took, t = {}, time.perf_counter()
        with Stamped("live.landing.tables"):
            for (side, fac_attr, emap, theirs, F, history), asked in zip(
                    sides, (users, items)):
                n_live, n = len(emap), len(theirs)
                rows = None
                if n > n_live or not np.array_equal(theirs.ids,
                                                    emap.ids[:n]):
                    rows = emap.to_dense(theirs.ids)
                    if (rows < 0).any():
                        raise ValueError(
                            f"the refit holds {int((rows < 0).sum())} "
                            f"{side} ids the live model does not: fold "
                            "their events in before the landing")
                at[side] = rows
                has[side] = np.zeros(n_live, bool)
                has[side][slice(n) if rows is None else rows] = True
                new = emap.ids[~has[side]]
                lost = [e for e in new.tolist() if e not in history]
                if lost:
                    raise ValueError(
                        f"{len(lost)} {side} ids of the live model have "
                        "neither a row in the refit nor a kept rating to "
                        f"fold one from (first: {lost[0]})")
                todo[side] = np.union1d(
                    np.asarray(asked, dtype=emap.ids.dtype), new)
            for side, fac_attr, emap, theirs, F, _ in sides:
                # (a user-only server's catalog gets its buffer here)
                self._reserve(items_side=side == "item")
                buf, n_live = self._bufs[fac_attr], len(emap)
                if at[side] is None:
                    buf[:len(theirs)] = F
                    buf[len(theirs):n_live] = 0.0
                else:
                    buf[:n_live] = 0.0
                    buf[at[side]] = F
        took["tables"], t = time.perf_counter() - t, time.perf_counter()
        for dev_attr in ("_V", "_Ud"):
            # both released before either is placed anew
            old = getattr(self, dev_attr)
            if old is not None:
                old.delete()
        for dev_attr, fac_attr, side in (("_V", "_V", "item"),
                                         ("_Ud", "_U", "user")):
            if dev_attr == "_Ud" and not both:
                continue
            nbytes = int(getattr(m, fac_attr).nbytes)
            with Stamped("live.landing.place", side=side, bytes=nbytes):
                setattr(self, dev_attr, self._place(fac_attr))
        took["place"], t = time.perf_counter() - t, time.perf_counter()
        out = {"user": ([], []), "item": ([], [])}
        rounds = calls = 0
        could = {"user": {}, "item": {}}    # ratings its last fold here used
        with Stamped("live.landing.catchup") as span:
            while True:
                folded = 0
                for side, fac_attr, emap, _, _, _ in (
                        sides if both else sides[:1]):
                    if not len(todo[side]):
                        continue
                    done, x, made = self._catch_up(
                        todo[side], side == "item", has, could[side])
                    if len(done):
                        out[side][0].append(done)
                        out[side][1].append(x)
                        has[side][emap.to_dense(done)] = True
                    folded, calls = folded + len(done), calls + made
                if not folded:
                    break
                rounds += 1
            left = sum(int((~has[side]).sum()) for side in has)
            span.set_metadata(users=sum(map(len, out["user"][0])),
                              items=sum(map(len, out["item"][0])),
                              rounds=rounds, calls=calls)
        if left:
            # (unreachable where every entity was appended by a fold: its
            # usable rating named an older entity)
            obs.emit("warning", what="live.landing",
                     reason=f"{left} entities new since the snapshot have "
                            "no usable rating: their rows are zero until "
                            "their next event")
        self.last_rows = {"users": None, "items": None}
        self.last_appended = (self.last_appended[0][:0],
                              self.last_appended[1][:0])
        took["catchup"] = time.perf_counter() - t
        width = int(m._U.shape[1])
        return {"rounds": rounds, "calls": calls, "seconds": took, **{
            side + "s": ((np.concatenate(ids), np.concatenate(x))
                         if ids else (emap.ids[:0].copy(),
                                      np.empty((0, width), np.float32)))
            for (side, _, emap, _, _, _), (ids, x) in zip(
                sides, (out["user"], out["item"]))}}

    def _catch_up(self, entities, items_side, has, could):
        """One side's part of one round of a landing's catch-up
        (:meth:`land`): those of ``entities`` (original ids, each with a
        kept history) that can use more of their kept ratings — the ones
        whose other side ``has`` a row in the landed tables — than
        ``could`` says their last fold of this landing did (none: 0) are
        folded over them, in calls of at most ``CATCHUP_ROWS`` entities
        (a shape ``prewarm`` ran), the rows written into the host's
        table and the server's own on the device.  Returns ``(the
        entities folded, their rows, the calls made)``."""
        m = self.model
        side = "item" if items_side else "user"
        emap, fixed_map = ((m._item_map, m._user_map) if items_side
                           else (m._user_map, m._item_map))
        history = self._item_history if items_side else self._history
        usable_rows = has["user" if items_side else "item"]
        hists = [history[e] for e in entities.tolist()]
        _look_again([h for h in hists
                     if h.unknown and h.looked < len(fixed_map)], fixed_map)
        packs = []
        for h in hists:
            rows, stars = h.usable()
            ok = usable_rows[rows]
            packs.append((rows[ok], stars[ok]))
        lens = np.array([len(rows) for rows, _ in packs])
        used = self._used[side]
        more = np.zeros(len(lens), bool)
        for k, (e, n_ok) in enumerate(zip(entities.tolist(),
                                          lens.tolist())):
            used[e] = n_ok
            more[k] = n_ok > could.get(e, 0)
            could[e] = n_ok
        fold = np.flatnonzero(more)
        if not len(fold):
            return entities[:0], np.empty((0, m._U.shape[1]),
                                          np.float32), 0
        F, YtY = self._fixed(items_side), self.yty(items_side)
        dev_attr = "_V" if items_side else "_Ud"
        table = getattr(m, "_V" if items_side else "_U")
        x, calls = np.empty((len(fold), F.shape[1]), np.float32), 0
        dense = emap.to_dense(entities[fold])
        for lo in range(0, len(fold), CATCHUP_ROWS):
            part = np.arange(lo, min(lo + CATCHUP_ROWS, len(fold)))
            for sel in self._calls(lens[fold[part]]):
                sel = part[sel]
                n_pad = pad_for(len(sel))
                w = rung_for(int(lens[fold[sel]].max()), self._widths)
                rows = _packed((packs[i] for i in fold[sel].tolist()),
                               n_pad, w)
                with Stamped("live.landing.catchup.call", side=side + "s",
                             rows=n_pad, width=w):
                    solved = self._fold(F, rows, YtY)
                    x[sel] = np.asarray(solved)[:len(sel)]
                calls += 1
                table[dense[sel]] = x[sel]
                if getattr(self, dev_attr) is not None:
                    self._write(dev_attr, dense[sel], x[sel], placed=solved)
        return entities[fold], x, calls

    def latency(self, q=0.5, skip_warmup=False):
        """Latency quantile over processed batches.  ``skip_warmup`` drops
        the first batch (jit compile) — what latency benchmarks want."""
        stats = list(self.stats)
        if skip_warmup:
            stats = stats[1:]
        lat = sorted(s[2] for s in stats)
        if not lat:
            return float("nan")
        return lat[min(len(lat) - 1, int(len(lat) * q))]

    def p50_latency(self):
        return self.latency(0.5)


# a history nothing has been put in yet: no buffer to write into
_NO_ROWS, _NO_STARS = np.empty(0, np.int32), np.empty(0, np.float32)


class _Ratings:
    """One entity's ratings in the form the fold program takes them: the
    other side's TABLE ROWS (int32; -1 where the id map does not hold the
    id yet) and the stars, contiguous, in arrival order, in buffers with
    room to grow (``core.ratings.growth_room``).  An id keeps its row for
    good (``IdMap.append`` only adds rows at the end), so a row is looked
    up once, when its event arrives; the original id is kept only where
    the row is -1 (``unknown``: place -> id), and looked up again only
    once the map has grown (``looked``: its length when these last
    looked; :func:`_look_again`)."""

    __slots__ = ("rows", "stars", "n", "unknown", "looked")

    def __init__(self, rows=_NO_ROWS, stars=_NO_STARS, n=0):
        # buffers, and how many of their entries are ratings
        self.rows, self.stars, self.n = rows, stars, n
        self.unknown, self.looked = {}, 0

    def extend(self, rows, ids, stars, grown, lacking=True):
        """The events ``(rows, ids, stars)`` — their table rows as a map
        of ``grown`` ids gave them (``lacking``: some may have none),
        their original ids — behind these.  An entity's first events'
        arrays ARE its history until it is rated again: only then is
        there something to copy, into buffers with room."""
        n, most = self.n, self.n + len(rows)
        if not len(self.rows):
            self.rows, self.stars = rows, stars
        else:
            if most > len(self.rows):
                size = most + int(growth_room(most))
                self.rows, self.stars = (
                    _resized(a, n, size) for a in (self.rows, self.stars))
            self.rows[n:most], self.stars[n:most] = rows, stars
        self.n = most
        if lacking:
            if not self.unknown:
                self.looked = grown
            for k in np.flatnonzero(rows < 0).tolist():
                self.unknown[n + k] = ids[k]

    def rate(self, rows, ids, stars, grown, lacking=True):
        """:meth:`extend` under the rule ONE rating an id: an event on an
        id these hold already — or an earlier event of the same ones does
        — replaces that rating's stars and adds nothing.  Rows are
        compared with rows, original ids only among the entries without
        one.  Returns which of the events added nothing (their places:
        mostly none)."""
        n, old, at = self.n, [], {}
        for k, (row, original, star) in enumerate(
                zip(rows.tolist(), ids.tolist(), stars.tolist())):
            had = next((p for p, i in self.unknown.items() if i == original),
                       None) if self.unknown else None
            if had is None and row >= 0:
                had = next(iter(
                    (self.rows[:n] == row).nonzero()[0].tolist()), None)
            if had is not None:
                self.stars[had] = star
                old.append(k)
            elif original in at:
                stars[at[original]] = star
                old.append(k)
            else:
                at[original] = k
        if old:
            new = np.ones(len(rows), bool)
            new[old] = False
            rows, ids, stars = rows[new], ids[new], stars[new]
        self.extend(rows, ids, stars, grown, lacking)
        return old

    def usable(self):
        """``(rows, stars)`` of the ratings whose other side has a row."""
        rows, stars = self.rows[:self.n], self.stars[:self.n]
        if not self.unknown:
            return rows, stars
        known = rows >= 0
        return rows[known], stars[known]


def _packed(ratings, n_pad, w):
    """``(cols, vals, mask)``, the planes of the ONE ``int32[3, n_pad, w]``
    host array a call of the fold-in program takes
    (``core.foldin.pack_rows``), holding ``ratings`` — one ``(table rows,
    stars)`` an entity, each at most ``w`` long — as slice copies, an
    entity a row."""
    rows = cols, vals, mask = planes(np.zeros((3, n_pad, w), dtype=np.int32))
    for k, (ids, stars) in enumerate(ratings):
        cols[k, :len(ids)] = ids
        vals[k, :len(ids)] = stars
        mask[k, :len(ids)] = 1.0
    return rows


def _resized(a, n, size, dtype=None):
    """``a[:n]`` at the start of a new array of ``size`` (of ``a``'s
    type, or of ``dtype``)."""
    out = np.empty(size, dtype or a.dtype)
    out[:n] = a[:n]
    return out


def _look_again(hists, id_map):
    """The entries of ``hists`` without a row looked up in ``id_map``
    again, ONE lookup for all of them; those it now holds take their rows
    for good.  Returns how many ids were looked up."""
    if not hists:
        return 0
    ids = [i for h in hists for i in h.unknown.values()]
    rows = iter(id_map.to_dense(ids).tolist())
    for h in hists:
        for at in list(h.unknown):
            row = next(rows)
            if row >= 0:
                h.rows[at] = row
                del h.unknown[at]
        h.looked = len(id_map)
    return len(ids)
