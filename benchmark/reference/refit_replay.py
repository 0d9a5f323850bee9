"""Plain reference for the live deployment on which REFITS land: the
sibling's replay (``foldin_replay.py``: the configuration's rule batch by
batch in float64, each fold from the rows the program published) extended
by the landing.  Imports nothing of the program.

The run is a list of STEPS in the order the program published them: a
batch of so many admitted events, or a landing.  A batch follows the
sibling's rule to the letter (users first, then items; a fold over all of
the entity's ratings whose other side has a row when it runs; ALS-WR
weighting; new entities take the next table rows in ascending order of
their ids).  A landing ``(snapshot, U', V')``:

1. the tables become the refit's.  ``U'`` holds the first ``len(U')`` rows
   of the user table — the users the model held when the refit's data was
   cut — and ``V'`` likewise; every row a fold of the run had made is
   forgotten.  An entity appended to a table since (its row number is
   ``>= len`` of the refit's table) has NO row now; it keeps its row number.
2. the CATCH-UP.  Folded again, by the batches' own rule, is every entity
   with an event admitted at or after ``snapshot`` (and folded by a batch
   before the landing) and every entity without a row: first the users,
   each over ALL its kept ratings whose item has a row now (the refit's
   catalog), then the items, each over all its kept ratings whose user has
   a row now (the refit's user table with the users' catch-up in it).  A
   rating that names an entity without a row cannot be used until that
   entity's own side gave it one: so the rounds go on — users, then items,
   each folding again whoever can now use MORE of its kept ratings than its
   last fold of this landing could — until a round folds nobody.
3. every other entity keeps the refit's row, bit for bit.

No rating is counted as ENTERING a fold in a catch-up (``entered`` counts a
rating and side once, when a batch's fold first uses it); what a later fold
counts is measured against what the catch-up could use.

A step that publishes is a GENERATION: 0 the start, ``g`` the ``g``-th step.
An ERA is the stretch between two landings: a base catalog (the seeded one,
then each refit's) and the rows folded over it since.  With ``published``
(per step, the rows the program itself published; for a landing the rows
its catch-up made) every fold is computed in float64 from the state the
PROGRAM had, the program's row is held to it (``fold_err`` a batch's folds,
``catchup_err`` a landing's) and the state takes the program's row, as in
the sibling and for its reason: folds chain, and the chain amplifies.

Controls: ``operand_dtype`` rounds a fold's operands one precision step
down (``reference/foldin.py``); ``catchup`` = ``"none"`` leaves the
catch-up out (the events since the snapshot are lost until their entities
are rated again) and ``"stale"`` folds it over the tables as they stood
BEFORE the landing.

Base ids are their own table rows (``0 .. len(U0) - 1``); an entity
appended in the run has the row the rule gave it (``row_of``).  Answers are
compared in TABLE ROWS, which is what the engine answers with.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import foldin as ref_foldin


class Era:
    """A base catalog and what was folded over it until the next landing:
    ``first`` the generation that installed it (0: the start), ``U``/``V``
    the tables (float32, not copied), ``item_log`` one ``(generation, table
    rows, float64 rows)`` per step that moved items."""

    def __init__(self, first, U, V):
        self.first, self.U, self.V = first, U, V
        self.item_log = []

    def moved(self):
        return np.unique(np.concatenate(
            [rows for _, rows, _ in self.item_log]
            or [np.empty(0, np.int64)]))


class Replay:
    """The replayed run.  ``eras``; ``row_of`` ``({user id: row}, {item id:
    row})`` of the entities appended in the run; ``rows`` ``({id: float64
    row}, {...})`` folded in the LAST era; ``n_items[g - 1]`` the catalog's
    size after step ``g``; ``touched`` ``(user ids, item ids)`` with an event
    at or after the last landing's snapshot and the entities new since it
    (all of them where no refit landed); ``entered``, ``widest`` as the
    sibling's.  Against ``published``: ``fold_err`` / ``catchup_err``
    ``(user errors, item errors)``, ``missing`` folds the rule asks for
    that no row was published for, ``unasked`` rows published for an entity
    the rule folds nothing for in that step (batches and landings
    apart)."""

    def __init__(self, U0, V0):
        self.eras = [Era(0, U0, V0)]
        self.row_of = ({}, {})
        self.rows = ({}, {})
        self.n_rows = [len(U0), len(V0)]
        self.n_items = []
        self.entered = self.widest = 0
        self.fold_err, self.catchup_err = ([], []), ([], [])
        self.missing = self.unasked = 0
        self.catchup_missing = self.catchup_unasked = 0
        self.touched = (set(), set())
        self.catchup_sizes = []     # per landing: (events since the
        #                             snapshot, user folds, item folds)

    def table_row(self, side, e):
        """The table row of entity ``e`` (None: it has none)."""
        if e < len(self.eras[0].U if side == 0 else self.eras[0].V):
            return e
        return self.row_of[side].get(e)

    def row(self, side, e):
        """Entity ``e``'s factor as the last step left it (None: none)."""
        if e in self.rows[side]:
            return self.rows[side][e]
        base = self.eras[-1].U if side == 0 else self.eras[-1].V
        at = self.table_row(side, e)
        return base[at] if at is not None and at < len(base) else None

    def era_of(self, gen):
        return max((e for e in self.eras if e.first <= gen),
                   key=lambda e: e.first)

    def catalog_as_of(self, gen):
        """``(era, table rows, float64 rows, catalog size)`` of every item
        folded in generation ``gen``'s era up to it, as that generation
        served it."""
        era = self.era_of(gen)
        rows = {}
        for g, moved, x in era.item_log:
            if g <= gen:
                rows.update(zip(moved.tolist(), x))
        keep = np.array(sorted(rows), dtype=np.int64)
        size = self.n_items[gen - 1] if gen > 0 else len(self.eras[0].V)
        width = era.V.shape[1]
        return (era, keep, np.stack([rows[i] for i in keep.tolist()])
                if len(keep) else np.empty((0, width)), size)

    def final_catalog(self):
        """The whole catalog after the last step, float64, by table row."""
        era = self.eras[-1]
        size = self.n_items[-1] if self.n_items else len(era.V)
        V = np.zeros((size, era.V.shape[1]))
        V[:min(size, len(era.V))] = era.V[:size]
        for i, x in self.rows[1].items():
            V[self.table_row(1, i)] = x
        return V


def _fold_side(out, side, entities, hist, used, reg, operand_dtype,
               count, rows_of=None):
    """The folds of one side of one step: ``(entities folded, their
    float64 rows)`` over the other side as ``rows_of`` (default: the state)
    gives it now; ``used`` takes what each could use, ``count`` adds the
    newly used to ``entered``."""
    other = 1 - side
    rows_of = rows_of or out.row
    moved, rows = [], []
    for e in entities:
        ok = [(o, r) for o, r in hist[side].get(e, ())
              if rows_of(other, o) is not None]
        if count:
            out.entered += len(ok) - used[side].get(e, 0)
        used[side][e] = len(ok)
        if not ok:
            continue
        F = np.stack([rows_of(other, o) for o, _ in ok])
        out.widest = max(out.widest, len(ok))
        moved.append(e)
        rows.append(ref_foldin.fold(F, np.arange(len(ok)),
                                    [r for _, r in ok], reg,
                                    operand_dtype=operand_dtype))
    return moved, rows


def _held_to(moved, rows, theirs, errs):
    """The rule's rows against the program's ``theirs`` (``{id: row}``; a
    row held to is taken out of it): each error appended to ``errs``, the
    program's row taken in the rule's place.  Returns the folds the rule
    asks for that the program published no row for."""
    missing = 0
    for j, (e, x) in enumerate(zip(moved, rows)):
        p = theirs.get(e)
        if isinstance(p, list):     # a landing's: one row a fold, in order
            p = p.pop(0)
            if not theirs[e]:
                del theirs[e]
        else:
            theirs.pop(e, None)
        if p is None:               # the rule's own row stands in
            missing += 1
            continue
        p = np.asarray(p, np.float64)
        errs.append(float(np.linalg.norm(p - x) / np.linalg.norm(x)))
        rows[j] = p
    return missing


def _unasked(theirs):
    """What is left of the program's rows: ``(ids, float64 rows)`` nobody
    asked for — served all the same, so the state takes them."""
    extra = sorted(theirs)
    return extra, [np.asarray(theirs[e][-1] if isinstance(theirs[e], list)
                              else theirs[e], np.float64) for e in extra]


def _install(out, side, gen, moved, rows):
    """A side's folds go in together; new entities take the next table
    rows in ascending order of their ids."""
    for e, x in zip(moved, rows):
        if out.table_row(side, e) is None:
            out.row_of[side][e] = out.n_rows[side]
            out.n_rows[side] += 1
        out.rows[side][e] = x
    if out.journal_lists[gen - 1]:
        for e, x in zip(moved, rows):
            out.journal[gen - 1][side].setdefault(e, []).append(x)
    else:
        out.journal[gen - 1][side].update(zip(moved, rows))
    if moved and side == 1:
        out.eras[-1].item_log.append((gen, np.array(
            [out.table_row(1, e) for e in moved], np.int64),
            np.stack(rows)))


def replay(U0, V0, users, items, stars, steps, reg, fold_items=True,
           operand_dtype=None, published=None, catchup="rule"):
    """:class:`Replay` of the events ``(users[j], items[j], stars[j])`` in
    admission order through ``steps``: an ``int`` is a batch of that many
    events, a ``dict(snapshot=, U=, V=)`` a landing (module docstring;
    ``snapshot`` counts the same events: a stream with quarantined events
    would need their places taken out).  ``published[s]`` the rows the
    program published in step ``s``: ``({user: row}, {item: row})``, for a
    landing ``({user: [row, ...]}, {item: [...]})`` — the rows its
    catch-up made of that entity, in order (a later round may fold one
    again); the replay's own, for a control to stand in the program's
    place, are its ``journal``."""
    out = Replay(U0, V0)
    out.journal = [({}, {}) for _ in steps]
    out.journal_lists = [isinstance(step, dict) for step in steps]
    hist, used = ({}, {}), ({}, {})
    events = np.stack([np.asarray(users, np.int64),
                       np.asarray(items, np.int64)], axis=1)
    stars = np.asarray(stars, np.float64)
    out.touched = (set(events[:, 0].tolist()), set(events[:, 1].tolist()))
    sides = (0, 1) if fold_items else (0,)
    lo = 0
    for s, step in enumerate(steps):
        gen = s + 1
        theirs = (tuple(dict(p) for p in published[s])
                  if published is not None else None)
        if not isinstance(step, dict):
            batch = range(lo, lo + step)
            lo += step
            for side in sides:
                for j in batch:
                    hist[side].setdefault(int(events[j, side]), []).append(
                        (int(events[j, 1 - side]), stars[j]))
                moved, rows = _fold_side(
                    out, side, sorted({int(events[j, side]) for j in batch}),
                    hist, used, reg, operand_dtype, count=True)
                if theirs is not None:
                    out.missing += _held_to(moved, rows, theirs[side],
                                            out.fold_err[side])
                    extra, extra_rows = _unasked(theirs[side])
                    out.unasked += len(extra)
                    moved, rows = moved + extra, rows + extra_rows
                _install(out, side, gen, moved, rows)
            out.n_items.append(out.n_rows[1])
            continue
        # -- a landing ---------------------------------------------------
        before = None
        if catchup == "stale":                # the state as it stood
            before = Replay.__new__(Replay)
            before.__dict__ = {**out.__dict__, "eras": list(out.eras),
                               "rows": tuple(dict(r) for r in out.rows)}
        out.eras.append(Era(gen, step["U"], step["V"]))
        out.rows = ({}, {})
        since = range(min(step["snapshot"], lo), lo)
        todo = tuple(
            {int(events[j, side]) for j in since}
            | {e for e, at in out.row_of[side].items()
               if at >= len(step["U"] if side == 0 else step["V"])}
            for side in (0, 1))
        out.touched = (todo[0] | set(events[lo:, 0].tolist()),
                       todo[1] | set(events[lo:, 1].tolist()))
        could = ({}, {})        # ratings an entity's last fold here used
        folds = [0, 0]
        while catchup != "none":
            folded = 0
            for side in sides:
                state = before.row if before is not None else out.row
                who = []
                for e in sorted(todo[side]):
                    n_ok = sum(state(1 - side, o) is not None
                               for o, _ in hist[side].get(e, ()))
                    if n_ok > could[side].get(e, 0):
                        who.append(e)
                    could[side][e] = used[side][e] = n_ok
                moved, rows = _fold_side(
                    out, side, who, hist, used, reg, operand_dtype,
                    count=False, rows_of=state)
                if theirs is not None:
                    out.catchup_missing += _held_to(
                        moved, rows, theirs[side], out.catchup_err[side])
                _install(out, side, gen, moved, rows)
                folded += len(moved)
                folds[side] += len(moved)
            if not folded or before is not None:
                break
        out.catchup_sizes.append((len(since), folds[0], folds[1]))
        if theirs is not None:
            for side in sides:
                extra, extra_rows = _unasked(theirs[side])
                out.catchup_unasked += len(extra)
                _install(out, side, gen, extra, extra_rows)
        out.n_items.append(out.n_rows[1])
    return out


def generation_topk(Q, gens, rep, k, block=64):
    """``(scores [n, k], table rows [n, k], catalog sizes [n])`` in float64:
    the exact top-k of query ``Q[j]`` over the catalog as generation
    ``gens[j]`` served it.  Each era's base catalog is scored once, in row
    blocks, for the queries of that era, with every item folded in the era
    masked out; each query's list is then merged with those items' rows as
    of ITS generation."""
    Q64 = np.asarray(Q, np.float64)
    gens = np.asarray(gens)
    scores = np.empty((len(Q64), k))
    ids = np.empty((len(Q64), k), dtype=np.int64)
    sizes = np.empty(len(Q64), dtype=np.int64)
    eras = np.array([rep.eras.index(rep.era_of(int(g))) for g in gens])
    for n_era, era in enumerate(rep.eras):
        mine = np.flatnonzero(eras == n_era)
        if not len(mine):
            continue
        V64 = np.asarray(era.V, np.float64)
        moved = era.moved()
        base_moved = moved[moved < len(V64)]
        by_gen = {}
        for lo in range(0, len(mine), block):
            part_q = mine[lo:lo + block]
            s = Q64[part_q] @ V64.T
            s[:, base_moved] = -np.inf
            part = np.argpartition(-s, k - 1, axis=1)[:, :k]
            for j, q in enumerate(part_q.tolist()):
                g = int(gens[q])
                if g not in by_gen:
                    by_gen[g] = rep.catalog_as_of(g)
                _, m_ids, m_rows, sizes[q] = by_gen[g]
                # (a base row past the catalog's size is a spare one)
                cand_i = np.concatenate([part[j], m_ids])
                cand_s = np.concatenate([s[j, part[j]], m_rows @ Q64[q]])
                top = np.argsort(-cand_s, kind="stable")[:k]
                scores[q], ids[q] = cand_s[top], cand_i[top]
    return scores, ids, sizes


def own_scores(Q, gens, ids, rep):
    """float64 dot products of each query with the table rows it was
    served, each row as of the query's generation (``nan`` for a row that
    generation's catalog did not hold)."""
    Q64 = np.asarray(Q, np.float64)
    out = np.full(ids.shape, np.nan)
    by_gen = {}
    for j, g in enumerate(np.asarray(gens).tolist()):
        if g not in by_gen:
            era, m_ids, m_rows, size = rep.catalog_as_of(g)
            by_gen[g] = (era, dict(zip(m_ids.tolist(), m_rows)), size)
        era, rows, size = by_gen[g]
        for c, i in enumerate(ids[j].tolist()):
            if 0 <= i < size:
                row = rows.get(i)
                if row is None and i < len(era.V):
                    row = era.V[i].astype(np.float64)
                if row is not None:
                    out[j, c] = Q64[j] @ row
    return out


def query_of(rep, gen, user_row):
    """The float32 row generation ``gen`` held for a user NO event touched:
    its era's base table's."""
    return rep.era_of(gen).U[user_row]


def recall_by_score(own, ids, ref_scores, largest):
    """Recall@k that counts a TIE at the k-th place as one place: the mean
    share of an answer's ``k`` ids (``ids [n, k]``, ``-1`` none; a repeated
    id counts once) whose float64 score ``own [n, k]`` (``nan``: a row its
    generation did not hold) reaches the exact k-th best score
    ``ref_scores[:, -1]``, to float64 rounding (1e-9 of ``largest``).
    Where no two catalog rows score alike this IS the share of the exact
    top-k ids that were returned; a catch-up makes rows that do — every
    item whose one rater is the same user, rated alike, is folded over that
    user's one row and comes out the same row bit for bit — and which of
    two equal rows stands in a top-k list is the sort's choice, in the
    reference as in the program: an id-by-id count calls that a miss."""
    own = np.asarray(own, np.float64)
    ids = np.asarray(ids)
    reach = np.nan_to_num(own, nan=-np.inf) >= (
        np.asarray(ref_scores, np.float64)[:, -1:] - 1e-9 * largest)
    first = np.ones(ids.shape, bool)        # the first place of each id
    order = np.argsort(ids, axis=1, kind="stable")
    ranked = np.take_along_axis(ids, order, axis=1)
    again = np.zeros(ids.shape, bool)
    again[:, 1:] = ranked[:, 1:] == ranked[:, :-1]
    np.put_along_axis(first, order, ~again, axis=1)
    return float((reach & first & (ids >= 0)).sum(axis=1).mean()
                 / ids.shape[1])
