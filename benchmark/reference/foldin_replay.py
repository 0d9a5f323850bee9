"""Plain reference for the live deployment with its catalog moving: the
configuration's rule replayed batch by batch in float64, from the seeded
factors.  Imports nothing of the program.

The rule (the configuration's ``guarantees``).  The admitted events come in
admission order and in the updater's batches.  In a batch the USERS fold
first, then the ITEMS:

    user u:  x = (Vu^T Vu + reg * n * I)^-1 Vu^T r    over u's ratings so far
             whose item has a factor now — the catalog as the batch BEFORE
             left it
    item i:  x = (Ui^T Ui + reg * n * I)^-1 Ui^T r    over i's ratings so far
             whose user has a factor now — the user factors as THIS batch's
             user fold left them

with ``n`` the ratings used (ALS-WR weighting).  A rating whose other side
has no factor yet is kept and enters the entity's first fold after the other
side has one; an entity with no usable rating gets no factor.  Entities new
in a batch take the next rows of their table in ascending order of their
ids.  ``reference/foldin.py::fold`` solves each system (a test holds the two
equal where no item is touched).

What comes back is the base tables and, per batch, the rows it changed — a
few thousand rows, not one catalog per generation — with the means to ask
for a catalog row, or the whole top-k, AS OF a generation.

Folds chain — an item's row feeds its users' next folds, and theirs its next
— and the chain is not a contraction: where a hot item's raters rated
nothing else, their rows lie along the item's own and its next fold divides
their small differences by ``reg * n``.  Seed 484613077 of the cell carries a
float32 rounding of 3e-5 of a row's length to 20 % within five batches
(PERF.md section 2), so a trajectory replayed from the seeded factors alone
says nothing of a program that folds every system right.  With ``published``
— per batch, the rows the program itself published, ``({user: row}, {item:
row})`` — each fold is therefore computed in float64 from the state the
PROGRAM had (guarantee 7: "against the user factors as the same batch's user
fold left them"), the program's row is held to it (``fold_err``: one
relative error for every fold of the run), and the state takes the program's
row: a generation's catalog is then what that generation served, and an
error is the error of one fold, not of a history.

``operand_dtype`` rounds every fold's gathered rows and ratings to a lower
precision first (``reference/foldin.py``): the CONTROL.  Its trajectory, read
back through :func:`published_of`, stands in the program's place.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import foldin as ref_foldin


class Replay:
    """The replayed run.  ``U0``/``V0`` the seeded tables (float32, not
    copied); ``user_rows`` / ``item_rows`` ``{id: float64 row}`` as the last
    batch left them; ``item_log`` one ``(batch, item ids, rows)`` per batch
    that moved items; ``n_items[b]`` the catalog's size after batch ``b``;
    ``entered`` how many (rating, side) pairs entered a fold; ``waiting``
    how many still wait; ``widest`` the most ratings one fold was over.
    ``user_log`` as ``item_log``.  Against ``published`` rows: ``fold_err``
    ``(user errors, item errors)``, one per fold — the published row against
    the float64 fold, as a share of the fold's length; ``missing`` folds the
    rule asks for that no row was published for; ``unasked`` rows published
    for an entity the rule folds nothing for in that batch."""

    def __init__(self, U0, V0):
        self.U0, self.V0 = U0, V0
        self.user_rows, self.item_rows = {}, {}
        self.user_log, self.item_log, self.n_items = [], [], []
        self.entered = self.waiting = self.widest = 0
        self.fold_err = ([], [])
        self.missing = self.unasked = 0

    def row(self, side, e):
        rows, base = ((self.user_rows, self.U0) if side == 0
                      else (self.item_rows, self.V0))
        if e in rows:
            return rows[e]
        return base[e] if e < len(base) else None

    def moved_items(self):
        return np.unique(np.concatenate(
            [ids for _, ids, _ in self.item_log] or [np.empty(0, np.int64)]))

    def catalog_as_of(self, batch):
        """``(item ids, float64 rows, catalog size)`` of every item any
        batch moved, as generation ``batch`` (the number of batches
        published, 0: none) served it: its last override up to then, else
        its seeded row, left out where it did not exist yet."""
        ids = self.moved_items()
        size = self.n_items[batch - 1] if batch > 0 else len(self.V0)
        rows = {int(i): self.V0[i].astype(np.float64)
                for i in ids if i < len(self.V0)}
        for b, moved, x in self.item_log:
            if b < batch:
                rows.update(zip(moved.tolist(), x))
        keep = np.array(sorted(rows), dtype=np.int64)
        return keep, np.stack([rows[i] for i in keep.tolist()]), size

    def final_catalog(self):
        """The whole catalog after the last batch, float64."""
        size = self.n_items[-1] if self.n_items else len(self.V0)
        V = np.zeros((size, self.V0.shape[1]))
        V[:len(self.V0)] = self.V0
        for i, x in self.item_rows.items():
            V[i] = x
        return V


def replay(U0, V0, users, items, stars, batch_sizes, reg, fold_items=True,
           operand_dtype=None, published=None):
    """:class:`Replay` of the events ``(users[j], items[j], stars[j])`` in
    admission order, cut into batches of ``batch_sizes``; ``published[b]``
    the rows the program published in batch ``b`` (module docstring)."""
    out = Replay(U0, V0)
    n_rows = [len(U0), len(V0)]                      # users, items with a row
    hist = ({}, {})                                  # side -> id -> [(o, r)]
    used = ({}, {})
    dense = ({}, {})         # the table row of an entity appended in the run
    events = np.stack([np.asarray(users, np.int64),
                       np.asarray(items, np.int64)], axis=1)
    stars = np.asarray(stars, np.float64)
    lo = 0
    for b, size in enumerate(batch_sizes):
        batch = range(lo, lo + size)
        lo += size
        for side in (0, 1) if fold_items else (0,):
            other = 1 - side
            for j in batch:
                hist[side].setdefault(int(events[j, side]), []).append(
                    (int(events[j, other]), stars[j]))
            moved, rows = [], []
            for e in sorted({int(events[j, side]) for j in batch}):
                ok = [(o, r) for o, r in hist[side][e]
                      if out.row(other, o) is not None]
                out.entered += len(ok) - used[side].get(e, 0)
                used[side][e] = len(ok)
                if not ok:
                    continue
                F = np.stack([out.row(other, o) for o, _ in ok])
                out.widest = max(out.widest, len(ok))
                moved.append(e)
                rows.append(ref_foldin.fold(
                    F, np.arange(len(ok)), [r for _, r in ok], reg,
                    operand_dtype=operand_dtype))
            # all of a side's folds read the other side as it was; the rows
            # go in together, new entities in ascending order of their ids
            target = out.user_rows if side == 0 else out.item_rows
            if published is not None:
                theirs = dict(published[b][side])
                for j, (e, x) in enumerate(zip(moved, rows)):
                    p = theirs.pop(e, None)
                    if p is None:       # the rule's own row stands in
                        out.missing += 1
                        continue
                    p = np.asarray(p, np.float64)
                    out.fold_err[side].append(
                        float(np.linalg.norm(p - x) / np.linalg.norm(x)))
                    rows[j] = p
                # a row nobody asked for is served all the same
                out.unasked += len(theirs)
                moved += sorted(theirs)
                rows += [np.asarray(theirs[e], np.float64)
                         for e in moved[len(rows):]]
            for e, x in zip(moved, rows):
                if out.row(side, e) is None:
                    dense[side][e] = n_rows[side]
                    n_rows[side] += 1
                target[e] = x
            if moved:
                (out.user_log, out.item_log)[side].append(
                    (b, np.array(moved, np.int64), np.stack(rows)))
        out.n_items.append(n_rows[1])
    out.dense_users, out.dense_items = dense
    out.waiting = sum(len(h) - used[s].get(e, 0)
                      for s in (0, 1) for e, h in hist[s].items())
    return out


def published_of(rep, n_batches):
    """What a program whose trajectory is ``rep`` published, batch by batch:
    ``[({user: row}, {item: row})]`` — the control's journal."""
    out = [({}, {}) for _ in range(n_batches)]
    for side, log in enumerate((rep.user_log, rep.item_log)):
        for b, ids, rows in log:
            out[b][side].update(zip(ids.tolist(), rows))
    return out


def generation_topk(Q, gens, rep, k, block=64):
    """``(scores [n, k], ids [n, k], catalog sizes [n])`` in float64: the
    exact top-k of query ``Q[j]`` over the catalog as generation
    ``gens[j]`` served it.  The seeded catalog is scored once, in row
    blocks, with every item any batch moved masked out; each query's list is
    then merged with those items' rows as of ITS generation."""
    Q64 = np.asarray(Q, np.float64)
    V64 = np.asarray(rep.V0, np.float64)
    moved = rep.moved_items()
    base_moved = moved[moved < len(V64)]
    scores = np.empty((len(Q64), k))
    ids = np.empty((len(Q64), k), dtype=np.int64)
    sizes = np.empty(len(Q64), dtype=np.int64)
    by_gen = {}
    for lo in range(0, len(Q64), block):
        s = Q64[lo:lo + block] @ V64.T
        s[:, base_moved] = -np.inf
        part = np.argpartition(-s, k - 1, axis=1)[:, :k]
        for j in range(len(s)):
            g = int(gens[lo + j])
            if g not in by_gen:
                by_gen[g] = rep.catalog_as_of(g)
            m_ids, m_rows, sizes[lo + j] = by_gen[g]
            cand_i = np.concatenate([part[j], m_ids])
            cand_s = np.concatenate([s[j, part[j]],
                                     m_rows @ Q64[lo + j]])
            top = np.argsort(-cand_s, kind="stable")[:k]
            scores[lo + j], ids[lo + j] = cand_s[top], cand_i[top]
    return scores, ids, sizes


def own_scores(Q, gens, ids, rep):
    """float64 dot products of each query with the ids it was served, each
    id's row as of the query's generation (``nan`` for an id that
    generation's catalog did not hold)."""
    Q64 = np.asarray(Q, np.float64)
    out = np.full(ids.shape, np.nan)
    by_gen = {}
    for j, g in enumerate(np.asarray(gens).tolist()):
        if g not in by_gen:
            m_ids, m_rows, size = rep.catalog_as_of(g)
            by_gen[g] = (dict(zip(m_ids.tolist(), m_rows)), size)
        rows, size = by_gen[g]
        for c, i in enumerate(ids[j].tolist()):
            if 0 <= i < size:
                row = rows.get(i)
                out[j, c] = Q64[j] @ (row if row is not None
                                      else rep.V0[i].astype(np.float64))
    return out
