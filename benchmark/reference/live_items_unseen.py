"""Plain reference for the storefront whose catalog also moves
(``amazon23-r256-share32-live-items-unseen``): the configuration's rule
replayed batch by batch in float64 — the item side of
``foldin_replay.py`` under the rule of a deployment whose ratings are
RESIDENT, the user side and the histories of ``live_unseen.py`` — and the
exact top-k of the ids a user has not rated over the catalog OF THE
GENERATION THAT ANSWERED.  numpy float64; imports nothing of the program
(``foldin.py`` and ``topk_unseen.py`` of this directory are used as they
are).

The rule (the configuration's ``guarantees``).  The admitted events come in
admission order and in the updater's batches; batch ``b`` (0, 1, ...) makes
generation ``b + 1``.  In a batch the USERS fold first, then the ITEMS, and
**a fold is over ALL of the entity's ratings, or it does not happen**:

    user u:  x = (Vu^T Vu + reg * n * I)^-1 Vu^T r   over the user's resident
             ratings and, behind them, every event of the user so far, ONE
             rating a user and item (an event on an item already rated
             replaces its stars) — those whose item has a factor now: the
             catalog as the batch BEFORE left it
    item i:  only if NONE of i's ratings is resident (a new id, or a catalog
             row no resident rating names; decided here, from the resident
             CSR): x = (Ui^T Ui + reg * n * I)^-1 Ui^T r   over every event
             of i so far whose user has a factor now — the user factors as
             THIS batch's user fold left them.  An item with resident
             ratings keeps its seeded row in every generation
             (``left_to_refit`` counts its events).

with ``n`` the ratings used (ALS-WR).  A rating whose other side has no
factor yet is kept and enters the entity's first fold after the other side
has one.  The id of an event JOINS its user's history (what a request by id
is answered without) in the batch that first gives both the user and the
item a row, which is the event's own batch unless one of them had none
(``joined``): so the first rater of a new item never gets it back, in the
segment or after the compaction that moved it.

As ``foldin_replay.py``, and for its reason (folds chain, and the chain
amplifies): with ``published`` — per batch the rows the program itself
published — each fold is made from the state the PROGRAM had, the program's
row is held to it (``fold_err``) and the state goes on from the program's
row.  An item's fold also records its conditioning, ``kappa = (sigma_max(
Ui^T Ui) + reg * n) / (reg * n)``: the first rater of a new item is folded
over the resident history in the same batch, so its row is a ridge
solution, but a float32 fold is held to ``c * kappa * 2^-24`` of its
length, not to a constant (``item_err_over_kappa``).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import foldin, topk_unseen


class Replay:
    """The replayed run; ``U0`` / ``V0`` the seeded tables (float32, not
    copied), ``(indptr, indices, stars)`` the resident ratings by user
    row."""

    def __init__(self, U0, V0, indptr, indices, stars, reg,
                 fold_items=True):
        self.U0, self.V0, self.reg = U0, V0, float(reg)
        self.indptr, self.indices, self.stars = indptr, indices, stars
        self.fold_items = fold_items
        # rule 3's "none of its ratings is resident", from the ratings
        self.rated_before = np.bincount(indices, minlength=len(V0)) > 0
        self.user_rows, self.item_rows = {}, {}     # id -> float64 row
        self.item_log = []                          # (batch, ids, rows)
        self._user_hist = {}                        # user -> [(batch, row)]
        self.user_events = {}       # user -> [(batch, item, stars)]
        self.item_events = {}       # item -> [(user, stars)], folded items
        self.n_items = []           # the catalog's size after each batch
        self.joined = []            # per batch: {(user, item)} that joined
        self._waiting = []          # (user, item) one side of which has no row
        self._used = ({}, {})
        self.batches = 0
        self.entered = self.left_to_refit = 0
        self.folds = {"first": 0, "again": 0}       # of items
        self.fold_err, self.item_kappa = ([], []), []
        self.missing = self.unasked = 0

    # -- state ------------------------------------------------------------
    def user_row(self, u, gen=None):
        """User ``u``'s float64 row as generation ``gen`` served it
        (``None``: the last), or ``None`` for a user without one yet."""
        mine = [x for b, x in self._user_hist.get(int(u), ())
                if gen is None or b < gen]
        if mine:
            return mine[-1]
        return self.U0[u].astype(np.float64) if u < len(self.U0) else None

    def _item_row(self, i):
        if i in self.item_rows:
            return self.item_rows[i]
        return self.V0[i] if i < len(self.V0) else None

    def _rows_of(self, items):
        """``(float64 rows of those of ``items`` that have one, which)``:
        the seeded rows gathered at once, the few moved ones put over."""
        ok = items < len(self.V0)
        F = np.zeros((len(items), self.V0.shape[1]))
        F[ok] = self.V0[items[ok]]
        if self.item_rows:
            moved = np.fromiter(self.item_rows, np.int64, len(self.item_rows))
            for k in np.flatnonzero(np.isin(items, moved)).tolist():
                F[k], ok[k] = self.item_rows[int(items[k])], True
        return F[ok], ok

    def resident(self, user):
        if not 0 <= user < len(self.indptr) - 1:
            return self.indices[:0], self.stars[:0]
        lo, hi = self.indptr[user], self.indptr[user + 1]
        return self.indices[lo:hi], self.stars[lo:hi]

    def ratings(self, user, gen=None):
        """``(items int64, stars float64)`` of ``user`` as of generation
        ``gen``: the resident ones and the events of batches below
        ``gen`` behind them, one rating a user and item."""
        items, stars = self.resident(user)
        mine = [e for e in self.user_events.get(int(user), ())
                if gen is None or e[0] < gen]
        items, stars = items.astype(np.int64).tolist(), stars.tolist()
        at = {i: k for k, i in enumerate(items)}
        for _, item, star in mine:
            if item in at:
                stars[at[item]] = star
            else:
                at[item] = len(items)
                items.append(item)
                stars.append(star)
        return np.array(items, np.int64), np.array(stars, np.float64)

    def ids(self, user, gen=None):
        """What ``user`` HAS RATED as of generation ``gen``: no request
        by id answered by it may return one (an id that has not joined
        yet names an item that generation's catalog does not hold)."""
        return self.ratings(user, gen)[0]

    def touched(self):
        return sorted(self.user_events)

    def rated_in_the_run(self, user):
        return [e[1] for e in self.user_events.get(int(user), ())]

    # -- one batch --------------------------------------------------------
    def step(self, users, items, stars, published=None, operand_dtype=None):
        """Batch ``self.batches`` of the run: its events in admission
        order; ``published``: ``({user: row}, {item: row})`` the program
        published for it (module docstring); ``operand_dtype``: the
        CONTROL for the item folds — what a fold with operands of that
        precision (``foldin.fold``'s) makes of the same inputs is held to
        the float64 fold in the program's row's place (``fold_err[1]``
        is then the control's), and the state goes on from the program's
        row all the same."""
        b = self.batches
        users, items = (np.asarray(a, np.int64).tolist()
                        for a in (users, items))
        stars = np.asarray(stars, np.float64).tolist()
        # users first, against the catalog as the batch before left it
        adds, again = [], 0
        for u, i, s in zip(users, items, stars):
            had = set(self.ratings(u)[0].tolist())
            if i in had:
                again += self._item_row(i) is not None
            else:
                adds.append((u, i))
            self.user_events.setdefault(u, []).append((b, i, s))
        self.entered += again
        moved, rows = [], []
        for u in sorted(set(users)):
            its, sts = self.ratings(u)
            F, ok = self._rows_of(its)
            self._count(0, u, len(F), len(self.resident(u)[0]))
            if not len(F):
                continue
            moved.append(u)
            rows.append(foldin.fold(F, np.arange(len(F)), sts[ok],
                                    self.reg))
        self._take(0, b, moved, rows, published)
        # then the items none of whose ratings is resident, against the
        # user factors as this batch's user fold left them
        moved, rows, kappa, low = [], [], [], []
        touched = []
        if self.fold_items:
            for u, i, s in zip(users, items, stars):
                if i < len(self.rated_before) and self.rated_before[i]:
                    self.left_to_refit += 1
                    continue
                self.item_events.setdefault(i, []).append((u, s))
                touched.append(i)
        for i in sorted(set(touched)):
            ok = [(u, s) for u, s in self.item_events[i]
                  if self.user_row(u) is not None]
            self._count(1, i, len(ok), 0)
            if not ok:
                continue
            F = np.stack([self.user_row(u) for u, _ in ok])
            lam = self.reg * len(ok)
            kappa.append((np.linalg.norm(F, 2) ** 2 + lam) / lam)
            self.folds["first" if self._item_row(i) is None
                       else "again"] += 1
            moved.append(i)
            rows.append(foldin.fold(F, np.arange(len(ok)),
                                    [s for _, s in ok], self.reg))
            if operand_dtype is not None:
                low.append(foldin.fold(F, np.arange(len(ok)),
                                       [s for _, s in ok], self.reg,
                                       operand_dtype=operand_dtype))
        self._take(1, b, moved, rows, published, kappa, low)
        self.n_items.append(len(self.V0) + sum(
            i >= len(self.V0) for i in self.item_rows))
        # the ids that join their users' histories with this publish
        pairs = self._waiting + adds
        ok = [self.user_row(u) is not None and self._item_row(i) is not None
              for u, i in pairs]
        self.joined.append({p for p, k in zip(pairs, ok) if k})
        self._waiting = [p for p, k in zip(pairs, ok) if not k]
        self.batches += 1

    def _count(self, side, e, usable, before):
        self.entered += usable - self._used[side].get(e, before)
        self._used[side][e] = usable

    def _take(self, side, b, moved, rows, published, kappa=None, low=()):
        """A side's folds go in together; against ``published`` the
        program's rows (or the control's, ``low``) are held to them and
        the program's taken in their place."""
        if published is not None:
            theirs = dict(published[side])
            for j, (e, x) in enumerate(zip(moved, rows)):
                p = theirs.pop(e, None)
                if p is None:           # the rule's own row stands in
                    self.missing += 1
                    continue
                p = np.asarray(p, np.float64)
                err = float(np.linalg.norm((low[j] if low else p) - x)
                            / np.linalg.norm(x))
                self.fold_err[side].append(err)
                if kappa is not None:
                    self.item_kappa.append(kappa[j])
                rows[j] = p
            self.unasked += len(theirs)
            moved = moved + sorted(theirs)
            rows = rows + [np.asarray(theirs[e], np.float64)
                           for e in moved[len(rows):]]
        if side == 0:
            self.user_rows.update(zip(moved, rows))
            for u, x in zip(moved, rows):
                self._user_hist.setdefault(u, []).append((b, x))
        else:
            self.item_rows.update(zip(moved, rows))
            if moved:
                self.item_log.append((b, list(moved), np.stack(rows)))

    # -- what a generation served -----------------------------------------
    def item_err_over_kappa(self):
        """Every item fold's relative error over its conditioning times
        the float32 unit roundoff: the ``c`` of ``rel_err <= c * kappa *
        2^-24`` that fold needed."""
        return (np.asarray(self.fold_err[1])
                / (np.asarray(self.item_kappa) * 2.0 ** -24))

    def moved_items(self):
        return np.unique(np.concatenate(
            [np.asarray(ids, np.int64) for _, ids, _ in self.item_log]
            or [np.empty(0, np.int64)]))

    def catalog_as_of(self, gen):
        """``(item ids, float64 rows, catalog size)`` of every item any
        batch moved, as generation ``gen`` served it (its seeded row, or
        left out where it did not exist yet)."""
        size = self.n_items[gen - 1] if gen > 0 else len(self.V0)
        rows = {int(i): self.V0[i].astype(np.float64)
                for i in self.moved_items() if i < len(self.V0)}
        for b, moved, x in self.item_log:
            if b < gen:
                rows.update(zip(moved, x))
        keep = np.array(sorted(rows), dtype=np.int64)
        return keep, (np.stack([rows[i] for i in keep.tolist()])
                      if len(keep) else np.zeros((0, self.V0.shape[1]))), size

    def final_catalog(self):
        size = self.n_items[-1] if self.n_items else len(self.V0)
        V = np.zeros((size, self.V0.shape[1]))
        V[:len(self.V0)] = self.V0
        for i, x in self.item_rows.items():
            V[i] = x
        return V


def replay(U0, V0, hist, users, items, stars, batch_sizes, reg,
           fold_items=True, published=None):
    """:class:`Replay` of the events in admission order, cut into batches
    of ``batch_sizes``; ``published[b]`` the rows the program published
    in batch ``b``."""
    rep, lo = Replay(U0, V0, *hist, reg, fold_items=fold_items), 0
    for b, size in enumerate(batch_sizes):
        sl = slice(lo, lo + size)
        lo += size
        rep.step(users[sl], items[sl], stars[sl],
                 None if published is None else published[b])
    return rep


def exact_topk_left(Q, gens, rep, k, excluded):
    """``(scores [n, k], ids [n, k], catalog sizes [n])`` in float64: the
    exact top-k of query ``Q[j]`` over the catalog as generation
    ``gens[j]`` served it, ``excluded[j]`` taken out before the top-k.
    The seeded catalog is scored once (``topk_unseen.exact_topk``, every
    moved item excluded there for every query); each query's list is then
    merged with the moved items' rows as of ITS generation."""
    Q64 = np.asarray(Q, np.float64)
    moved = rep.moved_items()
    base_moved = moved[moved < len(rep.V0)]
    s0, i0 = topk_unseen.exact_topk(
        Q64, rep.V0, k,
        [np.concatenate([np.asarray(e, np.int64), base_moved])
         for e in excluded])
    scores = np.full((len(Q64), k), -np.inf)
    ids = np.full((len(Q64), k), -1, np.int64)
    sizes = np.empty(len(Q64), np.int64)
    by_gen = {}
    for j, g in enumerate(np.asarray(gens).tolist()):
        if g not in by_gen:
            by_gen[g] = rep.catalog_as_of(g)
        m_ids, m_rows, sizes[j] = by_gen[g]
        left = ~np.isin(m_ids, np.asarray(excluded[j], np.int64))
        cand_i = np.concatenate([i0[j], m_ids[left]])
        cand_s = np.concatenate([s0[j], m_rows[left] @ Q64[j]])
        top = np.lexsort((cand_i, -cand_s))[:k]
        scores[j], ids[j] = cand_s[top], cand_i[top]
    ids[np.isneginf(scores)] = -1
    return scores, ids, sizes


def own_scores(Q, gens, ids, rep):
    """float64 dot products of each query with the ids it was served, each
    id's row as of the query's generation (``nan`` for an id that
    generation's catalog did not hold)."""
    Q64 = np.asarray(Q, np.float64)
    out = np.full(np.shape(ids), np.nan)
    by_gen = {}
    for j, g in enumerate(np.asarray(gens).tolist()):
        if g not in by_gen:
            m_ids, m_rows, size = rep.catalog_as_of(g)
            by_gen[g] = (dict(zip(m_ids.tolist(), m_rows)), size)
        rows, _ = by_gen[g]
        for c, i in enumerate(np.asarray(ids[j]).tolist()):
            row = rows.get(i)
            if row is None and 0 <= i < len(rep.V0):
                row = rep.V0[i].astype(np.float64)
            if row is not None:
                out[j, c] = Q64[j] @ row
    return out


def row_rel_err(x, x64):
    """``|x - x64| / |x64|`` (Euclidean), the error of one published row."""
    x64 = np.asarray(x64, np.float64)
    return float(np.linalg.norm(np.asarray(x, np.float64) - x64)
                 / max(np.linalg.norm(x64), 1e-300))
