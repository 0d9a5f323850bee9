"""Plain reference for the live deployment that knows its histories
(``amazon23-r256-share32-live-unseen``): what a user HAS RATED as of a given
generation, the float64 fold over all of it, and the exact top-k of the ids
left.  numpy float64; imports nothing of the program (``foldin.py`` and
``topk_unseen.py`` of this directory are used as they are).

A user's ratings as of generation ``seq`` are the resident ones (the CSR the
run was set up with) and, behind them in arrival order, every event of that
user published at or before ``seq``; ONE rating a user and item: an event on
an item the user has rated already replaces that rating's stars and adds no
id.  The fold is ``foldin.fold`` over all of them (ALS-WR: the ridge is
``reg`` times their number), the answer ``topk_unseen.exact_topk`` of the
query with their items excluded.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import foldin, topk_unseen


class Histories:
    """The resident histories and the events published on top of them."""

    def __init__(self, indptr, indices, stars):
        self.indptr, self.indices, self.stars = indptr, indices, stars
        self.events = {}        # user -> [(seq, item, stars)], as published
        self.count = 0          # events taken

    def publish(self, seq, users, items, stars):
        """The events generation ``seq`` made visible, in arrival order."""
        for u, i, s in zip(np.asarray(users).tolist(),
                           np.asarray(items).tolist(),
                           np.asarray(stars).tolist()):
            self.events.setdefault(int(u), []).append((int(seq), int(i),
                                                       float(s)))
            self.count += 1

    def resident(self, user):
        """``(items, stars)`` the user had rated before the run (views)."""
        if not 0 <= user < len(self.indptr) - 1:
            return self.indices[:0], self.stars[:0]
        lo, hi = self.indptr[user], self.indptr[user + 1]
        return self.indices[lo:hi], self.stars[lo:hi]

    def ratings(self, user, seq=None):
        """``(items int64, stars float64)`` of ``user`` as of generation
        ``seq`` (``None``: the last)."""
        items, stars = self.resident(user)
        mine = [e for e in self.events.get(int(user), ())
                if seq is None or e[0] <= seq]
        if not mine:
            return items.astype(np.int64), stars.astype(np.float64)
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

    def ids(self, user, seq=None):
        """The catalog ids ``user`` is not to be answered with as of
        generation ``seq``: :meth:`ratings`' items, without the stars."""
        items = self.resident(user)[0].astype(np.int64)
        new = list(dict.fromkeys(e[1] for e in self.events.get(int(user), ())
                                 if seq is None or e[0] <= seq))
        if not new:
            return items
        new = np.array(new, np.int64)
        return np.concatenate([items, new[~np.isin(new, items)]])

    def touched(self):
        return sorted(self.events)


def fold(V, hist, user, reg, seq=None, operand_dtype=None):
    """The float64 factor row of ``user`` over ALL the user's ratings as of
    generation ``seq`` (``operand_dtype``: the control, as ``foldin.fold``)."""
    items, stars = hist.ratings(user, seq)
    return foldin.fold(V, items, stars, reg, operand_dtype=operand_dtype)


def fold_events_only(V, hist, user, reg, seq=None):
    """What a server WITHOUT the resident history publishes (the ``-live``
    sibling's rule): the fold over the run's events alone, each a rating of
    its own."""
    mine = [e for e in hist.events.get(int(user), ())
            if seq is None or e[0] <= seq]
    return foldin.fold(V, [e[1] for e in mine], [e[2] for e in mine], reg)


def row_rel_err(x, x64):
    """``|x - x64| / |x64|`` (Euclidean), the error of one published row."""
    x64 = np.asarray(x64, np.float64)
    return float(np.linalg.norm(np.asarray(x, np.float64) - x64)
                 / max(np.linalg.norm(x64), 1e-300))


def exact_topk_left(Q, V, k, excluded):
    """``topk_unseen.exact_topk``: the exact top-k of the ids each query
    has not excluded."""
    return topk_unseen.exact_topk(Q, V, k, excluded)
