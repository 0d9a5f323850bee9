"""Plain reference for the live deployment under IMPLICIT feedback with its
catalog moving: ``foldin_replay.py``'s replay — the admitted events in
admission order, in the updater's own batches, the USERS of a batch folded
first and then the ITEMS, a fold over all of the entity's ratings whose other
side has a factor when it runs, each fold from the rows the program itself
published — under the rule of ``foldin_implicit.py``, which reads ``F^T F``
of the WHOLE fixed table at every fold.  Imports nothing of the program.

The replay keeps its OWN two Gram matrices in float64 (guarantee 10):
``gram(U0)`` and ``gram(V0)`` at the start, each MOVED by the rows that go
into its table — ``G + x x^T - old old^T`` for every row a side's folds
publish (``old`` zero for an entity new to the table) — so a user's fold
reads ``V^T V`` as the batch before left it and an item's ``U^T U`` as the
same batch's user fold left it.  With ``published`` the rows that go in are
the program's, so the Gram matrices are those of the tables the program
served; :meth:`ImplicitReplay.final_table` rebuilds those tables whole, and
``gram()`` of them is what the moved matrices are checked against once
(``gram_drift``), and what the program's own are held to.

``operand_dtype`` / ``gram_dtype``: the CONTROL — every fold's gathered rows
and strengths (``operand_dtype``), the rows of the whole-table Gram
matrices and of every update of them (``gram_dtype``) rounded to a lower
precision first.  Its trajectory, read back through
``foldin_replay.published_of``, stands in the program's place.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import foldin_implicit as ref_rule
from benchmark.reference.foldin_replay import Replay


class ImplicitReplay(Replay):
    """``foldin_replay.Replay`` with ``gram``: ``[U^T U, V^T V]`` in
    float64 as the last batch left them."""

    def __init__(self, U0, V0, gram_dtype=None):
        super().__init__(U0, V0)
        self.gram = [ref_rule.gram(F, operand_dtype=gram_dtype)
                     for F in (U0, V0)]

    def final_table(self, side):
        """The whole table of ``side`` (0: users, 1: items) after the last
        batch, float32 — the seeded rows, the last published row of every
        entity a fold moved in its place, entities new in the run behind
        them (a Gram matrix does not ask in which order)."""
        base = (self.U0, self.V0)[side]
        rows = (self.user_rows, self.item_rows)[side]
        new = [e for e in rows if e >= len(base)]
        T = np.zeros((len(base) + len(new), base.shape[1]), np.float32)
        T[:len(base)] = base
        for e, x in rows.items():
            if e < len(base):
                T[e] = x
        for j, e in enumerate(new):
            T[len(base) + j] = rows[e]
        return T

    def gram_drift(self):
        """``[users, items]``: how far each moved Gram matrix lies from
        ``gram()`` of the final table, Frobenius, as a share of that."""
        return [rel_err(self.gram[side], ref_rule.gram(
            self.final_table(side))) for side in (0, 1)]


def rel_err(G, want):
    """Frobenius distance of ``G`` from ``want`` as a share of ``want``."""
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(G, np.float64) - want)
                 / np.linalg.norm(want))


def replay(U0, V0, users, items, stars, batch_sizes, reg, alpha,
           fold_items=True, operand_dtype=None, gram_dtype=None,
           published=None):
    """:class:`ImplicitReplay` of the events ``(users[j], items[j],
    stars[j])`` (``stars``: the observations' strengths) in admission
    order, cut into batches of ``batch_sizes``; ``published[b]`` the rows
    the program published in batch ``b`` (``foldin_replay.py``)."""
    out = ImplicitReplay(U0, V0, gram_dtype)
    n_rows = [len(U0), len(V0)]
    hist, used, dense = ({}, {}), ({}, {}), ({}, {})
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
                # the fixed table's Gram matrix as it stands: the other
                # side's rows of this batch are in it if they went first
                rows.append(ref_rule.fold(
                    F, np.arange(len(ok)), [r for _, r in ok], reg, alpha,
                    out.gram[other], operand_dtype=operand_dtype))
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
                out.unasked += len(theirs)
                moved += sorted(theirs)
                rows += [np.asarray(theirs[e], np.float64)
                         for e in moved[len(rows):]]
            for e, x in zip(moved, rows):
                old = out.row(side, e)
                if old is None:
                    dense[side][e] = n_rows[side]
                    n_rows[side] += 1
                else:
                    old = ref_rule.rounded(old, gram_dtype)
                    out.gram[side] -= np.outer(old, old)
                new = ref_rule.rounded(x, gram_dtype)
                out.gram[side] += np.outer(new, new)
                target[e] = x
            if moved:
                (out.user_log, out.item_log)[side].append(
                    (b, np.array(moved, np.int64), np.stack(rows)))
        out.n_items.append(n_rows[1])
    out.dense_users, out.dense_items = dense
    out.waiting = sum(len(h) - used[s].get(e, 0)
                      for s in (0, 1) for e, h in hist[s].items())
    return out
