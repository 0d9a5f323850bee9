"""Plain reference for top-k retrieval of what a query has NOT seen: float64
scores of every query against the whole catalog, each query's excluded ids
taken out, exact top-k of the ids left.  The configuration
``amazon23-r256-share32-unseen``'s copy of the plain reference; imports
nothing of the program, and nothing of ``topk.py`` or ``topk_blocked.py``
(a test holds it equal to ``topk.py`` where nothing is excluded).

``excluded`` is one integer array a query (any order, repeats allowed, may
be empty).  Blocked over catalog ROWS like ``topk_blocked.py`` (a running
top-k, ties to the lower id), so a block's score matrix stays in the host's
caches and the masks cost one searchsorted a block.  Where fewer than ``k``
ids are left a row's surplus slots hold ``-inf`` and the id ``-1``.
"""

from __future__ import annotations

import numpy as np

ITEM_BLOCK = 1 << 17      # 131,072 rows: 0.27 GB in float64 at rank 256


def _pairs(excluded):
    """The excluded (query, id) pairs, sorted by id: ``(ids, queries)``."""
    rows = np.repeat(np.arange(len(excluded)), [len(e) for e in excluded])
    ids = (np.concatenate([np.asarray(e, np.int64).ravel() for e in excluded])
           if len(rows) else np.empty(0, np.int64))
    order = np.argsort(ids, kind="stable")
    return ids[order], rows[order]


def _running_topk(Q64, blocks, k, excluded):
    """(scores [n, k], ids [n, k]), descending, over ``blocks``: an
    iterable of ``(first id, float64 rows)``; each block's best are merged
    into the best so far, by score descending and then id ascending."""
    n = len(Q64)
    ex_ids, ex_rows = _pairs(excluded)
    best_s = np.full((n, 0), -np.inf)
    best_i = np.zeros((n, 0), dtype=np.int64)
    for lo, V64 in blocks:
        neg = -(Q64 @ V64.T)
        a, b = np.searchsorted(ex_ids, [lo, lo + len(V64)])
        neg[ex_rows[a:b], ex_ids[a:b] - lo] = np.inf
        kk = min(k, neg.shape[1])
        part = np.argpartition(neg, kk - 1, axis=1)[:, :kk]
        cat_s = np.concatenate(
            [best_s, -np.take_along_axis(neg, part, axis=1)], axis=1)
        cat_i = np.concatenate([best_i, part + lo], axis=1)
        order = np.lexsort((cat_i, -cat_s), axis=1)[:, :k]
        best_s = np.take_along_axis(cat_s, order, axis=1)
        best_i = np.take_along_axis(cat_i, order, axis=1)
    if best_s.shape[1] < k:
        more = k - best_s.shape[1]
        best_s = np.pad(best_s, ((0, 0), (0, more)),
                        constant_values=-np.inf)
        best_i = np.pad(best_i, ((0, 0), (0, more)), constant_values=-1)
    best_i[np.isneginf(best_s)] = -1
    return best_s, best_i


def _blocks(V, item_block, prepare=None):
    buf = np.empty((min(item_block, len(V)), V.shape[1]))
    for lo in range(0, len(V), item_block):
        rows = V[lo:lo + item_block]
        np.copyto(buf[:len(rows)], rows)
        yield lo, (buf[:len(rows)] if prepare is None
                   else prepare(buf[:len(rows)]))


def exact_topk(Q, V, k, excluded, item_block=ITEM_BLOCK):
    """(scores [n, k], ids [n, k]) in float64, descending: the exact top-k
    of the ids each query has not excluded."""
    return _running_topk(np.asarray(Q, np.float64), _blocks(V, item_block),
                         k, excluded)


def own_scores(Q, V, ids):
    """float64 dot products of each query with the ids it was served (an
    id outside the catalog scores ``nan``: no comparison holds)."""
    ids = np.asarray(ids)
    inside = (ids >= 0) & (ids < len(V))
    s = np.einsum("nr,nkr->nk", np.asarray(Q, np.float64),
                  np.asarray(V[np.where(inside, ids, 0)], np.float64))
    return np.where(inside, s, np.nan)


def recall(ids, ref_ids):
    """Mean share of each query's reference ids (its real ones: a slot
    that holds -1 asks for nothing) among the ids it was served; a query
    whose reference is empty counts 1."""
    shares = []
    for a, b in zip(ids, ref_ids):
        want = set(int(x) for x in b if x >= 0)
        shares.append(len(want & set(int(x) for x in a)) / len(want)
                      if want else 1.0)
    return float(np.mean(shares))


def seen_returned(ids, excluded, real=None):
    """How many served (query, slot) pairs hold an id the query was to
    exclude; ``real`` (bool, the shape of ``ids``) leaves out the slots
    that hold the program's sentinel and no answer."""
    ids = np.asarray(ids)
    real = np.ones(ids.shape, bool) if real is None else np.asarray(real)
    return int(sum(np.isin(row[ok], np.asarray(e)).sum()
                   for row, ok, e in zip(ids, real, excluded)))


def quantize_rows(X, bits):
    """Symmetric per-row integer quantisation to ``bits`` bits, returned
    dequantised (what an int<bits> scorer multiplies)."""
    X = np.asarray(X, np.float64)
    qmax = 2 ** (bits - 1) - 1
    scale = np.abs(X).max(axis=1, keepdims=True) / qmax
    scale[scale == 0] = 1.0
    return np.clip(np.round(X / scale), -qmax, qmax) * scale


def lower_precision_topk(Q, V, k, excluded, *, shortlist_k, shortlist_bits,
                         rescore_dtype, item_block=ITEM_BLOCK):
    """What the served path would answer one precision step down, the same
    ids excluded: a shortlist from an int<shortlist_bits> catalog, rescored
    from operands rounded to ``rescore_dtype`` (an ml_dtypes name)."""
    import ml_dtypes

    dt = getattr(ml_dtypes, rescore_dtype)
    Q64 = np.asarray(Q, np.float64)
    _, short = _running_topk(
        Q64, _blocks(V, item_block, lambda rows: quantize_rows(
            rows, shortlist_bits)), shortlist_k, excluded)
    left = short >= 0
    Ql = np.asarray(Q, np.float32).astype(dt).astype(np.float64)
    Vl = np.asarray(V[np.where(left, short, 0)],
                    np.float32).astype(dt).astype(np.float64)
    s = np.where(left, np.einsum("nr,nkr->nk", Ql, Vl), -np.inf)
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(s, order, axis=1),
            np.take_along_axis(short, order, axis=1))
