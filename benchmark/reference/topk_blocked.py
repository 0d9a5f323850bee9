"""``reference/topk.py`` for a catalog too large to hold in float64 at
once (12,047,500 x 256 are 24.7 GB): the same float64 scores of every
query against the whole catalog and the same exact top-k, computed over
blocks of catalog ROWS with a running top-k, and the same lower-precision
control.  The configuration ``amazon23-r256-host4of16``'s copy of the plain
reference; imports nothing of the program, and nothing of ``topk.py``
either (a test holds the two equal at a small size).
"""

from __future__ import annotations

import numpy as np

ITEM_BLOCK = 1 << 17      # 131,072 rows: 0.27 GB in float64 at rank 256


def _running_topk(Q64, blocks, k):
    """(scores [n, k], ids [n, k]), descending, over ``blocks``: an
    iterable of ``(first id, float64 rows)``.  Each block's best ``k`` are
    merged into the best so far; ties go to the lower id, as one stable
    sort over the whole catalog would have them.  The block's score matrix
    is written into one buffer, block after block (a fresh array a block
    costs five times its product in page faults)."""
    n = len(Q64)
    best_s = np.full((n, 0), -np.inf)
    best_i = np.zeros((n, 0), dtype=np.int64)
    flat = None
    for lo, V64 in blocks:
        if flat is None:              # the first block is the largest
            flat = np.empty(n * len(V64))
        neg = np.matmul(Q64, V64.T,
                        out=flat[:n * len(V64)].reshape(n, len(V64)))
        np.negative(neg, out=neg)
        kk = min(k, neg.shape[1])
        part = np.argpartition(neg, kk - 1, axis=1)[:, :kk]
        cat_s = np.concatenate(
            [best_s, -np.take_along_axis(neg, part, axis=1)], axis=1)
        cat_i = np.concatenate([best_i, part + lo], axis=1)
        # by score descending, then by id ascending
        order = np.lexsort((cat_i, -cat_s), axis=1)[:, :k]
        best_s = np.take_along_axis(cat_s, order, axis=1)
        best_i = np.take_along_axis(cat_i, order, axis=1)
    return best_s, best_i


def _blocks(V, item_block, prepare=None):
    """``(first id, float64 rows)`` of ``item_block`` rows of ``V`` at a
    time, converted into ONE buffer that the next block overwrites (or,
    with ``prepare``, whatever it makes of the converted rows)."""
    buf = np.empty((min(item_block, len(V)), V.shape[1]))
    for lo in range(0, len(V), item_block):
        rows = V[lo:lo + item_block]
        np.copyto(buf[:len(rows)], rows)
        yield lo, (buf[:len(rows)] if prepare is None
                   else prepare(buf[:len(rows)]))


def exact_topk(Q, V, k, item_block=ITEM_BLOCK):
    """(scores [n, k], ids [n, k]) in float64, descending: what
    ``topk.exact_topk`` returns, with no more than ``item_block`` rows of
    the catalog in float64 at a time."""
    return _running_topk(np.asarray(Q, np.float64), _blocks(V, item_block), k)


def own_scores(Q, V, ids):
    """float64 dot products of each query with the ids it was served;
    only the served rows are converted."""
    return np.einsum("nr,nkr->nk", np.asarray(Q, np.float64),
                     np.asarray(V[ids], np.float64))


def recall(ids, ref_ids):
    k = ref_ids.shape[1]
    return float(np.mean([len(set(a) & set(b)) / k
                          for a, b in zip(ids, ref_ids)]))


def quantize_rows(X, bits):
    """Symmetric per-row integer quantisation to ``bits`` bits, returned
    dequantised (what an int<bits> scorer multiplies).  Row by row, so a
    block of rows quantises as the whole catalog would."""
    X = np.asarray(X, np.float64)
    qmax = 2 ** (bits - 1) - 1
    scale = np.abs(X).max(axis=1, keepdims=True) / qmax
    scale[scale == 0] = 1.0
    return np.clip(np.round(X / scale), -qmax, qmax) * scale


def lower_precision_topk(Q, V, k, *, shortlist_k, shortlist_bits,
                         rescore_dtype, item_block=ITEM_BLOCK):
    """What the served path would answer one precision step down: a
    shortlist from an int<shortlist_bits> catalog, rescored from operands
    rounded to ``rescore_dtype`` (an ml_dtypes name) — ``topk.py``'s
    control, the catalog quantised a block at a time."""
    import ml_dtypes

    dt = getattr(ml_dtypes, rescore_dtype)
    Q64 = np.asarray(Q, np.float64)
    _, short = _running_topk(
        Q64, _blocks(V, item_block, lambda rows: quantize_rows(
            rows, shortlist_bits)), shortlist_k)
    Ql = np.asarray(Q, np.float32).astype(dt).astype(np.float64)
    Vl = np.asarray(V[short], np.float32).astype(dt).astype(np.float64)
    s = np.einsum("nr,nkr->nk", Ql, Vl)
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(s, order, axis=1),
            np.take_along_axis(short, order, axis=1))
