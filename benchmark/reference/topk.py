"""Plain reference for top-k retrieval: float64 scores of every query
against the whole catalog, exact top-k.  Imports nothing of the program.

``operand_dtype`` rounds queries and catalog to a lower precision first
and returns what that precision would have served — the CONTROL of the
serving comparison (int4-like: the program's shortlist is int8).
"""

from __future__ import annotations

import numpy as np


def exact_topk(Q, V, k, block=64):
    """(scores [n, k], ids [n, k]) in float64, descending, in row blocks
    (64 rows against 1.5 M items are 0.8 GB of scores: larger blocks fall
    out of the host's caches and take five times as long)."""
    Q64, V64 = np.asarray(Q, np.float64), np.asarray(V, np.float64)
    scores = np.empty((len(Q64), k))
    ids = np.empty((len(Q64), k), dtype=np.int64)
    for lo in range(0, len(Q64), block):
        s = Q64[lo:lo + block] @ V64.T
        part = np.argpartition(-s, k - 1, axis=1)[:, :k]
        ps = np.take_along_axis(s, part, axis=1)
        order = np.argsort(-ps, axis=1, kind="stable")
        ids[lo:lo + block] = np.take_along_axis(part, order, axis=1)
        scores[lo:lo + block] = np.take_along_axis(ps, order, axis=1)
    return scores, ids


def own_scores(Q, V, ids):
    """float64 dot products of each query with the ids it was served."""
    return np.einsum("nr,nkr->nk", np.asarray(Q, np.float64),
                     np.asarray(V, np.float64)[ids])


def recall(ids, ref_ids):
    k = ref_ids.shape[1]
    return float(np.mean([len(set(a) & set(b)) / k
                          for a, b in zip(ids, ref_ids)]))


def quantize_rows(X, bits):
    """Symmetric per-row integer quantisation to ``bits`` bits, returned
    dequantised (what an int<bits> scorer multiplies)."""
    X = np.asarray(X, np.float64)
    qmax = 2 ** (bits - 1) - 1
    scale = np.abs(X).max(axis=1, keepdims=True) / qmax
    scale[scale == 0] = 1.0
    return np.clip(np.round(X / scale), -qmax, qmax) * scale


def lower_precision_topk(Q, V, k, *, shortlist_k, shortlist_bits,
                         rescore_dtype):
    """What the served path would answer one precision step down: a
    shortlist from an int<shortlist_bits> catalog, rescored from operands
    rounded to ``rescore_dtype`` (an ml_dtypes name)."""
    import ml_dtypes

    dt = getattr(ml_dtypes, rescore_dtype)
    Q64 = np.asarray(Q, np.float64)
    Vq = quantize_rows(V, shortlist_bits)
    _, short = exact_topk(Q64, Vq, shortlist_k)
    Ql = np.asarray(Q, np.float32).astype(dt).astype(np.float64)
    Vl = np.asarray(V, np.float32).astype(dt).astype(np.float64)
    s = np.einsum("nr,nkr->nk", Ql, Vl[short])
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(s, order, axis=1),
            np.take_along_axis(short, order, axis=1))
