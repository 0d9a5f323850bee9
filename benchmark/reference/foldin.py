"""Plain reference for the live fold-in: for one user with rated items
``i_1..i_n`` and ratings ``r``, against the FIXED item factors ``V``,

    x = (Vu^T Vu + reg * n * I)^-1 Vu^T r,      Vu = V[i_1..i_n]

in float64 over ALL of that user's events, in whatever order (ALS-WR
weighting: the ridge grows with the number of ratings, as Spark MLlib's).
Imports nothing of the program.

``operand_dtype`` rounds the gathered rows and the ratings to a lower
precision first and returns what a fold in that precision would have
published — the CONTROL of the read-your-writes comparison (float8: the
program multiplies float32 operands in one bfloat16 pass).
"""

from __future__ import annotations

import numpy as np


def fold(V, items, ratings, reg, operand_dtype=None):
    """The folded factor row, float64 ``[rank]``."""
    Vu = np.asarray(V)[np.asarray(items, dtype=np.int64)]
    r = np.asarray(ratings)
    if operand_dtype is not None:
        import ml_dtypes

        dt = getattr(ml_dtypes, operand_dtype)
        Vu = Vu.astype(np.float32).astype(dt)
        r = r.astype(np.float32).astype(dt)
    Vu, r = Vu.astype(np.float64), r.astype(np.float64)
    A = Vu.T @ Vu + reg * len(r) * np.eye(Vu.shape[1])
    return np.linalg.solve(A, Vu.T @ r)


def fold_users(V, events_by_user, users, reg, operand_dtype=None):
    """``[len(users), rank]``: :func:`fold` of each user's
    ``(items, ratings)`` in ``events_by_user``."""
    return np.stack([fold(V, *events_by_user[u], reg,
                          operand_dtype=operand_dtype) for u in users])
