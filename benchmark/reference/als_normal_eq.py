"""Plain reference for one ALS half-step: the normal equations of a sample
of rows, built and solved in numpy float64.  Imports nothing of the
program.

Explicit feedback (ALS-WR, what Spark MLlib's ALS solves)::

    A = sum_k f_k f_k^T + (reg * n + jitter) I        b = sum_k r_k f_k

Implicit feedback (Hu, Koren, Volinsky 2008)::

    A = F^T F + sum_k alpha |r_k| f_k f_k^T + (reg * n_pos + jitter) I
    b = sum_k (1 + alpha |r_k|) [r_k > 0] f_k

``f_k`` are the opposite side's factor rows of the row's ratings, ``n`` the
number of its ratings (``n_pos``: of its positive ratings).  Duplicate
(row, column) pairs count once each, as the program's buckets hold them.

``operand_dtype`` computes the same thing from operands rounded to a lower
precision first — the CONTROL of the comparison that decides ``correct``
(a float8 here, one step below the bf16 pass that the configuration's
"f32 at the TPU's default matmul precision" already is).
"""

from __future__ import annotations

import numpy as np

BLOCK = 1 << 18     # ratings per block of a heavy row: 256 MB of float64


def _rounded(x, operand_dtype):
    if operand_dtype is None:
        return np.asarray(x, dtype=np.float64)
    import ml_dtypes  # ships with jax

    dt = getattr(ml_dtypes, operand_dtype)
    return np.asarray(x, dtype=np.float32).astype(dt).astype(np.float64)


def normal_equations(factors, cols, vals, *, reg, implicit, alpha=0.0,
                     jitter=1e-6, operand_dtype=None):
    """``(A [n, r, r], b [n, r])``, one system per entry of ``cols``/``vals``
    (lists of arrays), regularisation included.  A row without ratings gets
    ``A = I, b = 0``: its solution stays 0."""
    F = _rounded(factors, operand_dtype)
    rank = F.shape[1]
    eye = np.eye(rank)
    gram = F.T @ F if implicit else 0.0
    A = np.zeros((len(cols), rank, rank))
    b = np.zeros((len(cols), rank))
    for n, (c, v) in enumerate(zip(cols, vals)):
        v = np.asarray(v, dtype=np.float64)
        for lo in range(0, len(c), BLOCK):
            Fg = F[c[lo:lo + BLOCK]]
            vv = v[lo:lo + BLOCK]
            if implicit:
                conf_m1 = alpha * np.abs(vv)
                A[n] += (Fg * conf_m1[:, None]).T @ Fg
                b[n] += ((1.0 + conf_m1) * (vv > 0)) @ Fg
            else:
                A[n] += Fg.T @ Fg
                b[n] += vv @ Fg
        count = float((v > 0).sum()) if implicit else float(len(v))
        if count == 0:
            A[n] = eye
        else:
            A[n] += gram + (reg * count + jitter) * eye
    return A, b


def solve_rows(factors, cols, vals, **kw):
    """One solved row per entry of ``cols``/``vals``."""
    A, b = normal_equations(factors, cols, vals, **kw)
    return np.linalg.solve(A, b[..., None])[..., 0]


def residuals(A, b, x):
    """Per-row ``||A x - b|| / ||b||`` (rows with b = 0 use ``||A x||``):
    how far ``x`` is from solving the system, whatever its condition."""
    num = np.linalg.norm(np.einsum("nrs,ns->nr", A, np.asarray(x, np.float64))
                         - b, axis=1)
    den = np.linalg.norm(b, axis=1)
    return np.where(den > 0, num / np.where(den > 0, den, 1.0), num)


def row_distances(x, ref):
    """Per-row ||x - ref|| / ||ref|| (rows with ref = 0 use ||x||)."""
    num = np.linalg.norm(np.asarray(x, np.float64) - ref, axis=1)
    den = np.linalg.norm(ref, axis=1)
    return np.where(den > 0, num / np.where(den > 0, den, 1.0), num)


def ratings_of(row_ids, col_ids, vals, rows):
    """Every rating of ``rows`` (sorted, unique) as one ``(cols, vals)``
    pair per row, from parallel rating columns."""
    sel = np.flatnonzero(np.isin(row_ids, rows))
    local = np.searchsorted(rows, row_ids[sel])
    order = np.argsort(local, kind="stable")
    sel, local = sel[order], local[order]
    cuts = np.searchsorted(local, np.arange(1, len(rows)))
    return (np.split(col_ids[sel], cuts),
            np.split(np.asarray(vals)[sel], cuts))
