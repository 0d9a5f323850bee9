"""Plain reference for the live fold-in under IMPLICIT feedback (Hu, Koren
and Volinsky, ICDM 2008; Spark's ``ALS(implicitPrefs=True, alpha=...)``): for
one entity with usable ratings ``k`` of strengths ``r_k`` against the rows
``f_k`` of the FIXED table ``F``,

    x = (G + sum_k (c_k - 1) f_k f_k^T + reg * n_pos * I)^-1 sum_k c_k p_k f_k

with ``G = F^T F`` over the WHOLE fixed table, the confidence ``c_k = 1 +
alpha * |r_k|``, the preference ``p_k = 1`` if ``r_k > 0`` else 0, and
``n_pos`` the count of ``r_k > 0`` (Spark's ``numExplicits``: ALS-WR
weighting over the positive observations), in float64.  Imports nothing of
the program.

``operand_dtype`` rounds the gathered rows and the strengths to a lower
precision first and returns what a fold with operands of that precision
would have published — the CONTROL of the comparison (bfloat16: the chip's
default multiplies float32 operands in one bfloat16 pass, and accumulates
in float32).  :func:`gram` takes the same argument: the whole-table Gram
matrix of rows rounded that way (its products, not its sums: a Gram matrix
handed to :func:`fold` is used as it is).
"""

from __future__ import annotations

import numpy as np


def rounded(a, operand_dtype):
    """``a`` in float64, through ``operand_dtype`` first where one is
    given."""
    a = np.asarray(a)
    if operand_dtype is not None:
        import ml_dtypes

        a = a.astype(np.float32).astype(getattr(ml_dtypes, operand_dtype))
    return a.astype(np.float64)


def gram(F, block=1 << 16, operand_dtype=None):
    """``F^T F`` in float64, ``block`` rows at a time (a float64 copy of a
    table of 1.7 M x 256 is 3.5 GB; of a block, 134 MB)."""
    F = np.asarray(F)
    G = np.zeros((F.shape[1], F.shape[1]))
    for lo in range(0, len(F), block):
        part = rounded(F[lo:lo + block], operand_dtype)
        G += part.T @ part
    return G


def fold(F, ids, ratings, reg, alpha, G, operand_dtype=None):
    """The folded factor row, float64 ``[rank]``; ``G`` the fixed table's
    Gram matrix (:func:`gram`)."""
    Fk = rounded(np.asarray(F)[np.asarray(ids, dtype=np.int64)],
                  operand_dtype)
    r = rounded(ratings, operand_dtype)
    conf_m1 = alpha * np.abs(r)
    pref = (r > 0).astype(np.float64)
    A = (np.asarray(G, np.float64) + (Fk * conf_m1[:, None]).T @ Fk
         + reg * pref.sum() * np.eye(Fk.shape[1]))
    return np.linalg.solve(A, Fk.T @ ((1.0 + conf_m1) * pref))


def fold_jnp(F, ids, ratings, reg, alpha, G):
    """The same rule in plain ``jax.numpy`` float32 at
    ``Precision.HIGHEST``, no kernels, no padding, no batching: the
    reference the CPU tests hold the program to bit-closely (float64 is
    off in JAX by default)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        Fk = jnp.asarray(F, jnp.float32)[jnp.asarray(ids, jnp.int32)]
        r = jnp.asarray(ratings, jnp.float32)
        conf_m1 = alpha * jnp.abs(r)
        pref = (r > 0).astype(jnp.float32)
        A = (jnp.asarray(G, jnp.float32) + (Fk * conf_m1[:, None]).T @ Fk
             + reg * pref.sum() * jnp.eye(Fk.shape[1], dtype=jnp.float32))
        return jnp.linalg.solve(A, Fk.T @ ((1.0 + conf_m1) * pref))


def gram_jnp(F):
    """``F^T F`` in plain ``jax.numpy`` float32 at ``Precision.HIGHEST``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        F = jnp.asarray(F, jnp.float32)
        return F.T @ F
