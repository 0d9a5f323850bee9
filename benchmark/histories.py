"""Seeded rating histories for the cells whose answers depend on them, and
user factors that know them.

``seeded_histories``: who rated what, as CSR over catalog ids.  The
multiset of history lengths comes from NO seed
(``datagen.power_law_degrees``: every seed serves the same amount of
exclusion work); the seed decides who holds which length and which items
they are.  Items are drawn by popularity (``datagen.zipf_weights`` over a
seeded relabelling); an item drawn twice into one history is replaced by
one drawn uniformly from the catalog (the long histories reach into the
tail), until no history holds an id twice.  Rows come out ascending.

``planted_user_factors``: ``U[u] = sum over the history of stars * V[item]``
— the right-hand side of the user's normal equations, i.e. the regularised
half-step in the limit of a large ``regParam`` — as one segment sum on the
device, in chunks.  With factors that never saw the histories no rated item
ever reaches a top 10, and an engine that ignores the rule would read
``correct``.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import datagen


def seeded_histories(config, seed):
    """``(indptr int64[n_users + 1], indices int32[nnz], stars
    float32[nnz])``; ``config["histories"]`` gives ``user_power``,
    ``length_range``, ``item_zipf_s`` and the stars (``rating_range``,
    ``star_shares``)."""
    h = config["histories"]
    n_users, n_items = config["num_users"], config["num_items"]
    nnz = config["num_ratings"]
    rng = datagen.rng_for(seed, 5)
    lengths = np.empty(n_users, np.int64)
    lengths[rng.permutation(n_users)] = datagen.power_law_degrees(
        n_users, nnz, h["user_power"], *h["length_range"])
    indptr = np.concatenate([[0], np.cumsum(lengths)])
    row = np.repeat(np.arange(n_users, dtype=np.int64), lengths)
    # nnz independent draws by popularity: how often each item is drawn
    # (one multinomial), in a seeded order (17.9 M binary searches of the
    # cumulative weights took four times as long)
    item = np.repeat(
        rng.permutation(n_items),
        rng.multinomial(nnz, datagen.zipf_weights(n_items,
                                                  h["item_zipf_s"])))
    rng.shuffle(item)
    # sorted by (user, item): a history is a run of keys, ascending.  A
    # pair that stands twice is drawn again, uniformly, and put back in
    # its place (one full sort in all: the later rounds insert)
    key = np.sort(row * n_items + item)
    while True:
        twice = np.flatnonzero(key[1:] == key[:-1]) + 1
        if not len(twice):
            break
        again = np.sort(key[twice] // n_items * n_items
                        + rng.integers(0, n_items, len(twice)))
        key = np.delete(key, twice)
        key = np.insert(key, np.searchsorted(key, again), again)
    lo, hi = h["rating_range"]
    stars = lo + np.searchsorted(
        np.cumsum(h["star_shares"])[:hi - lo],
        rng.random(nnz, dtype=np.float32)).astype(np.float32)
    key -= row * n_items
    return indptr, key.astype(np.int32), stars


def planted_user_factors(indptr, indices, stars, V, chunk=1 << 20):
    """``float32[n_users, rank]`` on the host: the segment sum on the
    device, ``chunk`` ratings at a time (a gathered chunk is ``chunk x
    rank`` floats: 1 GB at rank 256)."""
    import jax
    import jax.numpy as jnp

    n_users, nnz = len(indptr) - 1, len(indices)
    row = np.repeat(np.arange(n_users, dtype=np.int32), np.diff(indptr))

    # U is donated: written in place.  (Undonated, every call's result is
    # a new 1.7 GB table allocated as the call is enqueued, and the host
    # runs many calls ahead of the device: 15.6 GB at the peak.)
    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(U, Vd, rows, ids, r):
        return U.at[rows].add(r[:, None] * jnp.take(Vd, ids, axis=0),
                              mode="drop", indices_are_sorted=True)

    Vd = jnp.asarray(V)
    U = jnp.zeros((n_users, V.shape[1]), jnp.float32)
    for lo in range(0, max(nnz, 1), chunk):
        n = min(chunk, nnz - lo)
        rows = np.full(chunk, n_users, np.int32)      # padding: dropped
        ids = np.zeros(chunk, np.int32)
        r = np.zeros(chunk, np.float32)
        rows[:n], ids[:n], r[:n] = (row[lo:lo + n], indices[lo:lo + n],
                                    stars[lo:lo + n])
        U = add(U, Vd, rows, ids, r)
    return np.asarray(U)
