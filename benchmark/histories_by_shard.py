"""``histories.py``'s seeded rating histories and planted user factors at a
four-chip host's size (13.6 M users, 142.9 M ratings, a 12 M-item catalog
whose float32 rows no single chip holds), made BY PARTS of the user table,
in parallel, on the host — the pattern of ``runners/serve_mesh.py::
host_factors``: ``PARTS`` row ranges, each from its own child of the seed,
one thread a part, so the seed's values do not depend on the machine.

Same model as the one-chip sibling's, key for key of
``config["histories"]``: the multiset of history lengths comes from NO seed
(``datagen.power_law_degrees``'s, computed by :func:`history_lengths`;
every seed serves the same amount of
exclusion work) and the seed decides who holds which length; items are
drawn by popularity (``datagen.zipf_weights`` over ONE seeded relabelling of
the whole catalog, shared by the parts); an item drawn twice into one
history is replaced by one drawn uniformly, until no history holds an id
twice; rows come out ascending; stars from the J-shaped ``star_shares``.
What differs from ``histories.seeded_histories`` is only that each part
draws its own ratings (so the values differ from that function's for the
same seed, as ``host_factors``' differ from ``serve.seeded_factors``').

``planted_user_factors``: ``U[u] = sum over the history of stars *
V[item]``, as the sibling plants it and for its reason (with factors that
never saw the histories no rated item reaches a top 10 and an engine that
ignores the rule reads ``correct``) — here as a sparse-times-dense product
on the host, a part of the user table a thread (``scipy.sparse``'s CSR
kernel: one row's sum in the order of its ids, so the values do not depend
on the number of threads): on the chips the 12.3 GB catalog would have to
visit every chip once for every quarter of the user table.
"""

from __future__ import annotations

import threading

import numpy as np

from benchmark import datagen

PARTS = 32     # fixed: the seed's values do not depend on the host


def _in_threads(work, n):
    threads = [threading.Thread(target=work, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def part_bounds(n_users, parts=PARTS):
    """The user rows ``[bounds[i], bounds[i + 1])`` of part ``i``."""
    return np.linspace(0, n_users, parts + 1).astype(np.int64)


def history_lengths(n_users, n_total, power, lo, hi):
    """``datagen.power_law_degrees``'s degrees — non-increasing
    ``clip(c * k**-power, lo, hi)`` with ``c`` such that they sum to
    ``n_total`` after rounding, from NO seed — with each step of the
    bisection on ``c`` in closed form: the weights fall with ``k``, so the
    clipped sum is ``hi`` times a prefix, ``lo`` times a suffix and ``c``
    times a difference of the weights' running sums between them.  (That
    function sums 13.6 M clipped weights a hundred times: 14 s of this
    cell's set-up on one core.)  The remainder of the rounding goes, as
    there, to the entities that lost most of it."""
    if not n_users * lo <= n_total <= n_users * hi:
        raise ValueError(f"{n_total} ratings do not fit {n_users} "
                         f"entities of {lo}..{hi} each")
    w = np.arange(1, n_users + 1, dtype=np.float64) ** (-power)
    below = np.concatenate([[0.0], np.cumsum(w)])
    falling = -w

    def total(c):
        a = np.searchsorted(falling, -hi / c, side="right")   # c * w >= hi
        b = np.searchsorted(falling, -lo / c, side="left")    # c * w > lo
        return hi * a + c * (below[b] - below[a]) + lo * (n_users - b)

    low, high = 0.0, float(n_total) / w[-1]
    for _ in range(100):
        c = 0.5 * (low + high)
        if total(c) < n_total:
            low = c
        else:
            high = c
    exact = np.clip(low * w, lo, hi)
    deg = np.floor(exact).astype(np.int64)
    room = np.flatnonzero(deg < hi)
    short = int(n_total - deg.sum())
    if not 0 <= short <= len(room):
        raise ValueError(f"{short} ratings left to hand to {len(room)} "
                         "entities: the closed form lost the sum")
    lost = np.argsort((deg - exact)[room], kind="stable")
    deg[room[lost[:short]]] += 1
    return -np.sort(-deg)


def seeded_histories(config, seed, parts=PARTS):
    """``(indptr int64[n_users + 1], indices int32[nnz], stars
    float32[nnz])``; ``config["histories"]`` as ``histories.
    seeded_histories`` reads it."""
    h = config["histories"]
    n_users, n_items = config["num_users"], config["num_items"]
    nnz = config["num_ratings"]
    rng = datagen.rng_for(seed, 5)
    lengths = np.empty(n_users, np.int64)
    lengths[rng.permutation(n_users)] = history_lengths(
        n_users, nnz, h["user_power"], *h["length_range"])
    indptr = np.concatenate([[0], np.cumsum(lengths)])
    relabel = rng.permutation(n_items).astype(np.int32)
    weights = datagen.zipf_weights(n_items, h["item_zipf_s"])
    lo_star, hi_star = h["rating_range"]
    star_edges = np.cumsum(h["star_shares"])[:hi_star - lo_star]
    bounds = part_bounds(n_users, parts)
    children = np.random.SeedSequence(
        [int(seed) % datagen.SEED_MOD, 6]).spawn(parts)
    indices = np.empty(nnz, np.int32)
    stars = np.empty(nnz, np.float32)
    failed = []

    def draw(i):
        try:
            a, b = bounds[i], bounds[i + 1]
            first, last = indptr[a], indptr[b]
            n = int(last - first)
            if not n:
                return
            part = np.random.default_rng(children[i])
            # n independent draws by popularity: how often each item is
            # drawn (one multinomial), in a seeded order
            item = np.repeat(relabel, part.multinomial(n, weights))
            part.shuffle(item)
            row = np.repeat(np.arange(a, b, dtype=np.int64), lengths[a:b])
            # sorted by (user, item): a history is a run of keys,
            # ascending.  A pair that stands twice is drawn again,
            # uniformly, and put back in its place
            key = np.sort(row * n_items + item)
            while True:
                twice = np.flatnonzero(key[1:] == key[:-1]) + 1
                if not len(twice):
                    break
                again = np.sort(key[twice] // n_items * n_items
                                + part.integers(0, n_items, len(twice)))
                key = np.delete(key, twice)
                key = np.insert(key, np.searchsorted(key, again), again)
            indices[first:last] = key - row * n_items
            stars[first:last] = lo_star + np.searchsorted(
                star_edges, part.random(n, dtype=np.float32))
        except BaseException as e:   # noqa: BLE001 — raised by the caller
            failed.append(e)

    _in_threads(draw, parts)
    if failed:
        raise failed[0]
    return indptr, indices, stars


def planted_user_factors(indptr, indices, stars, V, parts=PARTS):
    """``float32[n_users, rank]`` on the host: the sparse ratings matrix
    times ``V``, a part of the rows a thread."""
    from scipy import sparse

    n_users = len(indptr) - 1
    U = np.empty((n_users, V.shape[1]), np.float32)
    bounds = part_bounds(n_users, parts)
    failed = []

    def plant(i):
        try:
            a, b = bounds[i], bounds[i + 1]
            first, last = indptr[a], indptr[b]
            U[a:b] = sparse.csr_matrix(
                (stars[first:last], indices[first:last],
                 indptr[a:b + 1] - first),
                shape=(b - a, V.shape[0])) @ V
        except BaseException as e:   # noqa: BLE001 — raised by the caller
            failed.append(e)

    _in_threads(plant, parts)
    if failed:
        raise failed[0]
    return U
