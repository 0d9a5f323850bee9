"""Seeded inputs for every cell: ratings of a MovieLens-like shape, factor
tables for the serving cells, zipf client ids and Poisson arrival times.

The ratings generator keeps the model of
``tpu_als/io/movielens.py::synthetic_movielens`` (power-law degrees, users
shallower than items, a planted low-rank structure squashed onto the
half-star grid) and is kept here so that no later PR can move the yardstick
by changing the program's generator.  It differs from the original in what
a ratings table cannot be and in what only costs set-up time:

* the degrees are the power law's expected values clipped to a range the
  configuration gives (MovieLens-25M's own extremes), the same for every
  seed, where the original sampled them without a cap (its heaviest item
  held 3.4 M ratings from 162,541 users): a new seed moves no bucket shape,
  so it compiles nothing new and does the same amount of work;
* no (user, item) pair occurs twice (:func:`simple_pairing`), where the
  original paired slots at random and repeated 55 % of them;
* float32 work arrays, int32 ids (Spark's own id type), no timestamps.
"""

from __future__ import annotations

import numpy as np

SEED_MOD = 2 ** 32   # numpy seeds are unsigned; --seed may pass 2**31


def rng_for(seed, stream):
    """An independent generator per (seed, purpose)."""
    return np.random.default_rng([int(seed) % SEED_MOD, int(stream)])


def power_law_degrees(n_entities, n_total, power, lo=1, hi=None):
    """Non-increasing degrees ``clip(c * k**-power, lo, hi)`` (k = 1..n),
    with ``c`` such that they sum to ``n_total`` exactly after rounding.
    Deterministic: NO seed enters, so every seed trains on the same multiset
    of degrees — the same bucket shapes, hence the same compiled programs
    and the same amount of work — and only who holds which degree, and
    which ratings they are, moves with the seed."""
    hi = n_total if hi is None else hi
    if not n_entities * lo <= n_total <= n_entities * hi:
        raise ValueError(f"{n_total} ratings do not fit {n_entities} "
                         f"entities of {lo}..{hi} each")
    w = np.arange(1, n_entities + 1, dtype=np.float64) ** (-power)
    low, high = 0.0, float(n_total) / w[-1]
    for _ in range(100):                 # bisection on the scale c
        c = 0.5 * (low + high)
        if np.clip(c * w, lo, hi).sum() < n_total:
            low = c
        else:
            high = c
    exact = np.clip(low * w, lo, hi)
    deg = np.floor(exact).astype(np.int64)
    # hand the rounding's remainder to the entities that lost most of it
    room = np.flatnonzero(deg < hi)
    short = int(n_total - deg.sum())
    lost = np.argsort((deg - exact)[room], kind="stable")
    np.add.at(deg, room[lost[np.arange(short) % len(room)]], 1)
    return -np.sort(-deg)


def simple_pairing(user_deg, item_deg):
    """``(user, item)`` index arrays of a bipartite graph with exactly these
    (non-increasing) degrees and NO repeated pair.  Users' rating slots are
    laid out level by level — every user's first slot, then the second slot
    of every user that has two, ... — so that a run of consecutive slots
    holds distinct users, and the items take consecutive runs, heaviest
    first: the heaviest items are rated by everyone, the deep levels (the
    heaviest users only) go to the lightest items.  Raises where an item's
    run would meet a user twice (too dense or too skewed for this
    construction)."""
    n_total = int(user_deg.sum())
    if n_total != int(item_deg.sum()):
        raise ValueError("the two sides' degrees do not sum to the same")
    per_level = np.bincount(
        np.minimum(user_deg, user_deg.max()), minlength=user_deg.max() + 1)
    # users per level t (0-based): those with degree > t, a prefix
    users_at = len(user_deg) - np.cumsum(per_level)[:-1]
    level_start = np.cumsum(users_at) - users_at
    run_start = np.cumsum(item_deg) - item_deg
    first = np.searchsorted(level_start, run_start, side="right") - 1
    last = np.searchsorted(level_start, run_start + item_deg - 1,
                           side="right") - 1
    if ((last - first > 1)
            | ((last - first == 1) & (item_deg > users_at[first]))).any():
        raise ValueError("these degrees admit no pairing without a "
                         "repeated pair by this construction")
    # level t holds users 0..users_at[t]-1 (the degrees do not increase)
    by_level = (np.arange(n_total, dtype=np.int32)
                - np.repeat(level_start.astype(np.int32), users_at))
    item = np.repeat(np.arange(len(item_deg), dtype=np.int32), item_deg)
    return by_level, item


def synthetic_ratings(num_users, num_items, num_ratings, seed, *,
                      user_power=0.9, item_power=1.1, user_degree=None,
                      item_degree=None, planted_rank=16, noise=0.3):
    """``{"user", "item", "rating"}`` columns, deterministic per seed.

    ``user_degree`` / ``item_degree`` are ``[least, most]`` ratings of one
    entity (default: 1 and the other side's count, beyond which a pair
    would have to repeat).  The graph is the same for every seed
    (:func:`power_law_degrees`, :func:`simple_pairing`); the seed relabels
    users and items and draws the planted factors and the noise."""
    rng = rng_for(seed, 0)
    u_lo, u_hi = user_degree or (1, num_items)
    i_lo, i_hi = item_degree or (1, num_users)
    u, i = simple_pairing(
        power_law_degrees(num_users, num_ratings, user_power, u_lo, u_hi),
        power_law_degrees(num_items, num_ratings, item_power, i_lo, i_hi))
    u = rng.permutation(num_users).astype(np.int32)[u]
    i = rng.permutation(num_items).astype(np.int32)[i]
    ustar = rng.standard_normal((num_users, planted_rank), dtype=np.float32)
    vstar = rng.standard_normal((num_items, planted_rank), dtype=np.float32)
    vstar /= np.float32(np.sqrt(planted_rank))
    raw = np.empty(num_ratings, dtype=np.float32)
    step = 1 << 22                       # blocks keep the gathers in cache
    for lo in range(0, num_ratings, step):
        hi = min(lo + step, num_ratings)
        raw[lo:hi] = np.einsum("nr,nr->n", ustar[u[lo:hi]], vstar[i[lo:hi]])
    raw += np.float32(noise) * rng.standard_normal(num_ratings,
                                                   dtype=np.float32)
    stars = np.clip(np.round((3.5 + 1.1 * raw) * 2) / 2, 0.5, 5.0)
    return {"user": u, "item": i, "rating": stars.astype(np.float32)}


def zipf_weights(n, s):
    """P(k) ~ (k + 1)**-s over ``n`` ranks (``soak/traffic.py``'s draw)."""
    w = (np.arange(n, dtype=np.float64) + 1.0) ** (-float(s))
    return w / w.sum()


def poisson_arrivals(rng, rate, seconds):
    """Due times in [0, seconds) of a Poisson process at ``rate`` per
    second, with a FIXED count (rate * seconds) so every seed offers the
    same amount of work: sorted uniforms are a Poisson process conditioned
    on its count."""
    n = int(round(rate * seconds))
    return np.sort(rng.random(n)) * seconds
