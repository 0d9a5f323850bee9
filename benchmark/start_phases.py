"""What the ``start_*`` layer metrics read: the program's own record of its
start, from the process's ``tpu_als.obs`` registry (the runners never reset
it, and the traced run starts an engine like any other).

Since ISSUE 55 every phase of a serving start — ``ServingEngine.publish``
and its warm-ups, ``FoldInServer(...)``, ``prewarm``, ``LiveUpdater.start``
— closes with exact sums in two counters labelled by ``path``, the
'/'-joined ``start.*`` phases open on the thread (``start.seconds``,
``start.placed_bytes``), and JAX's own compile-path events are counted by
stage (``jax.programs``, ``jax.program_seconds``; ``when`` = ``traffic``
once an engine is started and no start phase is open).  A path with no '/'
is a start's TOP level: the program's own share of ``setup_s`` (the rest is
the benchmark's: imports, factors, histories, warm batches, the stream's
head); one that prefixes no other is a LEAF.

Every reader is ``None`` on a program without the counters (before ISSUE
55: the registry has no ``counter_series``).  Moves ``setup_s``.
"""

from __future__ import annotations


def series(name):
    """``[(labels, value)]`` of the program's counter ``name``; ``None``
    where the program keeps no such reading."""
    try:
        from tpu_als import obs
    except ImportError:
        return None
    read = getattr(obs, "counter_series", None)
    return None if read is None else read(name) or None


def by_path(name):
    rows = series(name)
    return None if rows is None else {
        labels["path"]: value for labels, value in rows if "path" in labels}


def top(paths):
    """The phases no other start phase held."""
    return {p: v for p, v in paths.items() if "/" not in p}


def leaves(paths):
    """The phases that held no other."""
    return {p: v for p, v in paths.items()
            if not any(q.startswith(p + "/") for q in paths)}


def named(paths, *endings):
    """The phases whose own name (the path's last part) ends in one of
    ``endings``."""
    return {p: v for p, v in paths.items()
            if p.rsplit("/", 1)[-1].endswith(endings)}


# a leaf that hands a table to the device
PLACES = (".place", ".users", ".catalog")


def seconds(pick):
    """The seconds of the phases ``pick`` chooses among all of them (0
    where a start ran none of them); ``None`` without the counter."""
    paths = by_path("start.seconds")
    return float(sum(pick(paths).values())) if paths else None


def unsplit_pct():
    """The share of the top-level phases' seconds that no leaf holds."""
    paths = by_path("start.seconds")
    whole = sum(top(paths).values()) if paths else 0.0
    if not whole:
        return None
    return 100.0 * max(0.0, whole - sum(leaves(paths).values())) / whole


def placed_gb():
    paths = by_path("start.placed_bytes")
    return 1e-9 * sum(top(paths).values()) if paths else None


def before_traffic(name, pick=lambda labels: True):
    """The sum of counter ``name`` over the series that are not
    ``traffic``'s and that ``pick`` admits."""
    rows = series(name)
    if rows is None:
        return None
    return float(sum(v for labels, v in rows
                     if labels.get("when") != "traffic" and pick(labels)))
