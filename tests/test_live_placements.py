"""The updater goes to the device once a program (PR 49), at a small size.

(a) ``fold_in`` through the packed ``_fold_in_jit`` returns, bit for bit, the
rows of the three-array program it took the place of; (b) a publish handed
the fold's rows ON THE DEVICE leaves every table on the device — the
engine's user table, catalog and histories, the index's segment, the fold-in
server's own two tables — bit for bit as the same publish from the host's
rows, for the four updater kinds, and goes up from the host where the
device's rows are not the rows it writes; (c) ``live.host_placements`` and the
``live.batch`` span's ``placements`` read one placement a batch; (d) nothing
compiles after the warm-ups; (e) the jitted programs keep the names the
benchmark's trace readers look for."""

from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import (
    test_live_deployment,
    test_live_items,
    test_live_items_unseen,
    test_live_unseen,
)
from tests.conftest import CompileCount
from tpu_als import obs
from tpu_als.core import foldin
from tpu_als.core.ratings import pad_for
from tpu_als.ops.solve import (
    compute_yty,
    normal_eq_explicit,
    normal_eq_implicit,
    solve_spd,
)
from tpu_als.serving import engine as engine_module
from tpu_als.serving import index as index_module

# -- (a) the fold's one input form -------------------------------------------


@functools.partial(jax.jit, static_argnames=("implicit_prefs",))
def three_array_fold(V, cols, vals, mask, reg_param, implicit_prefs=False,
                     alpha=1.0):
    """``_fold_in_jit`` as it stood before PR 49 (a handful of systems:
    true float32, XLA's Cholesky): three arrays, uploaded one by one."""
    with jax.default_matmul_precision("highest"):
        Vg = V[cols]
        if implicit_prefs:
            A, b, count = normal_eq_implicit(Vg, vals, mask, reg_param,
                                             alpha, compute_yty(V))
        else:
            A, b, count = normal_eq_explicit(Vg, vals, mask, reg_param)
    return solve_spd(A, b, count, backend="xla")


def ragged_rows(rng, n, w, n_items):
    """Padded rows with a full row, an empty one, a mask with holes (not
    a prefix) and ragged prefixes."""
    lens = rng.integers(1, w + 1, n)
    lens[0], lens[1] = w, 0
    mask = (np.arange(w)[None] < lens[:, None]).astype(np.float32)
    mask[2] = (rng.random(w) < 0.5).astype(np.float32)
    mask[2, -1] = 1.0
    cols = (rng.integers(0, n_items, (n, w)) * mask).astype(np.int32)
    vals = (rng.integers(1, 6, (n, w)) * mask).astype(np.float32)
    return cols, vals, mask


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
@pytest.mark.parametrize("n,w", [(8, 8), (8, 64), (8, 512), (64, 8),
                                 (64, 64), (64, 512)])
def test_the_packed_fold_returns_the_three_array_folds_rows(n, w, implicit):
    rng = np.random.default_rng(n * w + implicit)
    V = jnp.asarray(rng.normal(size=(700, 16)).astype(np.float32) / 4)
    cols, vals, mask = ragged_rows(rng, n, w, 700)
    how = {"implicit_prefs": implicit, "alpha": 3.0}
    want = np.asarray(three_array_fold(
        V, jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(mask), 0.1,
        **how))
    # host arrays, arrays on the device, and planes of one packed array
    # (what the stream driver fills): one program, the same bits
    packed = np.zeros((3, n, w), np.int32)
    for plane, a in zip(foldin.planes(packed), (cols, vals, mask)):
        plane[:] = a
    for rows in ((cols, vals, mask), map(jnp.asarray, (cols, vals, mask))):
        got = np.asarray(foldin.fold_in(V, *rows, 0.1, **how))
        assert np.array_equal(got, want)
    compiles = CompileCount()
    got = np.asarray(foldin.fold_in(V, *foldin.planes(packed), 0.1, **how))
    assert np.array_equal(got, want) and compiles.n == 0
    assert foldin.pack_rows(*foldin.planes(packed)) is packed


# -- (b) (c) (d) a publish from the fold's rows on the device -----------------

MAX_BATCH = 64      # rows' pads 8 and 64; a publish's one array as wide
SIZES = (5, 12, 7, 20, 3, 9)


def build(kind):
    """``(model, engine, server, updater, users, items)`` of an updater
    kind, everything run ahead as ``LiveUpdater.start`` does it — and the
    thread stopped at once: the test hands the batches over itself."""
    items = kind in ("live-items", "live-items-unseen")
    if kind == "live":
        mod = test_live_deployment
        _, _, _, model, eng, srv, upd = mod.make_stack(seed=49,
                                                       max_batch=MAX_BATCH)
    elif kind == "live-items":
        mod = test_live_items
        _, _, _, model, eng, srv, upd = mod.make_stack(seed=49,
                                                       max_batch=MAX_BATCH)
    elif kind == "live-unseen":
        mod = test_live_unseen
        _, _, _, model, eng, srv, upd = mod.make_stack(seed=49,
                                                       max_batch=MAX_BATCH)
    else:
        mod = test_live_items_unseen
        *_, model, eng, srv, upd = mod.make_stack(seed=49)
        upd.max_batch = MAX_BATCH
    srv.prewarm(rows=(MAX_BATCH,),
                sides=("user", "item") if items else ("user",))
    upd.start()
    upd.stop()
    if items:
        srv.prewarm(rows=(MAX_BATCH,), sides=("item",))
    return model, eng, srv, upd, mod.N_USERS, mod.N_ITEMS


def seeded_batches(seed, n_users, n_items, new_items):
    """Batches of ``SIZES`` events: known users on catalog items, a sixth
    from new users and, where items fold, a sixth on new items and as many
    on one of the run's new items again."""
    rng = np.random.default_rng(seed)
    next_user, next_item, added, out = n_users, n_items, [], []
    for size in SIZES:
        batch = []
        for j in range(size):
            user = int(rng.integers(0, n_users))
            item = int(rng.integers(0, n_items))
            if j % 6 == 1:
                user, next_user = next_user, next_user + 1
            elif j % 6 == 2 and new_items:
                item, next_item = next_item, next_item + 1
                added.append(item)
            elif j % 6 == 3 and added:
                item = added[int(rng.integers(0, len(added)))]
            batch.append((user, item, float(rng.integers(1, 6))))
        out.append(batch)
    return out


class Stats:
    """Stands in for the ``live.batch`` span: keeps its stats."""

    def __init__(self):
        self.stats = {}

    def set_metadata(self, **kw):
        self.stats.update(kw)


def process(upd, batch):
    """One batch through the updater's own ``_process``, on this thread;
    the stats it gave its ``live.batch`` span."""
    whole = Stats()
    upd._process([(u, i, r, time.perf_counter(), None)
                  for u, i, r in batch], whole)
    return whole.stats


def from_the_host(eng, srv):
    """The same engine and server with every row going up from the
    host's copy, as before PR 49: the publish is told of no row on the
    device, and the server's own tables are written from the host."""
    publish, write_back = eng.publish_update, srv._write_back
    eng.publish_update = lambda *a, device_rows=None, **kw: publish(*a, **kw)
    srv._write_back = lambda ids, rows, items_side=False, placed=None: (
        write_back(ids, rows, items_side))


def on_the_device(eng, srv):
    """Every array the write path keeps on the device, read back."""
    m = eng._model
    out = {"U": m.U, "V": m.V, "valid": m.valid, "srv_V": srv._V}
    if srv._Ud is not None:
        out["srv_U"] = srv._Ud
    if m.index is not None and m.index._seg is not None:
        out.update({f"seg{j}": a for j, a in enumerate(m.index._seg)})
        out["last_id"] = m.index._last_id()
    if m.seen is not None:
        out.update(start=m.seen.runs[0], count=m.seen.runs[1],
                   indices=m.seen.indices)
    return {k: np.array(v) for k, v in out.items()}


KINDS = ("live", "live-items", "live-unseen", "live-items-unseen")
# placements a batch whose rows go up from the host: the server's two
# tables, the user rows, the segment's rows, the history's plan
FROM_THE_HOST = {"live": 1, "live-items": 4, "live-unseen": 2,
                 "live-items-unseen": 5}


@pytest.fixture(scope="module", params=KINDS)
def pair(request):
    """The same batches through two stacks of one kind: rows written from
    the device, and from the host."""
    kind = request.param
    reg = obs.reset()
    out = {"kind": kind, "reg": reg}
    for side in ("device", "host"):
        model, eng, srv, upd, n_users, n_items = build(kind)
        if side == "host":
            from_the_host(eng, srv)
        batches = seeded_batches(49, n_users, n_items, "items" in kind)
        sent = {k: reg.counter_value(k) for k in (
            "live.host_placements", "live.publish_h2d_bytes",
            "live.catalog_h2d_bytes", "live.history_h2d_bytes")}
        compiles = CompileCount()
        stats = [process(upd, b) for b in batches]
        out[side] = {
            "compiled": compiles.n, "stats": stats, "eng": eng, "srv": srv,
            "upd": upd, "model": model, "arrays": on_the_device(eng, srv),
            "host": (model._U.copy(), model._V.copy()),
            "sent": {k: reg.counter_value(k) - v for k, v in sent.items()}}
    return out


def test_the_device_holds_the_same_bits_either_way(pair):
    dev, host = pair["device"], pair["host"]
    assert sorted(dev["arrays"]) == sorted(host["arrays"])
    for name, a in dev["arrays"].items():
        assert np.array_equal(a, host["arrays"][name]), name
    for a, b in zip(dev["host"], host["host"]):
        assert np.array_equal(a, b)
    m, h = dev["eng"]._model, host["eng"]._model
    assert (m.seq, m.n_users, m.n_items) == (h.seq, h.n_users, h.n_items)
    assert m.seq == 1 + len(SIZES)
    if m.index is not None:
        assert np.array_equal(m.index.d_rows, h.index.d_rows)
        assert m.index.n_items == h.index.n_items
    if m.seen is not None:
        assert np.array_equal(m.seen.lengths, h.seen.lengths)
    # and what the engine serves is what the host's tables hold
    n = m.n_users
    assert np.array_equal(dev["arrays"]["U"][:n], dev["host"][0][:n])


def test_one_placement_a_batch(pair):
    dev, host = pair["device"], pair["host"]
    assert [s["placements"] for s in dev["stats"]] == [1] * len(SIZES)
    assert dev["sent"]["live.host_placements"] == len(SIZES)
    want = FROM_THE_HOST[pair["kind"]]
    assert [s["placements"] for s in host["stats"]] == [want] * len(SIZES)
    assert host["sent"]["live.host_placements"] == want * len(SIZES)


def test_what_goes_up_is_row_numbers_not_rows(pair):
    """The publish's one ``int32[10, pad]``, split between the three
    counters; from the host the rows go up with their numbers."""
    dev, host = pair["device"]["sent"], pair["host"]["sent"]
    rank = pair["device"]["eng"]._model.rank
    users = [pad_for(s["users"]) for s in pair["device"]["stats"]]
    assert host["live.publish_h2d_bytes"] == sum(
        4 * pad * (1 + rank) for pad in users)
    total = sum(dev.values()) - dev["live.host_placements"]
    assert total % (4 * engine_module.PUBLISH_SENT * 8) == 0
    assert total <= 4 * engine_module.PUBLISH_SENT * 64 * len(SIZES)
    assert dev["live.publish_h2d_bytes"] < host["live.publish_h2d_bytes"]
    if "items" in pair["kind"]:
        assert 0 < dev["live.catalog_h2d_bytes"] \
            < host["live.catalog_h2d_bytes"]
    if "unseen" in pair["kind"]:
        assert dev["live.history_h2d_bytes"] \
            >= host["live.history_h2d_bytes"] > 0


def test_nothing_compiles_after_the_warm_ups(pair):
    assert pair["device"]["compiled"] == 0
    assert pair["host"]["compiled"] == 0


@pytest.mark.parametrize("why", ["two_fold_calls", "a_user_appended"])
def test_rows_that_are_not_the_publishs_go_up_from_the_host(pair, why):
    """A fold that took two calls leaves no one array of rows; a user
    appended by a fold nobody published makes the publish write a row the
    batch's fold did not solve: either way the user rows go up from the
    host with their numbers, on both stacks, which still agree bit for
    bit, and nothing compiles."""
    kind, reg = pair["kind"], pair["reg"]
    stacks = [pair["device"], pair["host"]]
    n_users = len(stacks[0]["model"]._user_map)
    item = 10**6 + (why == "a_user_appended") if "items" in kind else 5
    batch = [(3, item, 4.0), (n_users + 7, 6, 2.0), (11, 9, 5.0)]
    made, went_up = [], []
    for stack in stacks:
        srv, upd = stack["srv"], stack["upd"]
        compiles = CompileCount()
        before = reg.counter_value("live.publish_h2d_bytes")
        with contextlib.ExitStack() as undo:
            if why == "two_fold_calls":
                undo.callback(setattr, srv, "_calls", srv._calls)
                srv._calls = lambda lens: (
                    sel for sel in np.array_split(np.arange(len(lens)), 2)
                    if len(sel))
            else:
                p = srv.model._params
                srv.update({p["userCol"]: np.array([n_users + 3]),
                            p["itemCol"]: np.array([4]),
                            p["ratingCol"]: np.array([3.0], np.float32)})
            made.append(process(upd, batch)["placements"])
        went_up.append(reg.counter_value("live.publish_h2d_bytes") - before)
        assert compiles.n == 0
    rows_and_numbers = 4 * 8 * (1 + stacks[0]["eng"]._model.rank)
    assert went_up[1] == rows_and_numbers <= went_up[0]
    assert 1 <= made[0] <= made[1] == FROM_THE_HOST[kind]
    a, b = (on_the_device(s["eng"], s["srv"]) for s in stacks)
    for name in a:
        assert np.array_equal(a[name], b[name]), (why, name)


# -- (e) the names the benchmark's trace readers key on ------------------------


def _programs():
    r, n = 16, 8
    table = jnp.zeros((64, r), jnp.float32)
    rows, vals = jnp.zeros(n, jnp.int32), jnp.zeros((n, r), jnp.float32)
    seg = (jnp.zeros(32, jnp.int32), jnp.zeros((32, r), jnp.int8),
           jnp.ones(32, jnp.float32), jnp.zeros((32, r), jnp.float32),
           jnp.zeros(32, jnp.bool_))
    base = (table, jnp.zeros((128, r), jnp.int8), jnp.ones(128, jnp.float32),
            jnp.zeros(128, jnp.bool_))
    return {
        "jit__fold_in_jit": (foldin._fold_in_jit, (
            table, jnp.zeros((3, n, 8), jnp.int32), 0.1), {"backend": "xla"}),
        "jit__scatter_rows": (foldin._scatter_rows, (table, rows, vals), {}),
        "jit__scatter_items": (engine_module._scatter_items, (
            table, jnp.zeros(64, jnp.bool_), rows, vals,
            jnp.zeros(n, jnp.bool_)), {}),
        "jit__write_segment": (index_module._write_segment, (
            *seg, jnp.zeros((4, n), jnp.int32), vals), {}),
        "jit__fold_segment": (index_module._fold_segment_inplace,
                              (*base, *seg), {}),
        # read by no metric, named in PERF.md section 3
        "jit__scatter_users": (engine_module._scatter_users,
                               (table, rows, vals), {}),
        "jit__append_runs": (engine_module._append_runs, (
            rows, rows, jnp.zeros(64, jnp.int32),
            jnp.zeros((5, n), jnp.int32)), {}),
    }


@pytest.mark.parametrize("name", [
    "jit__fold_in_jit", "jit__scatter_rows", "jit__scatter_items",
    "jit__write_segment", "jit__fold_segment", "jit__scatter_users",
    "jit__append_runs"])
def test_the_programs_keep_the_names_the_trace_readers_look_for(name):
    """``benchmark/live_spans.py`` and ``live_item_spans.py`` find the
    device's runs by the ``XLA Modules`` line, which names a run after the
    jitted function: ``live_foldin_device_ms``, ``live_fold_hbm_pct`` and
    ``live_catalog_write_device_ms`` read ``None`` under any other name."""
    fn, args, statics = _programs()[name]
    assert f"module @{name} " in fn.lower(*args, **statics).as_text()
