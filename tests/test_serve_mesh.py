"""The mesh engine as one program a bucket (PR 32): the user table
sharded by rows with the by-id lookup inside the scoring program, the
merge on every shard, the row write in place in the owning shard, the
exact fallback per shard — on the CPU's virtual devices, four of them,
with a catalog and a user count that 4 does not divide."""

import re

import jax
import numpy as np
import pytest

from tpu_als import make_mesh, obs
from tpu_als.core.foldin import place_rows
from tpu_als.obs.schema import SERVE_MESH_SCOPES
from tpu_als.parallel.comm_audit import collective_bytes
from tpu_als.parallel.mesh import AXIS, shard_map
from tpu_als.serving import engine as engine_module
from tpu_als.serving import index as index_module
from tpu_als.serving.engine import ServingEngine, _mesh_lookup
from tpu_als.serving.index import (
    SCORE_ULPS,
    mesh_exchange_bytes,
    mesh_spread_bytes,
)

S, N_USERS, N_ITEMS, RANK, K = 4, 5003, 2003, 32, 10
BUCKETS = (8, 32)
P = jax.sharding.PartitionSpec


def factors(seed=0):
    rng = np.random.default_rng(seed)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    V = (rng.standard_normal((N_ITEMS, RANK))
         / np.sqrt(RANK)).astype(np.float32)
    return rng, U, V


def engine(U, V, mesh=True, **kw):
    eng = ServingEngine(k=K, buckets=BUCKETS, shortlist_k=128,
                        mesh=make_mesh(S) if mesh else None, **kw)
    eng.publish(U, V)
    eng.warmup()
    return eng


def drain(eng, payloads):
    tickets = [eng.submit(p) for p in payloads]
    while True:
        batch = eng.batcher.next_batch(timeout=0.01)
        if batch is None:
            break
        eng.serve_batch(batch)
    return [t.result(timeout=10) for t in tickets]


def values(table):
    """A device table's values without a view of its buffer (on the CPU
    ``np.asarray(table)`` keeps one, and a buffer with such a reference
    cannot be donated)."""
    return np.asarray(table + 0)


def shard_edges(table):
    n_loc = table.shape[0] // S
    return n_loc, sorted({s * n_loc + d for s in range(S)
                          for d in (0, n_loc - 1)})


# -- (a) the same answers as without a mesh ----------------------------------

@pytest.mark.parametrize("by", ["id", "vector"])
def test_mesh_engine_answers_as_the_meshless_engine_does(by):
    rng, U, V = factors()
    assert N_USERS % S and N_ITEMS % S
    ids = rng.choice(N_USERS, 40, replace=False)
    payloads = ([int(i) for i in ids] if by == "id" else
                [U[i] + 0.01 * rng.standard_normal(RANK).astype(np.float32)
                 for i in ids])
    want = drain(engine(U, V, mesh=False), payloads)
    got = drain(engine(U, V), payloads)
    for (ws, wi), (gs, gi) in zip(want, got):
        assert gi.tolist() == wi.tolist()
        tol = SCORE_ULPS * np.spacing(np.abs(ws).max())
        assert np.abs(gs - ws).max() <= tol


# -- (b) the lookup -----------------------------------------------------------

def lookup_program(mesh):
    return jax.jit(shard_map(
        lambda U, packed: _mesh_lookup(
            U, packed, me=jax.lax.axis_index(AXIS), axis=AXIS),
        mesh=mesh, in_specs=(P(AXIS), P()), out_specs=P(),
        check_vma=False))


def packed_ids(ids, rank):
    packed = np.zeros((len(ids), rank + 2), np.int32)
    packed[:, rank] = ids
    return packed


def test_sharded_lookup_returns_the_rows_bit_for_bit():
    rng, U, V = factors(1)
    eng = engine(U, V)
    table = eng._model.U
    n_loc, edges = shard_edges(table)
    assert table.shape[0] == S * n_loc >= N_USERS
    assert [int(s.data.shape[0]) for s in table.addressable_shards] \
        == [n_loc] * S
    # appended users: the first spare rows, on the last shards
    U2 = np.concatenate([U, rng.standard_normal((5, RANK)).astype(
        np.float32)])
    eng.publish_update(U2, V, touched_users=[0])
    table = eng._model.U
    live = [i for i in edges if i < len(U2)]
    ids = np.array(live + list(range(N_USERS, len(U2)))
                   + rng.choice(N_USERS, 16).tolist())
    assert {int(i) // n_loc for i in ids} == set(range(S))
    got = lookup_program(eng.mesh)(table, packed_ids(ids, RANK))
    assert np.asarray(got).tobytes() == U2[ids].tobytes()
    # a request by vector rides through untouched
    packed = packed_ids(ids[:8], RANK)
    rows = rng.standard_normal((8, RANK)).astype(np.float32)
    packed[:4, :RANK] = rows[:4].view(np.int32)
    packed[:4, RANK + 1] = 1
    got = np.asarray(lookup_program(eng.mesh)(table, packed))
    assert got[:4].tobytes() == rows[:4].tobytes()
    assert got[4:].tobytes() == U2[ids[4:8]].tobytes()


def test_place_rows_shards_a_table_by_rows_without_a_second_copy():
    rng, U, _ = factors(2)
    mesh = make_mesh(S)
    table = place_rows(U, capacity=N_USERS + 30, mesh=mesh)
    n_loc = -(-(N_USERS + 30) // S)
    assert table.shape == (S * n_loc, RANK)
    want = np.zeros(table.shape, np.float32)
    want[:N_USERS] = U
    for s, shard in enumerate(table.addressable_shards):
        assert shard.device == mesh.devices.flat[s]
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      want[s * n_loc:(s + 1) * n_loc])
    # a shard with no live row at all is zeros
    small = place_rows(U[:5], capacity=64, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(small)[5:], 0.0)
    np.testing.assert_array_equal(np.asarray(small)[:5], U[:5])


# -- (c) the row write --------------------------------------------------------

def test_publish_update_writes_in_place_into_the_owning_shard():
    reg = obs.reset()
    try:
        rng, U, V = factors(3)
        eng = engine(U, V)
        eng.warmup_publish()
        old = eng._model
        n_loc = old.U.shape[0] // S
        before = values(old.U)
        where = [s.data.unsafe_buffer_pointer()
                 for s in old.U.addressable_shards]
        row = n_loc + 3                              # shard 1's
        queued = eng.submit(int(row))                # admitted BEFORE
        U2 = U.copy()
        U2[row] = rng.standard_normal(RANK).astype(np.float32)
        eng.publish_update(U2, V, touched_users=[row])
        new = eng._model
        assert old.U.is_deleted() and new.U.shape == old.U.shape
        assert [s.data.unsafe_buffer_pointer()
                for s in new.U.addressable_shards] == where
        after = values(new.U)
        changed = np.flatnonzero((after != before).any(axis=1))
        assert changed.tolist() == [row]
        np.testing.assert_array_equal(after[row], U2[row])
        assert reg.counter_value("serving.user_table_writes",
                                 how="inplace") == 1
        # dequeued after the write: the new row answers
        eng.serve_batch(eng.batcher.next_batch(timeout=1.0))
        got_s, got_i = queued.result(timeout=10)
        want_s, want_i = drain(engine(U2, V, mesh=False), [int(row)])[0]
        assert got_i.tolist() == want_i.tolist()
        assert np.abs(got_s - want_s).max() <= SCORE_ULPS * np.spacing(
            np.abs(want_s).max())
    finally:
        obs.reset()


# -- (d) one pinned program a bucket ------------------------------------------

class CountingCalls:
    def __init__(self, compiled):
        self.compiled, self.calls = compiled, 0

    def __call__(self, *args):
        self.calls += 1
        return self.compiled(*args)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_after_warmup_a_batch_runs_one_pinned_program(bucket):
    import jax.monitoring

    reg = obs.reset()
    try:
        _, U, V = factors(4)
        eng = engine(U, V)
        plans = [e for e in reg._events if e["type"] == "serving_mesh_plan"]
        assert [e["bucket"] for e in plans] == list(BUCKETS)
        assert {(e["shards"], e["items_per_shard"], e["users_per_shard"],
                 e["k_loc"]) for e in plans} == {
            (S, -(-N_ITEMS // S), eng._model.U.shape[0] // S, K)}
        assert set(eng._pinned) == {(B, p) for B in BUCKETS
                                    for p in ("int8", "exact")}
        pin = eng._pinned[(bucket, "int8")] = CountingCalls(
            eng._pinned[(bucket, "int8")])
        compiled = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _, **kw: compiled.append(event)
            if event.endswith("backend_compile_duration") else None)
        rows = bucket - 3
        answers = drain(eng, list(range(rows)))
        assert len(answers) == rows and pin.calls == 1
        assert not compiled
        assert (bucket, "int8") in eng._pinned       # it ran, and stays
        rec = eng.batch_flight.records()[-1]
        assert (rec["bucket"], rec["path"]) == (bucket, "int8_sharded")
        plan = next(e for e in plans if e["bucket"] == bucket)
        assert reg.counter_value("serving.mesh_exchange_bytes") \
            == plan["exchange_bytes"]
    finally:
        obs.reset()


# -- (e) what the compiled programs hold --------------------------------------

COLLECTIVE = re.compile(
    r"\b(all-gather|all-reduce|all-to-all|collective-permute|"
    r"reduce-scatter)(-start)?\(")


def table_shaped(text, rows_loc, rank, pattern):
    """Lines of the compiled text that apply ``pattern`` to an array with
    a whole shard's rows."""
    return [ln for ln in text.splitlines() if re.search(pattern, ln)
            and re.search(rf"\[{rows_loc},{rank}\]", ln)]


def test_compiled_row_write_has_no_table_shaped_copy_or_collective():
    _, U, V = factors(5)
    eng = engine(U, V)
    m = eng._model
    n_loc = m.U.shape[0] // S
    rows, vals = jax.device_put(
        (np.full(8, m.U.shape[0], np.int32), np.zeros((8, RANK), np.float32)),
        eng._replicated)
    text = engine_module._build_mesh_scatter(eng.mesh).lower(
        m.U, rows, vals).compile().as_text()
    assert "input_output_alias" in text
    assert not COLLECTIVE.search(text)
    assert not table_shaped(text, n_loc, RANK, r" copy\(")


def test_compiled_scoring_program_moves_queries_and_answers_only():
    _, U, V = factors(6)
    eng = engine(U, V)
    m = eng._model
    n_loc, ni_loc = m.U.shape[0] // S, m.index.ni_loc
    text = eng._pinned[(8, "int8")].as_text()
    moved = [ln for ln in text.splitlines() if COLLECTIVE.search(ln)]
    assert moved
    for ln in moved:                # [8, rank] queries, [.., 8, k] answers
        assert not re.search(rf"\[({n_loc}|{ni_loc}),", ln), ln
    assert not table_shaped(text, n_loc, RANK, r" copy\(")
    assert not table_shaped(text, ni_loc, RANK, r" copy\(")
    for scope in SERVE_MESH_SCOPES:
        assert scope in text


# -- the closed form of the exchange -------------------------------------------

@pytest.mark.parametrize("bucket", BUCKETS)
def test_exchange_bytes_match_the_traced_program(bucket):
    _, U, V = factors(7)
    eng = engine(U, V)
    m = eng._model
    packed = eng._proto(bucket, RANK)       # placed as a batch is
    spread = mesh_spread_bytes(S, bucket, RANK)
    for call, idx in ((eng._int8_call(m, m.index, packed), m.index),
                      (eng._exact_call(m, packed), None)):
        fn, args, _ = call
        traced, breakdown = collective_bytes(fn, *args, axis_size=S)
        plan = eng._mesh_plan(m, idx, bucket)
        assert set(breakdown) == {"psum", "all_gather"}
        # the batch's spread and the lookup, an all-reduce each
        assert breakdown["psum"] == spread + 2 * (S - 1) * bucket * RANK
        assert (plan["placements"], plan["spread_bytes"]) == (1, spread)
        assert traced == plan["exchange_bytes"] == mesh_exchange_bytes(
            S, bucket, RANK, K)
    assert mesh_spread_bytes(4, 8, 256) == 12384       # 1.5 x 8,256
    assert mesh_exchange_bytes(4, 8, 256, 10) == 12384 + 12288 + 1920


# -- the exact fallback ----------------------------------------------------------

def test_exact_fallback_scores_per_shard_and_uploads_nothing(monkeypatch):
    _, U, V = factors(8)
    eng = ServingEngine(k=K, buckets=(8,), mesh=make_mesh(S))
    eng.publish(U, V, quantize=False)          # no index: exact serves
    eng.warmup()
    m = eng._model
    assert m.index is None and len(m.V.sharding.device_set) == S
    assert [int(s.data.shape[0]) for s in m.V.addressable_shards] \
        == [-(-N_ITEMS // S)] * S
    uploads = []
    real = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: (
        uploads.append(np.asarray(x).nbytes if not isinstance(x, tuple)
                       else 0), real(x, *a, **k))[1])
    got = drain(eng, [3, 2700, N_USERS - 1])
    # the staged batch rides the program's call; the last catalog id
    # went up once, in ``warmup()``: no ``device_put`` on a batch's path
    assert uploads == []
    want = drain(engine(U, V, mesh=False), [3, 2700, N_USERS - 1])
    for (ws, wi), (gs, gi) in zip(want, got):
        assert gi.tolist() == wi.tolist()
        assert np.abs(gs - ws).max() <= SCORE_ULPS * np.spacing(
            np.abs(ws).max())
    assert eng.batch_flight.records()[-1]["path"] == "exact"


# -- (g) a segment joined at the shortlist's last stage (PR 43) ---------------


@pytest.mark.parametrize("state", ["free_slots", "overridden", "appended",
                                   "full"])
@pytest.mark.parametrize("n_items,shortlist_k,stages", [(N_ITEMS, 128, 1),
                                                        (36_000, 16, 2)])
def test_a_shards_score_with_a_segment_answers_as_concatenated(
        monkeypatch, state, n_items, shortlist_k, stages):
    """``shortlist_rescore`` on a shard hands its shortlist the
    (replicated) segment's scores as a ``tail``: on every seeded state of the segment the sharded
    program's scores and ids are, bit for bit, those of the program that
    concatenated them to its shard's matrix."""
    from tests.test_live_items import concatenated_top_k, segment_states

    rng = np.random.default_rng(43 + n_items)
    V = rng.standard_normal((n_items, RANK)).astype(np.float32)
    sh = index_module.build_sharded_index(V, make_mesh(S),
                                          shortlist_k=shortlist_k)
    # the shards' stride leaves spare ids past the catalog to append to
    spare = sh.capacity - n_items
    assert spare >= 1
    idx = segment_states(sh, V, rng, slots=32, appended=min(spare, 9))[state]
    plan = idx.shortlist_plan(rows=9)
    assert (plan.stages, plan.columns, plan.tail) == (stages, idx.ni_loc, 32)
    Q = jax.numpy.asarray(
        rng.standard_normal((9, RANK)).astype(np.float32))
    got = idx.topk(Q, K)
    monkeypatch.setattr(index_module, "shortlist_topk", concatenated_top_k)
    k_loc, sk_loc = idx.shard_widths(K)
    # the builder behind its cache: a program of its own, traced now
    want = index_module._build_sharded_int8.__wrapped__(
        idx.mesh, K, k_loc, sk_loc, idx.ni_loc, True)(Q, *idx.score_args())
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    if state in ("appended", "full"):
        assert np.isin(np.asarray(got[1]), idx.d_rows).any()


# -- (h) one placement a batch (PR 44) ----------------------------------------

ALL_BUCKETS = (8, 32, 128)


def given_to_every_shard(monkeypatch, eng, path, st):
    """The packed response of the parent's form of a mesh engine's
    ``path`` program: the engine's own builder (behind its cache: traced
    now) with the spread taken out, on the staged batch given to every
    shard whole — a placement a shard."""
    monkeypatch.setattr(engine_module, "_mesh_spread",
                        lambda block, axis: block)
    m = eng._model
    whole = jax.device_put(np.tile(st, (S, 1)), eng._by_rows)
    if path == "int8":
        _, args, _ = eng._int8_call(m, m.index, whole)
        fn = index_module._build_sharded_int8.__wrapped__(
            eng.mesh, K, *m.index.shard_widths(K), m.index.ni_loc, False,
            engine_module._mesh_queries, engine_module._pack_response,
            "serve_mesh_int8")
    else:
        _, args, _ = eng._exact_call(m, whole)
        ni_loc = int(m.V.shape[0]) // S
        fn = engine_module._build_mesh_exact.__wrapped__(
            eng.mesh, K, min(K, ni_loc), ni_loc, min(eng.item_chunk, ni_loc))
    return np.asarray(fn(*args))


def staged_batch(rng, U, bucket, rows):
    """A staged batch as ``_staged`` lays it out: ``rows`` requests, ids
    of every shard and every fourth by vector, then pad slots."""
    st = np.zeros((bucket, RANK + 2), np.int32)
    st[:rows, RANK] = rng.choice(N_USERS, rows, replace=False)
    st[:4, RANK] = [0, N_USERS - 1, N_USERS // 2, N_USERS // 4]
    for j in range(3, rows, 4):
        st[j, :RANK] = (U[st[j, RANK]] + rng.standard_normal(RANK).astype(
            np.float32) / 8).view(np.int32)
        st[j, RANK:] = 0, 1
    return st


@pytest.fixture(scope="module")
def wide_engine():
    rng, U, V = factors(44)
    eng = ServingEngine(k=K, buckets=ALL_BUCKETS, shortlist_k=128,
                        mesh=make_mesh(S))
    eng.publish(U, V)
    eng.warmup()
    return rng, U, eng


@pytest.mark.parametrize("path", ["int8", "exact"])
@pytest.mark.parametrize("bucket", ALL_BUCKETS)
def test_one_placement_answers_as_a_placement_a_shard_did(
        monkeypatch, wide_engine, bucket, path):
    """The program that spreads ONE placed block answers, bit for bit, as
    the parent's program did on the batch given to every shard whole: the
    int8 program and the exact fallback, every bucket."""
    rng, U, eng = wide_engine
    m = eng._model
    st = staged_batch(rng, U, bucket, bucket - 3)
    call = eng._int8_call if path == "int8" else eng._exact_call
    _, args, _ = call(*((m, m.index) if path == "int8" else (m,)),
                      eng._place_one(st))
    got = np.asarray(eng._pinned[(bucket, path)](*args))
    want = given_to_every_shard(monkeypatch, eng, path, st)
    assert got.shape == (bucket, 2 * K)
    assert got.tobytes() == want.tobytes()
    # by id and by vector, rows of every shard: not the same answer twice
    assert len({r.tobytes() for r in got[:bucket - 3]}) == bucket - 3


def blocks(placed):
    """A placed batch's shards, in the order of their rows."""
    return sorted(placed.addressable_shards, key=lambda s: s.index[0].start)


def test_place_one_transfers_one_block_and_keeps_the_others(wide_engine):
    rng, U, eng = wide_engine
    st = staged_batch(rng, U, 8, 5)
    first, second = eng._place_one(st), eng._place_one(st.copy())
    assert first.shape == (S * 8, RANK + 2) and first.dtype == np.int32
    assert first.sharding.is_equivalent_to(eng._by_rows, 2)
    assert [s.device for s in blocks(first)] == list(eng.mesh.devices.flat)
    assert np.asarray(blocks(first)[0].data).tobytes() == st.tobytes()
    for s in blocks(first)[1:]:
        assert not np.asarray(s.data).any()
    # the zeros lie where they lay: the same buffers in every batch's array
    where = [[s.data.unsafe_buffer_pointer() for s in blocks(a)]
             for a in (first, second)]
    assert where[0][1:] == where[1][1:] and where[0][0] != where[1][0]


@pytest.mark.parametrize("path", ["int8", "exact"])
def test_the_pin_takes_the_sharding_dispatch_hands_it_and_never_compiles(
        path):
    """``warmup()``'s prototype is placed as a batch is, so the pinned
    program's input sharding is the batch's, the pin takes all of 50
    batches and nothing compiles (the jit call after a dropped pin:
    ``tests/test_serving_dispatch.py``)."""
    from tests.conftest import CompileCount

    rng, U, V = factors(45)
    eng = ServingEngine(k=K, buckets=BUCKETS, shortlist_k=128,
                        mesh=make_mesh(S))
    eng.publish(U, V, quantize=path == "int8")
    eng.warmup()
    for B in BUCKETS:
        packed_in = eng._pinned[(B, path)].input_shardings[0][1]
        handed = eng._place_one(eng._staged((), B, RANK)).sharding
        assert packed_in.is_equivalent_to(handed, 2)
        assert not packed_in.is_fully_replicated
    pin = eng._pinned[(8, path)] = CountingCalls(eng._pinned[(8, path)])
    compiles = CompileCount()
    for j in range(50):
        drain(eng, [int(rng.integers(N_USERS)) for _ in range(1 + j % 8)])
    assert (pin.calls, compiles.n) == (50, 0)


def test_upload_span_and_plan_event_say_one_placement(tmp_path):
    """The words that say the entry engaged: the upload span's ``how`` and
    the batch record's ``upload_how`` read ``put_one`` (with the staged
    array's bytes), every ``serving_mesh_plan`` event ``placements`` 1
    with the spread's bytes, and ``obs/schema.py`` declares them."""
    import inspect

    from tests.test_serving_spans import _spans_by_line
    from tpu_als.obs import schema

    reg = obs.reset()
    try:
        rng, U, V = factors(46)
        eng = engine(U, V)
        drain(eng, [1, 2, 3])                    # the bucket's program ran
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            drain(eng, [5, 2600, N_USERS - 1])
            drain(eng, list(range(20)))
        finally:
            jax.profiler.stop_trace()
        uploads = [s[3] for s in _spans_by_line(str(tmp_path))
                   if s[0] == schema.SERVE_DISPATCH_SPAN_KEYS[0]]
        assert [(u["how"], u["bytes"]) for u in uploads] == [
            ("put_one", 8 * (RANK + 2) * 4), ("put_one", 32 * (RANK + 2) * 4)]
        assert [r["upload_how"] for r in eng.batch_flight.records()] \
            == ["put_one"] * 3
        plans = [e for e in reg._events if e["type"] == "serving_mesh_plan"]
        assert [(e["bucket"], e["placements"], e["spread_bytes"])
                for e in plans] == [
            (B, 1, mesh_spread_bytes(S, B, RANK)) for B in BUCKETS]
        assert {"placements", "spread_bytes"} <= set(
            schema.EVENTS["serving_mesh_plan"][0])
        assert "upload_how = call|put_one|put" in " ".join(
            schema.EVENTS["flight_record"][1].split())
        assert "put_one: a mesh engine's" in " ".join(
            inspect.getsource(schema).replace("#", " ").split())
    finally:
        obs.reset()
