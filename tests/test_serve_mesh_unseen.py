"""The unseen rule on a mesh (PR 52): the users' histories sharded with the
user table, the owning shard handing a batch its lists inside the scoring
program, every shard masking the ids it owns among its own columns — on
four of the CPU's virtual devices, against the one-chip engine given the
same publish and the same requests and against the plain float64
reference (``benchmark/reference/topk_unseen.py``).  Histories are each
user's BEST items (the rule must bite), users and excluded ids lie on
every shard and on the shards' boundaries, and ZERO excluded ids come
back on any path: int8, exact fallback, segment."""

import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import topk_unseen as ref  # noqa: E402
from tpu_als import make_mesh, obs  # noqa: E402
from tpu_als.obs.schema import (  # noqa: E402
    SERVE_EXCLUDE_SCOPE,
    SERVE_MESH_HISTORY_SCOPE,
    SERVE_MESH_SCOPES,
)
from tpu_als.ops.topk import (  # noqa: E402
    NOT_AN_ID,
    excluded_mask,
    topk_validity,
)
from tpu_als.parallel.comm_audit import collective_bytes  # noqa: E402
from tpu_als.parallel.mesh import AXIS, shard_map  # noqa: E402
from tpu_als.serving import index as index_module  # noqa: E402
from tpu_als.serving.engine import MAX_EXCLUDE, ServingEngine  # noqa: E402
from tpu_als.serving.index import (  # noqa: E402
    SCORE_ULPS,
    _shard_merge,
    _topk_jit,
    mask_block,
    mesh_exchange_bytes,
    mesh_history_bytes,
    shard_lists,
    shortlist_rescore,
)

S, K, SK = 4, 10, 64
N_USERS, N_ITEMS, RANK = 4501, 36_866, 16      # 4 divides neither
BUCKETS = (8, 32)
PADS = (64, 512, 4096)
ROWS = {8: 7, 32: 27}
P = jax.sharding.PartitionSpec


def csr(histories):
    indptr = np.concatenate([[0], np.cumsum([len(h) for h in histories])])
    indices = (np.concatenate([np.sort(h) for h in histories])
               if len(histories) else np.empty(0))
    return indptr.astype(np.int64), indices.astype(np.int32)


def drain(eng, requests):
    """Submit ``[(payload, exclude)]``, serve them on the caller's
    thread, return ``[(scores, ids)]``."""
    tickets = [eng.submit(p, exclude=e) for p, e in requests]
    while True:
        batch = eng.batcher.next_batch(timeout=0.01)
        if batch is None:
            break
        eng.serve_batch(batch)
    return [t.result(timeout=10) for t in tickets]


def engine(mesh, **kw):
    return ServingEngine(k=K, buckets=BUCKETS, shortlist_k=SK,
                         mesh=make_mesh(S) if mesh else None, **kw)


@pytest.fixture(scope="module")
def world():
    """Factors, histories and the two engines given the same publish.
    The table's shards: ``n_loc`` user rows and ``ni_loc`` catalog ids
    each.  The items on the shards' boundaries are the BEST items of the
    users on the shards' boundaries (their rows point along those
    users'), so a mask one column off is an answer that differs."""
    rng = np.random.default_rng(52)
    V = (rng.standard_normal((N_ITEMS, RANK)) / 4).astype(np.float32)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    probe = engine(True)
    probe.publish(U, V)
    n_loc = probe._model.U.shape[0] // S
    ni_loc = probe.published_index.ni_loc
    assert N_USERS % S and N_ITEMS % S and (S - 1) * ni_loc < N_ITEMS
    edge_users = sorted({0, N_USERS - 1} | {
        s * n_loc + d for s in range(1, S) for d in (-1, 0)
        if s * n_loc + d < N_USERS})
    edge_items = sorted({0, N_ITEMS - 1} | {
        s * ni_loc + d for s in range(1, S) for d in (-1, 0)})
    for j, i in enumerate(edge_items):
        u = U[edge_users[j % len(edge_users)]]
        V[i] = u * (2.0 + 0.1 * j) / (u @ u)
    # lengths: the edge users 0, 1 and short; then, on every shard, users
    # of every pad's class, the longest exactly 4,096; every other user a
    # few random ids (nobody asks for them)
    lengths = rng.integers(2, 60, N_USERS)
    lengths[edge_users[0]], lengths[edge_users[1]] = 0, 1
    by_pad = {64: list(edge_users), 512: [], 4096: []}
    for s in range(S):
        lo = min(s * n_loc, N_USERS - 16)
        by_pad[64] += list(range(lo + 8, lo + 14))
        by_pad[512] += [lo + 4, lo + 5]
        by_pad[4096] += [lo + 6, lo + 7]
        lengths[[lo + 4, lo + 5]] = 65 + 400 * s // S, 512
        lengths[[lo + 6, lo + 7]] = 513 + s, 4096
    asked = sorted({u for users in by_pad.values() for u in users})
    scores = U[asked].astype(np.float64) @ V.astype(np.float64).T
    best = dict(zip(asked, np.argsort(-scores, axis=1, kind="stable")))
    hist = [best[u][:n] if u in best
            else rng.choice(N_ITEMS, n, replace=False)
            for u, n in enumerate(lengths)]
    # every other boundary item is in its edge user's history, its
    # neighbour is not: the one is never answered, the other always
    for j, i in enumerate(edge_items[::2]):
        u = edge_users[(2 * j) % len(edge_users)]
        if lengths[u] > 1:
            hist[u] = np.union1d(hist[u][:-1], [i])
    one, mesh = engine(False), engine(True)
    for eng in (one, mesh):
        eng.publish(U, V, user_seen=csr(hist))
    return dict(U=U, V=V, hist=hist, best=best, one=one, mesh=mesh,
                n_loc=n_loc, ni_loc=ni_loc, by_pad=by_pad,
                edge_users=edge_users, edge_items=edge_items)


def held_to_reference(answers, Q, V, excluded):
    """No excluded id, the reference's ids slot for slot, its scores, a
    sentinel exactly where the reference has no id left."""
    scores = np.stack([a[0] for a in answers])
    ids = np.stack([a[1] for a in answers])
    real = np.asarray(topk_validity(scores))
    assert ref.seen_returned(ids, excluded, real) == 0
    ref_s, ref_i = ref.exact_topk(Q, V, K, excluded)
    assert (real == (ref_i >= 0)).all()
    assert (np.where(real, ids, -1) == ref_i).all()
    assert np.abs(np.where(real, scores - ref_s, 0)).max() < 1e-4
    assert (np.diff(scores, axis=1) <= 0).all()     # the sentinel is least
    return ids, ref_i


def same_answers(got, want):
    """Id for id, and score for score to ``SCORE_ULPS`` units in the last
    place of the row's largest score — the tolerance the mesh tests
    hold the two engines to (``tests/test_serve_mesh.py``): a shard
    rescores ``n * sk_loc`` gathered columns of ITS slice, the one-chip
    program ``n * shortlist_k`` of the whole catalog, and XLA blocks the
    rank contraction by the GEMM's shape (``serving/index.py``'s
    docstring), so the last bits differ."""
    for (gs, gi), (ws, wi) in zip(got, want):
        real = np.asarray(topk_validity(ws))
        assert gi[real].tolist() == wi[real].tolist()
        assert (np.asarray(topk_validity(gs)) == real).all()
        tol = SCORE_ULPS * np.spacing(np.abs(ws[real]).max(initial=1.0))
        assert np.abs(gs[real] - ws[real]).max(initial=0.0) <= tol


# -- (i) the mesh engine answers as the one-chip engine and the reference ----

@pytest.mark.parametrize("pad", PADS)
@pytest.mark.parametrize("bucket", BUCKETS)
def test_mesh_answers_as_one_chip_and_the_reference(world, bucket, pad):
    U, V, hist, mesh = (world[x] for x in ("U", "V", "hist", "mesh"))
    rng = np.random.default_rng(bucket * pad)
    best = world["best"]
    # users of this pad's class on every shard, the edge users, one
    # request by vector with a list of 64, one by id with a list too
    users = list(world["by_pad"][pad]) + list(world["edge_users"])
    if pad > 64:
        users = list(world["by_pad"][pad]) + users
    pool = list(dict.fromkeys(
        users + list(rng.permutation(world["by_pad"][64]))))
    # one of every shard first, then the pool as it comes
    firsts = [next(u for u in pool if min(u // world["n_loc"], S - 1) == s)
              for s in range(S)]
    users = [int(u) for u in list(dict.fromkeys(firsts + pool))[
        :ROWS[bucket] - 2]]
    assert {min(u // world["n_loc"], S - 1) for u in users} \
        == set(range(S))
    longest = max(len(hist[u]) for u in users)
    assert mesh._model.seen.pad_for([longest]) == pad
    v_user = users[3]
    own = best[v_user][:MAX_EXCLUDE]
    extra = best[users[4]][len(hist[users[4]]):][:5]
    requests = ([(int(u), None) for u in users]
                + [(U[v_user], own), (int(users[4]), extra)])
    Q = np.stack([U[u] for u in users] + [U[v_user], U[users[4]]])
    excluded = ([hist[u] for u in users]
                + [own, np.concatenate([hist[users[4]], extra])])
    got = drain(mesh, requests)
    rec = mesh.batch_flight.records()[-1]
    assert (rec["bucket"], rec["path"]) == (bucket, "int8_sharded")
    ids, ref_i = held_to_reference(got, Q, V, excluded)
    want = drain(world["one"], requests)
    assert world["one"].batch_flight.records()[-1]["path"] == "int8"
    # the one-chip engine keeps ONE shortlist of 64 where the mesh keeps
    # four: behind a history of thousands of a user's best items a few of
    # its answers miss the reference, which the mesh's do not; wherever
    # it has the reference's ids the two engines agree
    at = [j for j, (_, wi) in enumerate(want)
          if (np.where(topk_validity(want[j][0]), wi, -1)
              == ref_i[j]).all()]
    assert len(at) >= 0.8 * len(want)
    same_answers([got[j] for j in at], [want[j] for j in at])
    if pad == 64:
        # the boundary items: in its history never answered, else first
        for j, u in enumerate(users):
            for i in world["edge_items"]:
                if best[u][0] == i:
                    assert (i in hist[u]) != (ids[j, 0] == i)


def test_a_row_with_fewer_than_k_ids_left_carries_sentinels():
    """A catalog of 4,100 ids, a history of 4,096 and one of 4,095 + a
    list of 4: 4 and 1 ids are left, on whichever shards, and the other
    slots hold the sentinel."""
    rng = np.random.default_rng(7)
    n = 4100
    V = (rng.standard_normal((n, RANK)) / 4).astype(np.float32)
    U = rng.standard_normal((6, RANK)).astype(np.float32)
    left = [np.array([0, 1025, 2051, n - 1]), np.array([1024, 3000, 3001,
                                                        3002, 4099])]
    hist = [np.setdiff1d(np.arange(n), left[0]),
            np.setdiff1d(np.arange(n), left[1]), np.arange(3),
            np.empty(0, np.int64), np.arange(5), np.arange(70)]
    eng = ServingEngine(k=K, buckets=(8,), shortlist_k=SK,
                        mesh=make_mesh(S))
    eng.publish(U, V, user_seen=csr(hist))
    requests = [(0, None), (1, left[1][1:]), (2, None)]
    got = drain(eng, requests)
    excluded = [hist[0], np.concatenate([hist[1], left[1][1:]]), hist[2]]
    ids, _ = held_to_reference(got, U[:3], V, excluded)
    assert sorted(ids[0][:4].tolist()) == left[0].tolist()
    assert ids[1][0] == 1024
    real = np.asarray(topk_validity(np.stack([g[0] for g in got])))
    assert real.sum(axis=1).tolist() == [4, 1, K]


# -- (ii) the shards' masks tie to the whole ----------------------------------

@pytest.mark.parametrize("nb,rows,width", [(1024, 8, 64), (9216, 5, 512),
                                           (1001, 8, 64), (2560, 32, 4096)])
def test_the_shards_masks_are_the_unsharded_mask(nb, rows, width):
    """The union over the four shards of the columns each masks is the
    mask the unsharded ``excluded_mask`` builds over all of them: no
    column masked twice, none missed."""
    rng = np.random.default_rng(nb)
    total = S * nb
    lists = np.full((rows, width), NOT_AN_ID, np.int32)
    own = np.full((rows, MAX_EXCLUDE), NOT_AN_ID, np.int32)
    for r in range(rows):
        n = int(rng.integers(0, min(width, total) + 1))
        lists[r, :n] = rng.choice(total, n, replace=False)
        own[r, :9] = [0, nb - 1, nb, 2 * nb - 1, 2 * nb, 3 * nb - 1,
                      3 * nb, total - 1, total]      # the last: no column
    lists[0, :] = NOT_AN_ID                           # an empty history
    seen = (jnp.asarray(lists), jnp.asarray(own))

    def mask(seen, columns):
        block = mask_block(columns)
        return np.asarray(excluded_mask(seen, columns, block).transpose(
            1, 0, 2).reshape(rows, columns))

    whole = mask(seen, total)
    parts = [mask(shard_lists(seen, s * nb, nb), nb) for s in range(S)]
    assert (np.concatenate(parts, axis=1) == whole).all()
    # every id of a row is masked by exactly one shard
    per_row = [len({int(i) for i in np.r_[lists[r], own[r]]
                    if i < total}) for r in range(rows)]
    assert sum(p.sum(axis=1) for p in parts).tolist() == per_row
    assert whole.sum(axis=1).tolist() == per_row


# -- (iii) a shard's score with a segment and lists ---------------------------

@pytest.mark.parametrize("state", ["free_slots", "overridden", "appended",
                                   "full"])
def test_a_shard_with_a_segment_excludes_as_the_unsharded_call(state):
    """``shortlist_rescore(seen=, delta=, shard=)`` per shard, merged,
    against the unsharded call with the same segment and the same lists
    of logical ids: base ids, overridden ids and appended ids among
    them."""
    from tests.test_live_items import segment_states

    n_items, rank = 2003, 32
    rng = np.random.default_rng(len(state))
    V = rng.standard_normal((n_items, rank)).astype(np.float32)
    mesh = make_mesh(S)
    sh = index_module.build_sharded_index(V, mesh, shortlist_k=128)
    spare = sh.capacity - n_items
    sidx = segment_states(sh, V, rng, slots=32,
                          appended=min(spare, 9))[state]
    # the same generation without a mesh: the sharded base's rows and
    # the same segment
    rows, ok = sidx.rows(np.arange(sidx.n_items))
    base = index_module.build_index(V, shortlist_k=128).reserve(
        sh.capacity, 32)
    moved = sidx.d_rows
    one = base.with_updates(moved, rows[moved], ok[moved]) \
        if len(moved) else base
    Q = rng.standard_normal((9, rank)).astype(np.float32)
    s64 = np.where(ok[None, :], Q.astype(np.float64)
                   @ rows.astype(np.float64).T, -np.inf)
    best = np.argsort(-s64, axis=1, kind="stable")
    lists = np.full((9, 64), NOT_AN_ID, np.int32)
    own = np.full((9, MAX_EXCLUDE), NOT_AN_ID, np.int32)
    for r in range(9):
        lists[r, :3 * r] = best[r, :3 * r]
        own[r, :min(len(moved), 6)] = moved[:6]
    seen = (jnp.asarray(lists), jnp.asarray(own))
    k_loc, sk_loc = sidx.shard_widths(K)

    def per_shard(Q, lists, own, Vq, sv, Vs, valid, last_id, *delta):
        me = jax.lax.axis_index(AXIS)
        s, gids = shortlist_rescore(
            Q, Vq, sv, Vs, valid, k=k_loc, shortlist_k=sk_loc, delta=delta,
            seen=(lists, own), shard=(me, sidx.ni_loc))
        return _shard_merge(s, gids, last_id, axis=AXIS, k=K)

    got = jax.jit(shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), P(), P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P())
        + (P(),) * 5, out_specs=(P(), P()), check_vma=False))(
        Q, *seen, *sidx.score_args())
    want = _topk_jit(jnp.asarray(Q), one.Vq, one.sv, one.V, one.valid, k=K,
                     shortlist_k=one.shortlist_k, delta=one._seg,
                     last_id=one._last_id(), seen=seen)
    gi, wi = np.asarray(got[1]), np.asarray(want[1])
    excluded = [np.r_[lists[r][lists[r] < NOT_AN_ID],
                      own[r][own[r] < NOT_AN_ID]] for r in range(9)]
    assert ref.seen_returned(gi, excluded) == 0
    assert (gi == wi).all()
    ws = np.asarray(want[0])
    assert np.abs(np.asarray(got[0]) - ws).max() <= SCORE_ULPS * np.spacing(
        np.abs(ws).max())
    left = s64.copy()
    for r in range(9):
        left[r, excluded[r]] = -np.inf
    assert (gi == np.argsort(-left, axis=1, kind="stable")[:, :K]).all()


# -- (iv) the exact fallback --------------------------------------------------

@pytest.mark.parametrize("bucket", BUCKETS)
def test_exact_fallback_on_a_mesh_excludes(world, bucket):
    U, V, hist = world["U"], world["V"], world["hist"]
    eng = engine(True)
    eng.publish(U, V, quantize=False, user_seen=csr(hist))
    assert eng.published_index is None
    users = (world["by_pad"][4096][:4] + world["edge_users"])[:ROWS[bucket]
                                                               - 1]
    own = world["best"][users[0]][:MAX_EXCLUDE]
    requests = [(int(u), None) for u in users] + [(U[users[0]], own)]
    got = drain(eng, requests)
    assert eng.batch_flight.records()[-1]["path"] == "exact"
    held_to_reference(got, np.stack([U[u] for u in users] + [U[users[0]]]),
                      V, [hist[u] for u in users] + [own])
    one = engine(False)
    one.publish(U, V, quantize=False, user_seen=csr(hist))
    same_answers(got, drain(one, requests))


# -- (v) what a mesh still refuses, and what it carries ------------------------

def test_a_mesh_refuses_histories_that_grow_and_carries_them(world):
    U, V, hist = world["U"], world["V"], world["hist"]
    eng = engine(True)
    eng.publish(U, V, user_seen=csr(hist))
    with pytest.raises(NotImplementedError, match="shard-local write"):
        eng.publish_update(U, V, touched_users=[0],
                           seen_appended=([0], [1]))
    assert eng.published_seq == 1
    # a user row alone: the histories are carried as they are, and a user
    # appended to the table has none
    u = world["by_pad"][512][0]
    U2 = np.concatenate([U, U[u:u + 1]])
    U2[u] = -U[u]
    assert eng.publish_update(U2, V, touched_users=[u]) == (2, "retag")
    assert eng.holds_histories
    got = drain(eng, [(int(u), None), (N_USERS, None)])
    held_to_reference(got, U2[[u, N_USERS]], V,
                      [hist[u], np.empty(0, np.int64)])
    # a request's own list on a mesh engine that published no histories
    bare = engine(True)
    bare.publish(U, V)
    own = world["best"][u][:7]
    held_to_reference(drain(bare, [(U[u], own), (int(u), own)]),
                      U[[u, u]], V, [own, own])


def test_a_history_is_held_once_by_the_shard_that_holds_its_users_row(world):
    seen, hist = world["mesh"]._model.seen, world["hist"]
    n_loc = world["n_loc"]
    assert len(seen.runs.sharding.device_set) == S
    runs = [np.asarray(s.data) for s in sorted(
        seen.runs.addressable_shards, key=lambda s: s.index[0].start)]
    ids = [np.asarray(s.data) for s in sorted(
        seen.indices.addressable_shards, key=lambda s: s.index[0].start)]
    assert {len(r) for r in runs} == {n_loc + 1}
    assert len({len(i) for i in ids}) == 1
    # whole granules and the longest pad of spare ids: the shape does not
    # move with who holds which history
    assert (len(ids[0]) - seen.pads[-1]) % (1 << 16) == 0
    total = 0
    for s in range(S):
        users = range(s * n_loc, min((s + 1) * n_loc, N_USERS))
        assert runs[s][0] == 0
        for j, u in enumerate(users):
            assert ids[s][runs[s][j]:runs[s][j + 1]].tolist() \
                == np.sort(hist[u]).tolist()
        held = int(runs[s][len(users)])
        assert (runs[s][len(users):] == held).all()     # spare rows: none
        assert (ids[s][held:] == NOT_AN_ID).all()
        assert len(ids[s]) - held >= seen.pads[-1]      # no slice clamped
        total += held
    assert total == sum(len(h) for h in hist)


def test_the_histories_shapes_do_not_move_with_who_holds_which(world):
    """The same multiset of histories dealt to other users (another seed
    of the benchmark's cell) is placed in arrays of the same shapes: the
    pinned programs of one publish fit the next."""
    rng = np.random.default_rng(1)
    eng = engine(True)
    shapes = set()
    for _ in range(3):
        order = rng.permutation(N_USERS)
        eng.publish(world["U"], world["V"],
                    user_seen=csr([world["hist"][u] for u in order]))
        seen = eng._model.seen
        shapes.add((seen.runs.shape, seen.indices.shape))
    assert len(shapes) == 1


# -- (vi) what the programs trace ------------------------------------------------

def primitives(jaxpr):
    """Every primitive of a jaxpr, those of its nested jaxprs too."""
    out = []
    for e in jaxpr.eqns:
        out.append(str(e.primitive))
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += primitives(inner)
    return out


def test_without_a_shard_nothing_of_the_mesh_is_traced():
    """The unsharded call with lists traces no collective, and nothing
    for a first id: its primitives are the parent's, listed."""
    nb, n, h = 36_864, 8, 64
    shapes = (jax.ShapeDtypeStruct((n, RANK), jnp.float32),
              jax.ShapeDtypeStruct((nb, RANK), jnp.int8),
              jax.ShapeDtypeStruct((nb,), jnp.float32),
              jax.ShapeDtypeStruct((nb, RANK), jnp.float32),
              jax.ShapeDtypeStruct((nb,), jnp.bool_),
              jax.ShapeDtypeStruct((n, h), jnp.int32),
              jax.ShapeDtypeStruct((n, MAX_EXCLUDE), jnp.int32))
    jax.clear_caches()
    names = primitives(jax.make_jaxpr(
        lambda U, Vq, sv, V, valid, a, b: shortlist_rescore(
            U, Vq, sv, V, valid, k=K, shortlist_k=SK, seen=(a, b)))(
        *shapes).jaxpr)
    # the parent's primitives, counted (``cond``'s two branches and the
    # jitted helpers' bodies among them): no collective, no ``axis_index``,
    # and the three subtractions are the floor divisions' own
    assert dict(collections.Counter(names)) == {
        "abs": 1, "add": 19, "and": 12, "broadcast_in_dim": 20,
        "concatenate": 2, "convert_element_type": 15, "div": 5,
        "dot_general": 2, "eq": 4, "gather": 7, "ge": 1, "iota": 5,
        "jit": 29, "layout_constraint": 1, "lt": 15, "max": 1, "min": 1,
        "mul": 9, "ne": 14, "not": 2, "optimization_barrier": 1,
        "reduce_max": 2, "rem": 6, "reshape": 15, "round": 1,
        "scatter-add": 1, "select_n": 22, "shift_left": 1,
        "shift_right_arithmetic": 2, "shift_right_logical": 1, "sign": 6,
        "slice": 2, "sort": 2, "sub": 3, "top_k": 3, "transpose": 2}
    # on a shard the same call subtracts the shard's first id
    mesh = make_mesh(S)
    sharded = jax.make_jaxpr(shard_map(
        lambda U, Vq, sv, V, valid, a, b: shortlist_rescore(
            U, Vq, sv, V, valid, k=K, shortlist_k=SK, seen=(a, b),
            shard=(jax.lax.axis_index(AXIS), nb)),
        mesh=mesh, in_specs=(P(),) * 7, out_specs=(P(), P()),
        check_vma=False))(*shapes)
    on_a_shard = collections.Counter(primitives(sharded.jaxpr))
    # ``ids - first``, in the test of each list and in its value
    assert on_a_shard["sub"] == 3 + 4 and on_a_shard["axis_index"] == 1


@pytest.mark.parametrize("pad", PADS)
@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_lists_are_all_of_the_rule_that_crosses_a_link(world, bucket,
                                                            pad):
    """The traced programs' collectives: the batch's spread (64 columns
    wider), the lookup, the merge — and ONE all-reduce of the ``[bucket,
    pad]`` lists; the closed forms, the plan and the counters say the
    same bytes."""
    reg = obs.reset()
    try:
        eng = world["mesh"]
        m = eng._model
        packed = eng._proto(bucket, RANK, wide=True)
        for call, idx in ((eng._int8_call(m, m.index, packed, m.seen, pad),
                           m.index),
                          (eng._exact_call(m, packed, m.seen, pad), None)):
            fn, args, _ = call
            traced, breakdown = collective_bytes(fn, *args, axis_size=S)
            plan = eng._mesh_plan(m, idx, bucket, pad)
            assert set(breakdown) == {"psum", "all_gather"}
            assert plan["history_bytes"] == mesh_history_bytes(
                S, bucket, pad) == 2 * (S - 1) * bucket * pad * 4 // S
            assert plan["exchange_bytes"] == mesh_exchange_bytes(
                S, bucket, RANK, K, MAX_EXCLUDE)
            assert traced == plan["exchange_bytes"] + plan["history_bytes"]
            text = fn.lower(*args).as_text(debug_info=True)
            for scope in (*SERVE_MESH_SCOPES, SERVE_MESH_HISTORY_SCOPE,
                          SERVE_EXCLUDE_SCOPE):
                assert scope in text
        assert mesh_history_bytes(4, 8, 4096) == 196_608
        # a batch feeds the counters with its plan's bytes
        users = world["by_pad"][pad][:ROWS[bucket]]
        drain(eng, [(int(u), None) for u in users])
        rec = eng.batch_flight.records()[-1]
        B = rec["bucket"]
        riding = eng._model.seen.pad_for(
            [len(world["hist"][u]) for u in users])
        assert reg.counter_value("serving.mesh_history_bytes") \
            == mesh_history_bytes(S, B, riding)
        assert reg.counter_value("serving.mesh_exchange_bytes") \
            == mesh_exchange_bytes(S, B, RANK, K, MAX_EXCLUDE)
        assert reg.histogram_count("serving.excluded_ids",
                                   source="history") == len(users)
    finally:
        obs.reset()


def test_warmup_pins_one_mesh_program_a_bucket_and_pad(world):
    """``warmup()`` on a mesh generation with histories pins and runs the
    int8 program at every history pad and the exact one at the longest,
    announces each, and a batch rides its pin without compiling."""
    from tests.conftest import CompileCount

    reg = obs.reset()
    try:
        eng = engine(True)
        eng.publish(world["U"], world["V"], user_seen=csr(world["hist"]))
        eng.warmup()
        assert set(eng._pinned) == (
            {(B, "int8", pad) for B in BUCKETS for pad in PADS}
            | {(B, "exact", PADS[-1]) for B in BUCKETS})
        plans = [e for e in reg._events if e["type"] == "serving_mesh_plan"]
        assert [(e["bucket"], e["history_pad"]) for e in plans] == [
            (B, pad) for B in BUCKETS for pad in PADS]
        assert all(e["history_bytes"] == mesh_history_bytes(
            S, e["bucket"], e["history_pad"]) for e in plans)
        masks = [e for e in reg._events if e["type"] == "serving_exclusion"]
        assert {e["columns"] for e in masks if e["path"] == "int8"} \
            == {world["ni_loc"]}
        compiles = CompileCount()
        users = world["by_pad"][512][:5]
        got = drain(eng, [(int(u), None) for u in users])
        assert compiles.n == 0
        held_to_reference(got, world["U"][users], world["V"],
                          [world["hist"][u] for u in users])
        spans = eng.batch_flight.records()[-1]
        assert spans["path"] == "int8_sharded"
        # made ready for histories that grow, a mesh keeps them as they
        # lie and pins the same programs
        eng.warmup_histories()
        assert eng._model.seen.room is None
        assert (8, "int8", 512) in eng._pinned
    finally:
        obs.reset()
