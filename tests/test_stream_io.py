"""Streaming fold-in driver + MovieLens IO tests."""

import numpy as np
import pytest

from tpu_als import ALS, ColumnarFrame
from tpu_als.io.movielens import (
    load_movielens_100k,
    load_movielens_csv,
    synthetic_movielens,
)
from tpu_als.stream.microbatch import FoldInServer

from conftest import make_ratings


def _fitted(rng):
    u, i, r, _, _ = make_ratings(rng, 50, 40, rank=3, density=0.4)
    frame = ColumnarFrame({"user": u, "item": i, "rating": r})
    return ALS(rank=3, maxIter=6, regParam=0.05, seed=0).fit(frame), frame


def test_foldin_server_improves_new_user(rng):
    model, frame = _fitted(rng)
    V = model._V
    # a brand-new user whose tastes follow item-factor direction 0
    pref = V[:, 0]
    top_items = np.argsort(-pref)[:8]
    item_ids = model._item_map.to_original(top_items)
    batch = ColumnarFrame({
        "user": np.full(8, 777_777),
        "item": item_ids,
        "rating": np.full(8, 5.0, dtype=np.float32),
    })
    srv = FoldInServer(model)
    touched = srv.update(batch)
    assert touched.tolist() == [777_777]
    # the new user now exists and predicts high on their liked items
    preds = model.transform(batch)["prediction"]
    assert np.isfinite(preds).all()
    other_items = model._item_map.to_original(np.argsort(pref)[:8])
    low = model.transform(ColumnarFrame({
        "user": np.full(8, 777_777), "item": other_items,
        "rating": np.zeros(8, dtype=np.float32)}))["prediction"]
    assert preds.mean() > low.mean()


def test_foldin_server_existing_user_history_merge(rng):
    model, frame = _fitted(rng)
    uid = int(model._user_map.ids[0])
    before = model._U[0].copy()
    batch = ColumnarFrame({
        "user": np.array([uid]),
        "item": np.array([int(model._item_map.ids[0])]),
        "rating": np.array([5.0], dtype=np.float32),
    })
    srv = FoldInServer(model)
    srv.update(batch)
    after = model._U[model._user_map.to_dense(np.array([uid]))[0]]
    assert not np.allclose(before, after)
    assert len(srv.stats) == 1
    assert np.isfinite(srv.p50_latency())


def test_foldin_server_prewarm_matches_serving_shapes(rng):
    # prewarm compiles the same jit entries update() later hits: after
    # prewarming the grid, a batch whose padded shape is in the grid adds
    # no new cache entry (its latency is serve-only)
    model, frame = _fitted(rng)
    srv = FoldInServer(model)
    srv.prewarm(rows=(8,), widths=(8,))
    from tpu_als.core import foldin as foldin_mod

    sizes0 = foldin_mod._fold_in_jit._cache_size()
    batch = ColumnarFrame({
        "user": np.array([1, 1, 1, 1, 1, 2, 3]),
        "item": model._item_map.to_original(
            np.array([0, 1, 2, 3, 4, 5, 6])),
        "rating": np.full(7, 4.0, np.float32),
    })
    srv.update(batch)  # 3 touched users -> rows pad to 8; max count 5 ->
    # width pads to 8: exactly the prewarmed (8, 8) entry
    assert foldin_mod._fold_in_jit._cache_size() == sizes0


def test_foldin_server_unknown_items_ignored(rng):
    model, _ = _fitted(rng)
    srv = FoldInServer(model)
    batch = ColumnarFrame({
        "user": np.array([1, 2]),
        "item": np.array([10**9, 10**9 + 1]),  # never trained
        "rating": np.array([5.0, 5.0], dtype=np.float32),
    })
    touched = srv.update(batch)
    assert len(touched) == 0


def test_foldin_server_new_item(rng):
    """Symmetric item fold-in: a brand-new item rated 5.0 by a cohort of
    users must (a) become transformable with finite scores, (b) score
    higher for its raters than an anti-cohort, and (c) be visible to
    SUBSEQUENT user fold-ins (the server's cached V refreshes)."""
    model, frame = _fitted(rng)
    U = model._U
    pref = U[:, 1]
    raters = model._user_map.to_original(np.argsort(-pref)[:8])
    anti = model._user_map.to_original(np.argsort(pref)[:8])
    new_item = 888_888
    batch = ColumnarFrame({
        "user": raters,
        "item": np.full(8, new_item),
        "rating": np.full(8, 5.0, dtype=np.float32),
    })
    srv = FoldInServer(model)
    touched = srv.update_items(batch)
    assert touched.tolist() == [new_item]
    hi = model.transform(ColumnarFrame({
        "user": raters, "item": np.full(8, new_item),
        "rating": np.zeros(8, np.float32)}))["prediction"]
    lo = model.transform(ColumnarFrame({
        "user": anti, "item": np.full(8, new_item),
        "rating": np.zeros(8, np.float32)}))["prediction"]
    assert np.isfinite(hi).all() and hi.mean() > lo.mean()
    # a user folded in AFTER the item sees it (cache refreshed): a new
    # user who rates ONLY the new item gets a factor along its direction
    ubatch = ColumnarFrame({
        "user": np.array([999_999]),
        "item": np.array([new_item]),
        "rating": np.array([5.0], np.float32),
    })
    assert srv.update(ubatch).tolist() == [999_999]
    p = model.transform(ColumnarFrame({
        "user": np.array([999_999]), "item": np.array([new_item]),
        "rating": np.zeros(1, np.float32)}))["prediction"]
    assert np.isfinite(p).all() and p[0] > 0


def test_foldin_item_matches_item_half_step(rng):
    """update_items == the item half-step restricted to the touched item
    (same math oracle the user fold-in tests pin)."""
    import jax.numpy as jnp

    from tpu_als.core.foldin import fold_in

    model, frame = _fitted(rng)
    iid = int(model._item_map.ids[3])
    dense_i = 3
    # exact expected factor: regress the item's (training) ratings on U
    u = np.asarray(frame["user"])
    i = np.asarray(frame["item"])
    r = np.asarray(frame["rating"])
    sel = i == iid
    ud = model._user_map.to_dense(u[sel])
    w = len(ud)
    cols = np.zeros((1, w), np.int32); cols[0] = ud
    vals = np.zeros((1, w), np.float32); vals[0] = r[sel]
    mask = np.ones((1, w), np.float32)
    want = np.asarray(fold_in(
        jnp.asarray(model._U), jnp.asarray(cols), jnp.asarray(vals),
        jnp.asarray(mask), 0.05))[0]

    srv = FoldInServer(model)
    srv.update_items(ColumnarFrame({
        "user": u[sel], "item": i[sel], "rating": r[sel]}))
    got = model._V[dense_i]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_foldin_item_unknown_users_ignored(rng):
    model, _ = _fitted(rng)
    srv = FoldInServer(model)
    touched = srv.update_items(ColumnarFrame({
        "user": np.array([10**9, 10**9 + 1]),  # never trained
        "item": np.array([5, 5]),
        "rating": np.array([5.0, 5.0], np.float32),
    }))
    assert len(touched) == 0


def test_synthetic_movielens_shape_and_determinism():
    f1 = synthetic_movielens(200, 100, 5000, seed=3)
    f2 = synthetic_movielens(200, 100, 5000, seed=3)
    assert len(f1) == 5000
    np.testing.assert_array_equal(f1["user"], f2["user"])
    np.testing.assert_array_equal(f1["rating"], f2["rating"])
    assert f1["rating"].min() >= 0.5 and f1["rating"].max() <= 5.0
    # half-star grid
    assert np.all((f1["rating"] * 2) == np.round(f1["rating"] * 2))
    assert f1["user"].max() < 200 and f1["item"].max() < 100


def test_movielens_loaders(tmp_path):
    # u.data format
    udata = tmp_path / "u.data"
    udata.write_text("1\t10\t5\t100\n2\t20\t3\t200\n")
    f = load_movielens_100k(str(tmp_path))
    assert f["user"].tolist() == [1, 2]
    assert f["rating"].tolist() == [5.0, 3.0]
    # ratings.csv format
    csv = tmp_path / "ratings.csv"
    csv.write_text("userId,movieId,rating,timestamp\n1,10,4.5,99\n3,11,2.0,98\n")
    f2 = load_movielens_csv(str(csv))
    assert f2["user"].tolist() == [1, 3]
    assert f2["rating"].tolist() == [4.5, 2.0]
    # trainable end-to-end
    model = ALS(rank=2, maxIter=2).fit(f)
    assert model.rank == 2


def test_movielens_dat_loader(tmp_path):
    from tpu_als.io.movielens import load_movielens_dat

    # ml-1m/ml-10m format: '::' separated, no header, half-star ratings
    dat = tmp_path / "ratings.dat"
    dat.write_text("1::1193::5::978300760\n2::661::3.5::978302109\n\n")
    f = load_movielens_dat(str(tmp_path))  # directory form resolves
    assert f["user"].tolist() == [1, 2]
    assert f["item"].tolist() == [1193, 661]
    assert f["rating"].tolist() == [5.0, 3.5]
    assert f["timestamp"].tolist() == [978300760, 978302109]
    assert f["user"].dtype == np.int64 and f["rating"].dtype == np.float32

    bad = tmp_path / "bad.dat"
    bad.write_text("1::2::3\n")  # missing timestamp field
    with pytest.raises(ValueError, match="malformed ratings line"):
        load_movielens_dat(str(bad))
    bad.write_text("1::2::xx::9\n")  # non-numeric rating
    with pytest.raises(ValueError, match="malformed ratings line"):
        load_movielens_dat(str(bad))


def test_fastcsv_native_parser(tmp_path):
    import time

    from tpu_als.io.fastcsv import load_ratings_csv, load_u_data

    rng = np.random.default_rng(0)
    n = 200_000
    u = rng.integers(1, 10000, n)
    i = rng.integers(1, 5000, n)
    r = np.round(rng.uniform(0.5, 5.0, n) * 2) / 2
    t = rng.integers(10**9, 2 * 10**9, n)
    csv = tmp_path / "ratings.csv"
    with open(csv, "w") as f:
        f.write("userId,movieId,rating,timestamp\n")
        for k in range(n):
            f.write(f"{u[k]},{i[k]},{r[k]},{t[k]}\n")

    t0 = time.perf_counter()
    pu, pi, pr, pt = load_ratings_csv(str(csv))
    dt = time.perf_counter() - t0
    np.testing.assert_array_equal(pu, u)
    np.testing.assert_array_equal(pi, i)
    np.testing.assert_allclose(pr, r.astype(np.float32), rtol=1e-6)
    np.testing.assert_array_equal(pt, t)
    assert dt < 5.0  # 200k rows well under 5s

    tsv = tmp_path / "u.data"
    with open(tsv, "w") as f:
        for k in range(100):
            f.write(f"{u[k]}\t{i[k]}\t{int(r[k])}\t{t[k]}\n")
    pu2, _, pr2, _ = load_u_data(str(tsv))
    assert len(pu2) == 100
    np.testing.assert_array_equal(pu2, u[:100])


def test_fastcsv_no_trailing_newline(tmp_path):
    from tpu_als.io.fastcsv import load_ratings_csv

    csv = tmp_path / "r.csv"
    csv.write_text("userId,movieId,rating,timestamp\n1,2,3.5,100\n7,8,1.0,200")
    pu, pi, pr, pt = load_ratings_csv(str(csv))
    assert pu.tolist() == [1, 7]
    assert pr.tolist() == [3.5, 1.0]
    assert pt.tolist() == [100, 200]


def test_synthetic_return_factors():
    frame, Us, Vs = synthetic_movielens(50, 30, 500, seed=3,
                                        return_factors=True)
    assert Us.shape == (50, 16) and Vs.shape == (30, 16)
    # same seed without factors -> identical frame
    frame2 = synthetic_movielens(50, 30, 500, seed=3)
    assert np.array_equal(frame["rating"], frame2["rating"])
