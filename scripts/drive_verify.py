"""User-style end-to-end drive (the /verify recipe).

Runs the whole library surface the way a user would: synth ratings ->
blocking -> train -> RMSE -> top-k -> fold-in -> Estimator -> two-tower
filtered recall.  ``--platform cpu`` forces the CPU backend; default
drives the device JAX finds.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ap = argparse.ArgumentParser()
ap.add_argument("--platform", default="default", choices=["default", "cpu"])
ap.add_argument("--rank", type=int, default=16)
args = ap.parse_args()

if args.platform == "cpu":
    import jax

    jax.config.update("jax_platforms", "cpu")

import jax
import jax.numpy as jnp

print("devices:", jax.devices(), file=sys.stderr)

from tpu_als.core.als import AlsConfig, predict, train
from tpu_als.core.foldin import fold_in
from tpu_als.core.ratings import build_csr_buckets
from tpu_als.ops.topk import chunked_topk_scores

rng = np.random.default_rng(0)
nU, nI, rank = 3000, 800, args.rank
Ustar = rng.normal(size=(nU, rank)).astype(np.float32) / np.sqrt(rank)
Vstar = rng.normal(size=(nI, rank)).astype(np.float32) / np.sqrt(rank)
nnz = 120_000
u = rng.integers(0, nU, nnz)
i = rng.integers(0, nI, nnz)
r = np.einsum("nr,nr->n", Ustar[u], Vstar[i]) + 0.05 * rng.normal(size=nnz)
r = r.astype(np.float32)

test = rng.random(nnz) < 0.1
ut, it_, rt = u[test], i[test], r[test]
u2, i2, r2 = u[~test], i[~test], r[~test]

ucsr = build_csr_buckets(u2, i2, r2, nU)
icsr = build_csr_buckets(i2, u2, r2, nI)
waste = (ucsr.padded_nnz / ucsr.nnz, icsr.padded_nnz / icsr.nnz)
print(f"padding waste: user {waste[0]:.2f}x item {waste[1]:.2f}x")
assert max(waste) < 2.5, waste

cfg = AlsConfig(rank=rank, max_iter=10, reg_param=0.005, seed=0)
t0 = time.time()
U, V = train(ucsr, icsr, cfg)
print(f"trained in {time.time()-t0:.1f}s")
ones = jnp.ones(len(rt), bool)
pred = np.asarray(predict(U, V, jnp.asarray(ut), jnp.asarray(it_),
                          ones, ones))
rmse = float(np.sqrt(np.mean((pred - rt) ** 2)))
print(f"held-out RMSE {rmse:.4f} vs rating std {rt.std():.4f}")
assert rmse < 0.6 * rt.std(), (rmse, rt.std())

s, idx = chunked_topk_scores(U, V, jnp.ones(nI, bool), k=10)
assert idx.shape == (nU, 10) and np.isfinite(np.asarray(s)).all()
print("top-k ok")

# fold-in: a new user with strong preferences for known items
w = 32
new_items = rng.choice(nI, w, replace=False)
new_r = np.einsum("r,nr->n", Ustar[0], Vstar[new_items]).astype(np.float32)
cols = jnp.asarray(new_items[None])
vals = jnp.asarray(new_r[None])
mask = jnp.ones((1, w), jnp.float32)
uf = np.asarray(fold_in(V, cols, vals, mask, cfg.reg_param))
fold_pred = np.asarray(uf @ np.asarray(V).T)[0, new_items]
corr = np.corrcoef(fold_pred, new_r)[0, 1]
print(f"fold-in corr {corr:.3f}")
assert corr > 0.8, corr

# Estimator surface + cold rows + duplicates
import tpu_als

frame = {"user": np.concatenate([u2, u2[:5]]),
         "item": np.concatenate([i2, i2[:5]]),
         "rating": np.concatenate([r2, r2[:5]])}
als = tpu_als.ALS(rank=8, maxIter=4, regParam=0.005, seed=0,
                  coldStartStrategy="nan")
model = als.fit(frame)
out = model.transform({"user": ut[:100], "item": it_[:100]})
assert np.isfinite(out["prediction"]).all()
cold = model.transform({"user": np.array([nU + 7]), "item": it_[:1]})
assert np.isnan(cold["prediction"]).all()
rec = model.recommendForAllUsers(5)
assert len(rec["user"]) > 0
print("estimator ok (cold rows nan, duplicates absorbed)")

# nonnegative + bfloat16 paths compile and stay finite
cfg_nn = AlsConfig(rank=8, max_iter=2, reg_param=0.01, nonnegative=True,
                   seed=0)
Un, Vn = train(ucsr, icsr, cfg_nn)
assert float(np.asarray(Un).min()) >= 0.0
cfg_bf = AlsConfig(rank=8, max_iter=2, reg_param=0.01,
                   compute_dtype="bfloat16", seed=0)
Ub, Vb = train(ucsr, icsr, cfg_bf)
assert np.isfinite(np.asarray(Ub)).all()
print("nonnegative + bfloat16 ok")

# streaming both directions: a NEW user then a NEW item through the
# FoldInServer, each servable immediately (round-4 symmetric fold-in)
from tpu_als.stream.microbatch import FoldInServer
from tpu_als.utils.frame import ColumnarFrame

srv = FoldInServer(model)
known_items = model._item_map.ids[:6]
assert srv.update(ColumnarFrame({
    "user": np.full(6, 10**7), "item": known_items,
    "rating": np.full(6, 5.0, np.float32)})).tolist() == [10**7]
known_users = model._user_map.ids[:6]
assert srv.update_items(ColumnarFrame({
    "user": known_users, "item": np.full(6, 10**7 + 1),
    "rating": np.full(6, 5.0, np.float32)})).tolist() == [10**7 + 1]
p = model.transform({"user": np.array([10**7]),
                     "item": np.array([10**7 + 1])})["prediction"]
assert np.isfinite(p).all()
print("fold-in server ok (new user + new item served)")

# rank-256 blocked lanes factorization (interpret off-TPU, real on chip)
from tpu_als.ops.pallas_lanes_blocked import chol_lanes_blocked

M = rng.normal(size=(4, 256, 256)).astype(np.float32) / 16.0
Aspd = jnp.asarray(M @ M.transpose(0, 2, 1)
                   + 0.5 * np.eye(256, dtype=np.float32)[None])
interp = args.platform == "cpu" or jax.devices()[0].platform != "tpu"
Lb = np.asarray(chol_lanes_blocked(Aspd, interpret=interp))
Lref = np.linalg.cholesky(np.asarray(Aspd, np.float64))
assert np.abs(Lb - Lref).max() / np.abs(Lref).max() < 1e-3
print("rank-256 blocked lanes cholesky ok")

# two-tower filtered recall sanity
from tpu_als.models.two_tower import (TwoTowerConfig, recall_at_k,
                                      train_two_tower)

pos = r2 > np.quantile(r2, 0.7)
tt = train_two_tower(u2[pos], i2[pos], nU, nI,
                     TwoTowerConfig(embed_dim=8, hidden=(16,), out_dim=8,
                                    epochs=2, batch_size=1024, seed=0))
rec_f = recall_at_k(tt, ut[:2000], it_[:2000], k=10,
                    exclude=(u2[pos], i2[pos]))
print(f"two-tower filtered recall@10 {rec_f:.4f}")
assert 0.0 <= rec_f <= 1.0

print("DRIVE OK")
