"""Sharded top-k serving: ``recommendForAll*`` over a device mesh.

The reference serves recommendations with the same machinery it trains
with — blockified factor RDDs, cross-join GEMMs, and a shuffle-merged
``BoundedPriorityQueue`` per user (``MatrixFactorizationModel.
recommendProductsForUsers`` / ``ALSModel.recommendForAllUsers``,
SURVEY.md §3.3).  At config-3 scale (SURVEY.md §6: ~48M items × rank 256)
the opposite factor table no longer fits one device for SERVING any more
than it does for training, so this module gives the serving path the same
two scale-out strategies the trainer has (``parallel/trainer.py``):

- ``all_gather``: query rows stay sharded; each device gathers the full
  item table once and runs the single-device chunked GEMM + running
  ``lax.top_k`` scan (``ops/topk.py``).  One collective, full-table HBM.
- ``ring``: the item-factor shards stream around the mesh via
  ``ppermute`` (the training ring's dataflow re-used for serving); each
  device folds one shard's local top-k into its running (scores, ids)
  per step.  The full table never materializes — peak HBM is two shards
  + the [n, k] running state, and the cross-device traffic is the item
  table once around the ring plus nothing else (the [n, 2k] merge is
  local).

Tie-breaking note: with equal scores the selected index can differ
between ``all_gather`` and ``ring`` (merge order is shard-rotation
order, which differs per device); scores agree to f32
reduction-order rounding (the per-strategy GEMM shapes differ — the
``SCORE_ULPS`` contract of tpu_als/serving/index.py).
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpu_als import obs
from tpu_als.ops.topk import NEG_INF, chunked_topk_scores
from tpu_als.parallel.mesh import AXIS, shard_map
from tpu_als.resilience import faults

STRATEGIES = ("all_gather", "ring")


class ServeShardLost(RuntimeError):
    """A sharded top-k gather failed (lost/stale factor shard) and no
    last-good catalog is cached to degrade onto — the request cannot be
    answered.  Callers that can shed load should catch this; the first
    successful request after recovery repopulates the cache."""


# (V, valid) REFERENCES from the last successful single-process sharded
# serve, keyed by mesh device ids ONLY — the degraded path answers from
# this host-side catalog when a gather fails.  Keyed, not a single
# global: two meshes in one process (a pod host serving two slices, the
# test harness) must never answer each other's requests from the wrong
# catalog.  Bounded to ONE entry per mesh — the newest publish replaces
# whatever any strategy served before (an answer from catalog
# generation g is correct for every strategy, so per-strategy entries
# only multiplied full-catalog retention by len(STRATEGIES)) — and the
# entry shares the caller's arrays instead of copying (``np.asarray``
# on the already-converted serving arrays is a view).  One catalog
# reference per mesh is the availability price — see
# docs/resilience.md.  The lock guards the dict against concurrent
# serving threads (the engine loop plus direct callers).
_last_good = {}
_last_good_lock = threading.Lock()


def _cache_key(mesh):
    return tuple(int(d.id) for d in mesh.devices.flat)


def reset_last_good():
    """Drop the degraded-serving cache (tests; memory pressure)."""
    with _last_good_lock:
        _last_good.clear()


def _serve_degraded(U, k, Nu, mesh, strategy, reason, record):
    """Answer from the last-good catalog on ONE device.  Slower and
    possibly stale — but an answer, which beats a crash for a
    recommender (the scores were approximate to begin with)."""
    with _last_good_lock:
        entry = _last_good.get(_cache_key(mesh))
    if entry is None:
        raise ServeShardLost(
            f"sharded top-k failed ({reason}) and no last-good factors "
            "are cached for this mesh to serve degraded from")
    Vg, validg = entry
    kk = min(k, Vg.shape[0])
    obs.counter("serve.degraded")
    obs.emit("serve_degraded", strategy=strategy, reason=reason)
    s, ix = chunked_topk_scores(jnp.asarray(U), jnp.asarray(Vg),
                                jnp.asarray(validg), kk)
    out = (np.asarray(s)[:Nu], np.asarray(ix)[:Nu].astype(np.int32))
    record(Nu)
    return out


def _merge_topk(s1, i1, s2, i2, k):
    """Fold (s2, i2) into the running (s1, i1): one [n, k1+k2] top_k."""
    cat_s = jnp.concatenate([s1, s2], axis=1)
    cat_i = jnp.concatenate([i1, i2], axis=1)
    new_s, sel = jax.lax.top_k(cat_s, k)
    return new_s, jnp.take_along_axis(cat_i, sel, axis=1)


@functools.lru_cache(maxsize=32)
def _build(mesh, ni_loc, k, k_loc, strategy, item_chunk):
    """Compiled sharded top-k for one (mesh, shapes, k, strategy) tuple.

    ``jax.sharding.Mesh`` is hashable, so the cache key is exact; without
    the cache every serving call would rebuild the shard_map closure and
    recompile.
    """
    D = mesh.devices.size

    def body_all_gather(U_loc, V_loc, valid_loc):
        V_full = jax.lax.all_gather(V_loc, AXIS, axis=0, tiled=True)
        valid_full = jax.lax.all_gather(valid_loc, AXIS, axis=0,
                                        tiled=True)
        return chunked_topk_scores(U_loc, V_full, valid_full, k,
                                   item_chunk=item_chunk)

    def body_ring(U_loc, V_loc, valid_loc):
        me = jax.lax.axis_index(AXIS)
        perm = [(i, (i + 1) % D) for i in range(D)]
        n = U_loc.shape[0]

        def step(t, carry):
            V_cur, valid_cur, s, ix = carry
            # device i starts with its own shard and receives from i-1:
            # after t permutes it holds shard (i - t) mod D
            owner = jax.lax.rem(me - t + D, D)
            sc_t, ix_t = chunked_topk_scores(U_loc, V_cur, valid_cur,
                                             k_loc,
                                             item_chunk=item_chunk)
            s, ix = _merge_topk(s, ix, sc_t,
                                owner.astype(jnp.int32) * ni_loc + ix_t,
                                k)
            return (jax.lax.ppermute(V_cur, AXIS, perm),
                    jax.lax.ppermute(valid_cur, AXIS, perm), s, ix)

        s0 = jnp.full((n, k), NEG_INF, dtype=jnp.float32)
        i0 = jnp.zeros((n, k), dtype=jnp.int32)
        _, _, s, ix = jax.lax.fori_loop(
            0, D, step, (V_loc, valid_loc, s0, i0))
        return s, ix

    body = body_all_gather if strategy == "all_gather" else body_ring
    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)),
        check_vma=False,
    ))


def topk_sharded(U, V, k, mesh, strategy="all_gather", item_valid=None,
                 item_chunk=8192, return_info=False):
    """Top-k over a mesh: ``U`` rows sharded as queries, ``V`` rows
    sharded as the catalog.  Identical (up to tie-breaking) to
    ``chunked_topk_scores(U, V, valid, k')`` on one device, with
    ``k' = min(k, len(V))``.

    Return contract depends on the deployment: single-process → host
    numpy ``(scores [Nu, k'], indices [Nu, k'])``; multi-process
    (``jax.process_count() > 1``) → GLOBAL jax.Arrays whose row shards
    live across hosts — read ``.addressable_shards`` for this host's
    rows (``shard.index[0].start`` is the global row offset).  The
    higher-level ``ALSModel.recommendFor*`` surfaces refuse the
    multi-process case rather than crash mid-assembly.

    Degraded mode (single-process only): when the sharded execute fails
    — a lost/stale factor shard, a device error, or the ``serve.gather``
    fault point — the request is answered from the last catalog this
    SAME mesh successfully served (any strategy — newest publish wins;
    the cache holds one catalog reference per mesh) on one device
    instead of crashing
    (``serve.degraded`` counter + ``serve_degraded`` event); with no
    last-good catalog cached, the typed :class:`ServeShardLost` raises.
    ``return_info=True`` appends ``{"degraded": bool, "reason": ...}``
    to the return tuple so callers can surface staleness.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown serving strategy {strategy!r} "
                         f"(expected one of {STRATEGIES})")
    t0 = time.perf_counter()

    def _record(nrows):
        # latency histogram + throughput counters: dict writes under a
        # lock, so instrumentation stays in the noise on the serve path
        obs.histogram("serve.request_seconds",
                      time.perf_counter() - t0, strategy=strategy)
        obs.counter("serve.requests")
        obs.counter("serve.rows", nrows)

    def _info(out, degraded, reason=None):
        return out + ({"degraded": degraded, "reason": reason},) \
            if return_info else out

    U = np.asarray(U, dtype=np.float32)
    V = np.asarray(V, dtype=np.float32)
    Nu, r = U.shape
    Ni = V.shape[0]
    if Ni == 0 or Nu == 0:
        kk = min(k, Ni)
        _record(Nu)
        return _info((np.zeros((Nu, kk), np.float32),
                      np.zeros((Nu, kk), np.int32)), False)
    valid = (np.ones(Ni, dtype=bool) if item_valid is None
             else np.asarray(item_valid, dtype=bool))
    D = mesh.devices.size
    k_eff = min(k, Ni)
    nu_loc = -(-Nu // D)
    ni_loc = -(-Ni // D)
    Vp = np.pad(V, ((0, D * ni_loc - Ni), (0, 0)))
    validp = np.pad(valid, (0, D * ni_loc - Ni))  # pad rows never win
    k_loc = min(k_eff, ni_loc)
    f = _build(mesh, ni_loc, k_eff, k_loc, strategy,
               min(item_chunk, ni_loc if strategy == "ring"
                   else D * ni_loc))
    Up = np.pad(U, ((0, D * nu_loc - Nu), (0, 0)))
    # place shard-wise (NOT jnp.asarray, which would commit the FULL
    # padded catalog to one device before resharding — the exact OOM the
    # ring strategy exists to avoid at 48M-item scale)
    from tpu_als.parallel.mesh import shard_leading

    spec = shard_leading(mesh)
    multiproc = jax.process_count() > 1
    try:
        with obs.span("serve.topk", strategy=strategy):
            # fault point: raise = failed gather collective; corrupt =
            # a shard is stale/lost (nothing sane to execute against)
            if faults.check("serve.gather") == "corrupt":
                raise ServeShardLost("stale/lost factor shard")
            s, ix = f(jax.device_put(Up, spec),
                      jax.device_put(Vp, spec),
                      jax.device_put(validp, spec))
            if multiproc:
                # multi-process mesh: the result is a GLOBAL array whose
                # shards live across hosts — np.asarray would fail on
                # non-addressable shards.  Trim the query padding on
                # device (every process executes the same op) and hand
                # the global arrays back; the caller reads
                # .addressable_shards for its own rows.
                _record(Nu)
                return _info((s[:Nu], ix[:Nu]), False)
            out = np.asarray(s)[:Nu], np.asarray(ix)[:Nu]
    except (OSError, RuntimeError) as e:
        if multiproc:
            # every process must degrade identically for the fallback to
            # be coherent; with no way to agree on that here, fail loud
            raise
        reason = f"{type(e).__name__}: {e}"
        return _info(_serve_degraded(U, k, Nu, mesh, strategy, reason,
                                     _record), True, reason)
    with _last_good_lock:
        _last_good[_cache_key(mesh)] = (V, valid)
    _record(Nu)
    return _info(out, False)
