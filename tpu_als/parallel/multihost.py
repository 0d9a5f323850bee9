"""Multi-host bring-up — the analog of Spark's cluster boot.

The reference stack scales past one machine with Spark's driver/executor
runtime: executors register with the driver over netty RPC and each holds
its partitions (SURVEY.md §2.B8).  The TPU-native equivalent is JAX's
multi-controller model: every host runs this same program,
``jax.distributed.initialize`` rendezvouses them over DCN, and afterwards
``jax.devices()`` spans the whole deployment, so
:func:`tpu_als.parallel.mesh.make_mesh` builds one global (slice-major)
mesh and the ``shard_map`` trainer is unchanged — XLA routes each
collective over ICI within a slice and DCN across (SURVEY.md §5.8).

What IS per-host is the data: at Amazon-2023 scale (~570M ratings,
BASELINE.json config 3) no host should materialize the full rating set.
:func:`local_positions` + :func:`local_rating_mask` give each process the
mesh-axis positions its devices own and the subset of COO ratings that
land there, so blocking (`build_csr_buckets` / `build_a2a`) runs on the
local shard only — the analog of executors building only their own
``InBlock``s.

Scope: three multi-process entry tiers, all exercised by REAL spawned
two-process gloo tests in ``tests/test_multihost.py``:

1. ``ALS(mesh=...).fit(frame)`` — every host fits the same replicated
   frame (``dataMode='replicated'``, the default) or its own disjoint
   split (``dataMode='per_host'``: id maps are agreed via
   :func:`global_id_union`, triples exchanged inside
   :func:`train_multihost`); factors match the single-process mesh fit
   exactly (same partitions/init/layout).  All runtime knobs are wired:
   gatherStrategy, checkpoint/resume, and ``fitCallback`` (entity-space
   gather every ``fitCallbackInterval`` iterations, invoked on process 0).
2. ``tpu_als.cli train`` — same convention, plus holdout eval and model
   save on process 0.
3. :func:`train_multihost` — per-host rating splits (redistributed or
   ``replicated=True``), for custom loops; built on
   ``data.shard_csr(positions=...)`` blocking into the globally-agreed
   ``data.shard_layout`` shapes and
   ``jax.make_array_from_process_local_data`` placement.
"""

from __future__ import annotations

import os

import numpy as np

import jax


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None, retry_policy=None):
    """Connect this process to the deployment (no-op when single-process).

    Resolution order: explicit args → the standard JAX env vars
    (``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID``, also set by TPU pod launchers) → single-process
    no-op.  Must run before first JAX use, like Spark's ``SparkContext``
    construction must precede any job.

    The rendezvous is retried under ``tpu_als.resilience.retry``
    (default: 5 attempts, 1s base exponential backoff) — a coordinator
    that is still binding its port, or a DCN blip, is the single most
    common pod-launch flake and must not kill the whole deployment.
    Fault point ``multihost.init`` fires inside each rendezvous attempt.
    Returns (process_index, process_count).
    """
    from tpu_als.resilience import faults
    from tpu_als.resilience.retry import RetryPolicy, retry_call

    coordinator_address = (coordinator_address
                           or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if coordinator_address and jax.distributed.is_initialized():
        # idempotent: a launcher (or test worker) may have rendezvoused
        # before handing control to code that also calls this — a second
        # jax.distributed.initialize would raise (the backend is up)
        coordinator_address = None

    def _rendezvous():
        # the fault point lives INSIDE the retried closure so chaos
        # tests exercise the retry loop even on the single-process path
        faults.check("multihost.init")
        if coordinator_address and not jax.distributed.is_initialized():
            kw = {"coordinator_address": coordinator_address}
            np_ = num_processes or os.environ.get("JAX_NUM_PROCESSES")
            pid = process_id if process_id is not None else \
                os.environ.get("JAX_PROCESS_ID")
            if np_ is not None:
                kw["num_processes"] = int(np_)
            if pid is not None:
                kw["process_id"] = int(pid)
            jax.distributed.initialize(**kw)

    policy = retry_policy if retry_policy is not None else \
        RetryPolicy(max_attempts=5, base_delay=1.0, max_delay=15.0,
                    retry_on=(OSError, TimeoutError, RuntimeError))
    retry_call(_rendezvous, policy=policy, what="multihost.init")
    return jax.process_index(), jax.process_count()


def rejoin(coordinator_address=None, num_processes=None, process_id=None,
           retry_policy=None):
    """Re-run the deployment rendezvous after an elastic mesh
    reformation (resilience.elastic → api.fitting recovery).

    Single-process deployments (every CPU test, and the single-host
    mesh path the elastic recovery currently drives) are a no-op —
    there is no cross-host barrier to re-form.  Multi-process: tear
    down the distributed client and rendezvous again with the
    survivors' coordinates, under the same retried
    :func:`init_distributed` discipline (the coordinator may itself be
    restarting).  Returns ``(process_index, process_count)``.
    """
    if jax.process_count() <= 1 and coordinator_address is None \
            and os.environ.get("JAX_COORDINATOR_ADDRESS") is None:
        return jax.process_index(), jax.process_count()
    try:
        jax.distributed.shutdown()
    except Exception:
        pass  # a dead peer may have already torn the client down
    return init_distributed(coordinator_address=coordinator_address,
                            num_processes=num_processes,
                            process_id=process_id,
                            retry_policy=retry_policy)


def _triples_digest(u, i, r):
    """Order-independent int64 digest of (u, i, r) triples: blake2b over
    the lexicographically sorted rows.  Used to detect identical per-host
    inputs without false positives on coincidentally-equal summary stats."""
    import hashlib

    order = np.lexsort((np.asarray(r), np.asarray(i), np.asarray(u)))
    buf = np.concatenate([
        np.asarray(u, dtype=np.int64)[order].view(np.uint8),
        np.asarray(i, dtype=np.int64)[order].view(np.uint8),
        np.asarray(r, dtype=np.float32)[order].view(np.uint8),
    ])
    h = hashlib.blake2b(buf.tobytes(), digest_size=8).digest()
    return int(np.frombuffer(h, dtype=np.int64)[0])


def _split_signatures_duplicated(sig):
    """True when any TWO non-empty per-process (len, digest) rows match —
    the duplicated-load mistake.  Pairwise, not all-equal: with P > 2
    processes, two hosts reading the same file must still be rejected
    even when the others differ (advisor r3).  Empty splits are excluded
    (several hosts legitimately holding no data share the empty digest)."""
    sig = np.asarray(sig)
    nonempty = sig[sig[:, 0] > 0]
    return len(nonempty) != len(np.unique(nonempty, axis=0))


def _ragged_allgather(arr, fill=0):
    """Concatenate every process's 1-D array (ragged lengths allowed).

    The shared collective idiom of this module: lengths are agreed first,
    locals are padded to the max, one ``process_allgather`` moves the
    data, padding is dropped.  O(P · max_len) host memory.
    """
    from jax.experimental import multihost_utils as mhu

    arr = np.asarray(arr)
    lens = np.asarray(mhu.process_allgather(
        np.array([len(arr)], dtype=np.int64))).ravel()
    pad = int(lens.max())
    buf = np.full(pad, fill, dtype=arr.dtype)
    buf[: len(arr)] = arr
    g = np.asarray(mhu.process_allgather(buf))
    keep = np.arange(pad)[None, :] < lens[:, None]
    return g[keep]


def train_multihost(u, i, r, num_users, num_items, cfg, mesh=None,
                    min_width=8, chunk_elems=1 << 19, replicated=False,
                    strategy="all_gather", init=None, start_iter=0,
                    callback=None):
    """Multi-process ALS training: every process calls this with its OWN
    rating triples (global dense ids) — the analog of Spark executors each
    reading their input split and ``partitionRatings`` shuffling blocks to
    owners (SURVEY.md §3.1).

    Pipeline: (1) redistribute triples so each host sees the ratings its
    entities own — implemented with ``process_allgather`` (O(total nnz)
    per host; pass ``replicated=True`` when every host already loaded the
    FULL dataset to skip the exchange, or at pod scale feed pre-sharded
    inputs through :func:`local_rating_mask`); (2) global
    counts → partitions → per-host blocking into the agreed
    :func:`tpu_als.parallel.data.shard_layout` shapes; (3) global-array
    assembly via ``jax.make_array_from_process_local_data``; (4) the
    ``shard_map`` trainer over the global mesh — collectives cross hosts
    over DCN (gloo on the CPU test mesh).

    Returns ``(U, V, user_part, item_part)``: slot-space global
    ``jax.Array`` factors sharded over the mesh.  Exercised end-to-end by
    ``tests/test_multihost.py`` (two spawned processes, result equal to
    the single-process run).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_als.core.als import init_factors
    from tpu_als.parallel.data import partition_balanced, shard_csr
    from tpu_als.parallel.mesh import AXIS, make_mesh
    from tpu_als.parallel.trainer import make_sharded_step

    if mesh is None:
        mesh = make_mesh()
    # pin dtypes BEFORE the cross-process gather: per-host divergence
    # (e.g. one host's empty split arriving as float64) would feed gloo
    # mismatched buffers
    u = np.asarray(u, dtype=np.int64)
    i = np.asarray(i, dtype=np.int64)
    r = np.asarray(r, dtype=np.float32)

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils as mhu

        # cross-host agreement check: divergent entity spaces would fail
        # far away (mismatched global shapes inside gloo) or silently
        # corrupt factors if shapes happened to coincide; divergent
        # iteration windows would have one host exit the training loop
        # while peers keep issuing collectives — a silent hang
        dims = np.asarray(mhu.process_allgather(np.array(
            [num_users, num_items, int(start_iter), int(cfg.max_iter)],
            dtype=np.int64)))
        if not (dims == dims[0]).all():
            raise ValueError(
                "hosts disagree on (num_users, num_items, start_iter, "
                f"max_iter): {dims.tolist()}; all hosts must share one "
                "id mapping and one iteration window (same resumeFrom "
                "checkpoint, same maxIter)")

        if replicated:
            # every host already holds the FULL triples (e.g. all loaded
            # the same file): skip the O(total nnz) exchange — but check
            # CONTENT agreement, not just length (same-length divergent
            # inputs would give hosts divergent partitions and corrupt
            # training far from here)
            sig = np.asarray(mhu.process_allgather(np.array(
                [len(u), int(u.sum()), int(i.sum()),
                 np.float64(r.astype(np.float64).sum()).view(np.int64)],
                dtype=np.int64)))
            if not (sig == sig[0]).all():
                raise ValueError(
                    "replicated=True but per-host rating data differ "
                    f"(len/Σu/Σi/Σr signatures: {sig.tolist()}) — every "
                    "host must load the SAME dataset, or pass each "
                    "host's own split with replicated=False")
    if jax.process_count() > 1 and not replicated:
        from jax.experimental import multihost_utils as mhu

        # catch the duplicated-load mistake BEFORE the exchange doubles
        # every rating: per-host splits with identical content mean every
        # host read the SAME file (replicated=False would then train on P
        # copies of each rating — effective regularization silently
        # divided by P).  Content = an order-independent 64-bit digest of
        # the sorted triples, not summary stats (equal sums on genuinely
        # disjoint splits would false-positive; a hash collision is
        # ~2^-64)
        sig = np.asarray(mhu.process_allgather(np.array(
            [len(u), _triples_digest(u, i, r)], dtype=np.int64)))
        if _split_signatures_duplicated(sig):
            raise ValueError(
                "replicated=False but two or more processes passed "
                "IDENTICAL rating triples — each host must pass its OWN "
                "disjoint split (per-host input files), or pass "
                "replicated=True for a shared load")
        u = _ragged_allgather(u)
        i = _ragged_allgather(i)
        r = _ragged_allgather(r)

    D = mesh.devices.size
    ucounts = np.bincount(u, minlength=num_users)
    icounts = np.bincount(i, minlength=num_items)
    upart = partition_balanced(ucounts, D)
    ipart = partition_balanced(icounts, D)
    positions = local_positions(mesh)

    leading = NamedSharding(mesh, P(AXIS))

    def assemble(local):
        return jax.make_array_from_process_local_data(leading, local)

    if strategy in ("ring", "ring_overlap"):
        # ring exists to bound DEVICE HBM (opposite factors never
        # materialize in full); its grid layout is computed globally
        # (every host holds the full triples at this point) but only the
        # local owner rows are allocated, filled, and placed
        from tpu_als.parallel.comm import shard_csr_grid
        from tpu_als.parallel.trainer import make_ring_step, stacked_counts

        ush = shard_csr_grid(upart, ipart, u, i, r, min_width=min_width,
                             chunk_elems=chunk_elems, positions=positions)
        ish = shard_csr_grid(ipart, upart, i, u, r, min_width=min_width,
                             chunk_elems=chunk_elems, positions=positions)
        pos_only = cfg.implicit_prefs
        extra = (
            assemble(stacked_counts(upart, u, r,
                                    positive_only=pos_only)[positions]),
            assemble(stacked_counts(ipart, i, r,
                                    positive_only=pos_only)[positions]),
        )
        if strategy == "ring_overlap":
            def step_factory(mesh, ush, ish, cfg):
                return make_ring_step(mesh, ush, ish, cfg, overlap=True)
        else:
            step_factory = make_ring_step
    elif strategy in ("all_gather", "all_gather_chunked"):
        umask = local_rating_mask(upart, u, positions=positions)
        imask = local_rating_mask(ipart, i, positions=positions)
        ush = shard_csr(upart, ipart, u[umask], i[umask], r[umask],
                        min_width=min_width, chunk_elems=chunk_elems,
                        positions=positions, row_counts=ucounts)
        ish = shard_csr(ipart, upart, i[imask], u[imask], r[imask],
                        min_width=min_width, chunk_elems=chunk_elems,
                        positions=positions, row_counts=icounts)
        extra = ()
        if strategy == "all_gather_chunked":
            from tpu_als.parallel.trainer import make_chunked_gather_step

            step_factory = make_chunked_gather_step
        else:
            step_factory = make_sharded_step
    elif strategy == "all_to_all":
        # exchange plan computed globally (full triples are present),
        # only the local source rows placed; degenerate plans (one hot
        # (src, dst) pair pushing the uniform budget past all_gather
        # bytes) fall back to all_gather, same as single-process fit
        from tpu_als.parallel.a2a import build_a2a
        from tpu_als.parallel.trainer import make_a2a_step

        ush = build_a2a(upart, ipart, u, i, r, min_width=min_width,
                        chunk_elems=chunk_elems, on_degenerate="stub",
                        positions=positions)
        ish = build_a2a(ipart, upart, i, u, r, min_width=min_width,
                        chunk_elems=chunk_elems, on_degenerate="stub",
                        positions=positions)
        if ush.degenerate or ish.degenerate:
            return train_multihost(
                u, i, r, num_users, num_items, cfg, mesh=mesh,
                min_width=min_width, chunk_elems=chunk_elems,
                replicated=True, strategy="all_gather",
                init=init, start_iter=start_iter, callback=callback)
        extra = (assemble(ush.send_idx), assemble(ish.send_idx))
        step_factory = make_a2a_step
    else:
        from tpu_als.parallel.trainer import EXECUTABLE_STRATEGIES

        raise ValueError(
            f"unknown strategy {strategy!r} for multi-host training "
            f"(expected one of {EXECUTABLE_STRATEGIES} — the table in "
            "parallel.trainer.GATHER_STRATEGIES)")

    ub = jax.tree.map(assemble, ush.device_buckets())
    ib = jax.tree.map(assemble, ish.device_buckets())

    U0 = np.zeros((upart.padded_rows, cfg.rank), np.float32)
    V0 = np.zeros((ipart.padded_rows, cfg.rank), np.float32)
    if init is not None:
        # entity-space warm start (checkpoint resume): scatter to slots
        U0[upart.slot] = np.asarray(init[0], dtype=np.float32)
        V0[ipart.slot] = np.asarray(init[1], dtype=np.float32)
    else:
        key = jax.random.PRNGKey(cfg.seed)
        ku, kv = jax.random.split(key)
        U0[upart.slot] = np.asarray(init_factors(ku, num_users, cfg.rank))
        V0[ipart.slot] = np.asarray(init_factors(kv, num_items, cfg.rank))
    rps_u, rps_i = upart.rows_per_shard, ipart.rows_per_shard
    U = assemble(np.concatenate(
        [U0[p * rps_u:(p + 1) * rps_u] for p in positions]))
    V = assemble(np.concatenate(
        [V0[p * rps_i:(p + 1) * rps_i] for p in positions]))

    step = step_factory(mesh, ush, ish, cfg)
    for it in range(start_iter, cfg.max_iter):
        U, V = step(U, V, ub, ib, *extra)
        if callback is not None:
            # slot-space global arrays + the partitions to unscatter them;
            # collective work inside the callback (e.g. a
            # gather_entity_factors for checkpointing) must run on EVERY
            # process
            callback(it + 1, U, V, upart, ipart)
    return U, V, upart, ipart


def save_checkpoint_sharded(path, Us, Vs, upart, ipart, user_map, item_map,
                            mesh, params=None, iteration=None):
    """Shard-per-process checkpoint: each process writes ONLY the factor
    shards its devices own — the SURVEY §5.4 design ("flat-array
    shard-per-device checkpoint with a JSON manifest").

    A replicated checkpoint costs an O(N_entities · rank) cross-host
    gather per checkpoint (the most expensive collective in the loop);
    here factor bytes never cross hosts: process-local ``np.savez`` per
    mesh position, process 0 adds ids/slots + manifest, one barrier, then
    process 0 runs the same old-aside/install/cleanup swap as
    ``io.checkpoint.save_factors`` so a complete checkpoint exists at
    ``path`` or ``path + '.old'`` at every instant.  The saved slot maps
    make the directory self-contained: ``io.checkpoint.load_factors``
    reassembles entity-space factors with the same return contract as
    the replicated format, so every resume/load path works unchanged.
    """
    import shutil

    from jax.experimental import multihost_utils as mhu

    from tpu_als.io.checkpoint import SHARDED_FORMAT, atomic_install

    Us.block_until_ready()
    Vs.block_until_ready()
    pidx = jax.process_index()
    tmp = path + ".tmp"
    # clear stale leftovers from a crashed attempt BEFORE anyone writes
    # (a dead run with a different shard count would otherwise leave
    # wrong-generation shard files inside the installed directory)
    if pidx == 0 and os.path.exists(tmp):
        shutil.rmtree(tmp)
    if jax.process_count() > 1:
        mhu.sync_global_devices(f"tpu_als_ckpt_clear_{iteration}")
    os.makedirs(tmp, exist_ok=True)
    positions = local_positions(mesh)

    def write_side(arr, name):
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        for pos, sh in zip(positions, shards):
            np.savez(os.path.join(tmp, f"{name}_shard_{pos:05d}.npz"),
                     factors=np.asarray(sh.data))

    write_side(Us, "user")
    write_side(Vs, "item")
    if pidx == 0:
        np.savez(os.path.join(tmp, "slots.npz"),
                 user_ids=np.asarray(user_map.ids),
                 item_ids=np.asarray(item_map.ids),
                 user_slot=np.asarray(upart.slot),
                 item_slot=np.asarray(ipart.slot))
        manifest = {
            "format_version": SHARDED_FORMAT,
            "sharded": True,
            "n_shards": int(upart.n_shards),
            "rows_per_shard_user": int(upart.rows_per_shard),
            "rows_per_shard_item": int(ipart.rows_per_shard),
            "rank": int(Us.shape[-1]),
            "num_users": int(len(user_map)),
            "num_items": int(len(item_map)),
            "iteration": iteration,
            "params": params or {},
            "extra": {},
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            import json

            json.dump(manifest, f, indent=2)
    if jax.process_count() > 1:
        mhu.sync_global_devices(f"tpu_als_ckpt_write_{iteration}")
    if pidx == 0:
        atomic_install(tmp, path)
    if jax.process_count() > 1:
        # peers must not race into the next iteration's tmp dir (or a
        # resume) while the swap is mid-flight
        mhu.sync_global_devices(f"tpu_als_ckpt_swap_{iteration}")


def global_id_union(local_ids):
    """Sorted union of every process's id set — the agreed entity space of
    a per-host-split fit (``ALS(dataMode='per_host')``).

    The reference analog is ``partitionRatings`` seeing the global id space
    through the shuffle (SURVEY.md §3.1); here each host contributes only
    its O(local unique) ids, so no host materializes the remote *ratings*
    to agree on the *entities*.  Deterministic (sorted) on every host, so
    the resulting ``IdMap`` — and everything downstream: partitions,
    layouts, init — is identical across processes.  Single-process: plain
    ``np.unique``.
    """
    uniq = np.unique(np.asarray(local_ids))
    if jax.process_count() == 1:
        return uniq
    return np.unique(_ragged_allgather(uniq.astype(np.int64)))


def global_vocab_union(labels):
    """Sorted union of every process's STRING vocabulary — the entity
    agreement for per-host streaming ingest (io/stream.py) whose raw ids
    are strings (config 3's Amazon-2023 schema, SURVEY.md §6 row 3).

    Same contract as :func:`global_id_union` but over an ``S``-dtype
    label array: each host contributes O(local distinct) label bytes,
    never its ratings.  Labels are padded to the globally-agreed width,
    moved as uint8 rows through the ragged allgather, and uniqued —
    deterministic (lexicographic) on every process.  Labels must not
    contain NUL bytes (the padding alphabet).  Single-process: plain
    ``np.unique``.  The local->global remap is
    ``np.searchsorted(global, local)``.
    """
    labels = np.asarray(labels, dtype="S")
    if jax.process_count() == 1:
        return np.unique(labels)
    from jax.experimental import multihost_utils as mhu

    w = int(np.asarray(mhu.process_allgather(
        np.array([max(labels.dtype.itemsize, 1)], dtype=np.int64))).max())
    rows = np.zeros((len(labels), w), dtype=np.uint8)
    if len(labels):
        loc_w = labels.dtype.itemsize
        rows[:, :loc_w] = (labels.view(np.uint8)
                           .reshape(len(labels), loc_w))
    flat = _ragged_allgather(rows.ravel())
    gathered = np.ascontiguousarray(
        flat.reshape(-1, w)).view(f"S{w}").ravel()
    return np.unique(gathered)


def gather_entity_factors(arr, part, mesh):
    """Host-replicated entity-space factors from a slot-space global array.

    Small-model convenience for the serving/persistence boundary (the
    reference's ``ALSModel`` is a driver-side object too); at pod scale
    keep factors sharded and serve from device.  Works single- and
    multi-process (one ``process_allgather`` of the local rows).
    """
    rps = part.rows_per_shard
    rank = arr.shape[-1]
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    local = np.concatenate([np.asarray(s.data) for s in shards])
    positions = np.asarray(local_positions(mesh), dtype=np.int64)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils as mhu

        g_rows = np.asarray(mhu.process_allgather(local))      # [P, L*rps, r]
        g_pos = np.asarray(mhu.process_allgather(positions))   # [P, L]
        slotspace = np.zeros((part.padded_rows, rank), np.float32)
        for p in range(g_rows.shape[0]):
            for li, pos in enumerate(g_pos[p]):
                slotspace[pos * rps:(pos + 1) * rps] = \
                    g_rows[p, li * rps:(li + 1) * rps]
    else:
        slotspace = local
    return slotspace[part.slot]


def local_positions(mesh):
    """Mesh-axis positions (0..D-1) owned by this process's devices.

    The sharded trainer lays factors and rating shards out device-major
    along the 1-D mesh axis; these are the leading-axis indices this host
    must have data for."""
    local = {d.id for d in jax.local_devices()}
    flat = list(mesh.devices.flat)
    return [k for k, d in enumerate(flat) if d.id in local]


def local_rating_mask(part, row_idx, mesh=None, positions=None):
    """Boolean mask over COO ratings: True where the solved-side entity is
    owned by one of this process's mesh positions.  Feed the masked
    triples to the blocking builders so each host blocks only its shard —
    O(local nnz) host memory instead of O(total nnz).

    ``positions`` overrides the mesh-derived ownership (tests / custom
    placement); exactly one of ``mesh`` / ``positions`` is required."""
    if positions is None:
        if mesh is None:
            raise ValueError("pass mesh or positions")
        positions = local_positions(mesh)
    own = np.zeros(part.n_shards, dtype=bool)
    own[list(positions)] = True
    return own[part.owner[np.asarray(row_idx)]]
