"""Command-line entry points: train / evaluate / recommend / foldin-bench.

The reference app layer is a runnable script (SURVEY.md §2.A); this CLI is
that surface for the TPU framework:

    python -m tpu_als.cli train --data ml-100k:/path/u.data --rank 16 \\
        --max-iter 10 --output /tmp/model
    python -m tpu_als.cli train --data synthetic:10000x2000x500000 ...
    (data specs: ml-100k:PATH | csv:PATH | dat:PATH | stream:PATH |
     synthetic:UxIxN; stream: = STRING-id csv with header, byte-range
     streamed — under --per-host-data each pod host reads only its own
     range of the ONE shared file and ids are agreed collectively)
    python -m tpu_als.cli evaluate --model /tmp/model --data ...
    python -m tpu_als.cli recommend --model /tmp/model --users 1,2,3 --k 10
    python -m tpu_als.cli foldin-bench --model /tmp/model
"""

from __future__ import annotations

import argparse
import json
import math
import sys

import numpy as np


def _vocab_lookup(labels, g):
    """Positions of ``labels`` in the sorted vocabulary ``g`` plus a
    known-mask, width-normalized once per array (shared by the eval and
    fold-in loaders — one definition, reviewer r5)."""
    import numpy as np

    w = max(labels.dtype.itemsize, g.dtype.itemsize, 1)
    lw = labels.astype(f"S{w}")
    gw = g.astype(f"S{w}")
    pos = np.searchsorted(gw, lw)
    known = np.zeros(len(labels), dtype=bool)
    inb = pos < len(g)
    known[inb] = gw[pos[inb]] == lw[inb]
    return pos, known


def _load_stream(path, host_index=0, num_hosts=1, vocab=None):
    """config-3-scale loader (``stream:PATH``): STRING-id ratings csv
    (``user_id,item_id,rating,timestamp`` with a header — the
    Amazon-2023 shape) streamed through the bounded-memory byte-range
    reader; ids densified into the globally-agreed (lexicographic)
    entity space.  Multi-process: each host streams only its byte range
    and the vocabularies are agreed with one collective — no ``{proc}``
    file splits needed.  Returns ``(frame, user_labels, item_labels)``
    (labels are numpy ``S``-dtype arrays, saved beside the model).

    ``vocab``: optional ``(user_labels, item_labels)`` from a trained
    model's ``stream_labels.npz`` sidecar.  Eval/serving data MUST be
    densified in the MODEL's id space — re-deriving a vocabulary from
    the eval file would silently score user b with user a's factors
    (reviewer, round 5).  Rows whose labels the model never saw are
    dropped (the cold-start ``'drop'`` semantics) with a stderr count.
    """
    import numpy as np

    from tpu_als.io.stream import (
        split_claim,
        strip_split_claims,
        stream_ingest,
        validate_split_claims,
    )
    from tpu_als.parallel.multihost import global_vocab_union
    from tpu_als.utils.frame import ColumnarFrame

    u_loc, i_loc, r, ul, il = stream_ingest(
        path, host_index, num_hosts, require_cols=4, skip_header=1)

    if vocab is None:
        # ride this host's byte-range claim through the user-vocab union
        # so a stale --num-hosts on any host fails HERE, not as silently
        # double-read/dropped ratings (io/stream.validate_split_claims)
        import jax

        claim = np.array([split_claim(host_index, num_hosts)])
        w = max(ul.dtype.itemsize, claim.dtype.itemsize, 1)
        claimed = np.concatenate([ul.astype(f"S{w}"), claim.astype(f"S{w}")])
        union = global_vocab_union(claimed)
        if jax.process_count() >= num_hosts:
            g_ul, _ = validate_split_claims(union)
        else:
            # single-process harness byte-splitting for a larger host
            # count: peer claims cannot arrive through a local union, so
            # coverage is unverifiable — strip without enforcement
            g_ul = strip_split_claims(union)
        g_il = global_vocab_union(il)
        u = np.searchsorted(g_ul, ul)[u_loc]
        i = np.searchsorted(g_il, il)[i_loc]
    else:
        g_ul, g_il = vocab
        pu, ku = _vocab_lookup(ul, g_ul)
        pi, ki = _vocab_lookup(il, g_il)
        keep = ku[u_loc] & ki[i_loc]
        dropped = int(len(u_loc) - keep.sum())
        if dropped:
            print(f"stream eval: dropped {dropped:,}/{len(u_loc):,} "
                  "rows with user/item ids unknown to the model",
                  file=sys.stderr)
        u = pu[u_loc][keep]
        i = pi[i_loc][keep]
        r = r[keep]
    return (ColumnarFrame({"user": u, "item": i, "rating": r}),
            g_ul, g_il)


def _load_train_data(args, pid=0, pcount=1):
    """The one stream-aware loader both train paths share (reviewer,
    round 5 — the spec dispatch must not live in three places).
    Returns ``(frame, stream_labels_or_None)``.

    ``stream:`` byte-range policy: a ``{proc}`` placeholder means the
    files are ALREADY per-host splits, so each host streams its whole
    expanded file (byte-splitting on top would silently drop
    (pcount-1)/pcount of every split); otherwise ``--per-host-data``
    byte-splits the one shared file, and replicated mode streams it
    whole on every host.  Vocabularies are agreed collectively in every
    multi-process case.

    ``{proc}`` expands ONLY under a real multi-process deployment: a
    single process expanding it to 0 would silently train on 1/N of the
    data where the literal path used to fail loudly (reviewer r5)."""
    spec = (args.data.replace("{proc}", str(pid)) if pcount > 1
            else args.data)
    kind, _, arg = spec.partition(":")
    if kind != "stream":
        return _load_data(spec), None
    if spec != args.data:
        host, hosts = 0, 1     # per-host FILES: stream each one whole
    elif getattr(args, "per_host_data", False):
        host, hosts = pid, pcount
    else:
        host, hosts = 0, 1
    frame, g_ul, g_il = _load_stream(arg, host, hosts)
    return frame, (g_ul, g_il)


def _model_vocab(model_dir):
    import os

    import numpy as np

    side = os.path.join(model_dir, "stream_labels.npz")
    if not os.path.exists(side):
        raise SystemExit(
            "stream: eval data needs the model's stream_labels.npz "
            "sidecar (present when the model was trained with "
            "--data stream:...); this model has none")
    z = np.load(side)
    return z["users"], z["items"]


def _load_eval_data(spec, model_dir):
    """Eval/serving-side loader: a ``stream:`` spec is densified in the
    MODEL's id space via its ``stream_labels.npz`` sidecar."""
    kind, _, arg = spec.partition(":")
    if kind != "stream":
        return _load_data(spec)
    frame, _, _ = _load_stream(arg, vocab=_model_vocab(model_dir))
    return frame


def _load_foldin_data(spec, model_dir, new_side):
    """Fold-in loader: the whole POINT of fold-in is ids the model has
    never seen, so the ``new_side`` ("user" for --foldin-data, "item"
    for --foldin-items-data) maps known labels through the sidecar and
    assigns FRESH dense ids (after the model's space, first-seen order)
    to new ones; the opposite side must be known (its factors do the
    folding) and unknown rows there are dropped with a count.

    Returns ``(frame, new_labels)`` — new_labels[j] is the original
    string id behind dense id ``len(model_side) + j``.
    """
    import numpy as np

    kind, _, arg = spec.partition(":")
    if kind != "stream":
        return _load_data(spec), []
    g_ul, g_il = _model_vocab(model_dir)
    from tpu_als.io.stream import stream_ingest
    from tpu_als.utils.frame import ColumnarFrame

    u_loc, i_loc, r, ul, il = stream_ingest(
        arg, require_cols=4, skip_header=1)

    pu, ku = _vocab_lookup(ul, g_ul)
    pi, ki = _vocab_lookup(il, g_il)
    # the keep-filter (opposite side known) runs FIRST: a new-side
    # entity whose every row is dropped must get NO fresh id — a fresh
    # id without a folded factor row would later resolve in --users and
    # serve a row the FoldInServer never solved (reviewer r5)
    if new_side == "user":
        keep = ki[i_loc]
        loc, base, labels_side = u_loc, g_ul, ul
        pos = pu
        unknown = ~ku
    else:
        keep = ku[u_loc]
        loc, base, labels_side = i_loc, g_il, il
        pos = pi
        unknown = ~ki
    surviving = np.zeros(len(labels_side), dtype=bool)
    surviving[np.unique(loc[keep])] = True
    fresh = unknown & surviving
    pos[fresh] = len(base) + np.arange(int(fresh.sum()))
    new_labels = [s.decode() for s in labels_side[fresh].tolist()]
    dropped = int(len(u_loc) - keep.sum())
    if dropped:
        opp = "item" if new_side == "user" else "user"
        print(f"stream fold-in: dropped {dropped:,}/{len(u_loc):,} "
              f"rows with {opp} ids unknown to the model (the known "
              f"{opp} factors are what fold the new {new_side}s in)",
              file=sys.stderr)
    frame = ColumnarFrame({"user": pu[u_loc][keep],
                           "item": pi[i_loc][keep], "rating": r[keep]})
    if new_labels:
        print(f"stream fold-in: {len(new_labels)} new {new_side} ids "
              f"-> dense {len(g_ul if new_side == 'user' else g_il)}+"
              f" (first-seen): {new_labels[:5]}"
              f"{'...' if len(new_labels) > 5 else ''}",
              file=sys.stderr)
    return frame, new_labels


def _save_stream_labels(out_dir, user_labels, item_labels):
    """Sidecar mapping dense ids -> original string ids, next to the
    model manifest (the stream loader's analog of persisting the fitted
    StringIndexerModels)."""
    import os

    import numpy as np

    np.savez(os.path.join(out_dir, "stream_labels.npz"),
             users=user_labels, items=item_labels)


def _load_data(spec):
    from tpu_als.io.movielens import (
        load_movielens_100k,
        load_movielens_csv,
        load_movielens_dat,
        synthetic_movielens,
    )

    kind, _, arg = spec.partition(":")
    if kind == "ml-100k":
        return load_movielens_100k(arg)
    if kind == "csv":
        return load_movielens_csv(arg)
    if kind == "dat":
        return load_movielens_dat(arg)
    if kind == "stream":
        return _load_stream(arg)[0]
    if kind == "synthetic":
        nu, ni, nnz = (int(x) for x in arg.split("x"))
        return synthetic_movielens(nu, ni, nnz)
    raise SystemExit(f"unknown data spec {spec!r} "
                     "(use ml-100k:PATH | csv:PATH | dat:PATH (ml-1m/10m "
                     "ratings.dat) | stream:PATH (string-id csv with "
                     "header, streamed) | synthetic:UxIxN)")


def _train_probe(train, test, max_rows=100_000):
    """Held-out (u_idx, i_idx, rating) triple in the DENSE id space the
    fitted model will use (``remap_ids`` over the train columns — the
    same first-seen order ``fit`` derives), for per-iteration probe RMSE.
    Test rows whose user/item never appears in train are dropped (they
    have no factors to score with); the probe is subsampled to a bounded
    size so the per-iteration host transfer stays O(1) in dataset size.
    Returns None when nothing survives."""
    from tpu_als.core.ratings import remap_ids

    if not len(test):
        return None
    _, umap = remap_ids(np.asarray(train["user"]))
    _, imap = remap_ids(np.asarray(train["item"]))
    u = umap.to_dense(np.asarray(test["user"]))
    i = imap.to_dense(np.asarray(test["item"]))
    keep = (u >= 0) & (i >= 0)
    u, i = u[keep], i[keep]
    r = np.asarray(test["rating"], dtype=np.float32)[keep]
    if not len(u):
        return None
    if len(u) > max_rows:
        step = len(u) // max_rows + 1
        u, i, r = u[::step], i[::step], r[::step]
    return u, i, r


def _iteration_cb(logger):
    """Wrap an IterationLogger so each record also lands in the metrics
    registry as an ``iteration`` event (what ``observe summarize`` reads)."""
    from tpu_als import obs

    def cb(iteration, U, V):
        logger(iteration, U, V)
        rec = logger.records[-1]
        obs.emit("iteration",
                 **{k: v for k, v in rec.items() if k != "tag"})
    return cb


def _resolve_resume(args):
    """``--resume PATH`` loads that checkpoint; ``--resume auto``
    discovers the newest VALID generation under --checkpoint-dir
    (digest-checked, corrupt generations quarantined, ``.old``
    considered) and starts fresh when none exists."""
    resume = getattr(args, "resume", None)
    if not resume:
        return None
    if resume != "auto":
        return resume
    if not getattr(args, "checkpoint_dir", None):
        raise SystemExit("--resume auto needs --checkpoint-dir (it "
                         "searches that directory for the newest valid "
                         "checkpoint)")
    from tpu_als.io.checkpoint import discover_resume

    path = discover_resume(args.checkpoint_dir)
    if path is None:
        print("--resume auto: no valid checkpoint under "
              f"{args.checkpoint_dir}; starting from scratch",
              file=sys.stderr)
    else:
        print(f"--resume auto: resuming from {path}", file=sys.stderr)
    return path


def cmd_train(args):
    from tpu_als import ALS, RegressionEvaluator, obs
    from tpu_als.resilience import preempt
    from tpu_als.utils.observe import IterationLogger

    # resolve the multi-process branch BEFORE loading data: every pod host
    # runs this same command, and _train_multiprocess does its own load —
    # loading here first would double the host I/O and peak memory
    mesh = None
    if args.devices != 1:
        import jax

        from tpu_als.parallel.mesh import make_mesh
        from tpu_als.parallel.multihost import init_distributed

        init_distributed()  # no-op single-process; DCN rendezvous on pods
        if jax.process_count() > 1:
            return _train_multiprocess(args)
        # make_mesh raises when the request exceeds visible devices
        mesh = make_mesh(None if args.devices == 0 else args.devices)
    if args.per_host_data:
        raise SystemExit(
            "--per-host-data is multi-process only (each process loads "
            "its own split); launch under a JAX distributed rendezvous "
            "with --devices 0 — single-process runs load one dataset")
    with obs.span("data.load"):
        frame, stream_labels = _load_train_data(args)
    train, test = frame.randomSplit([1 - args.holdout, args.holdout],
                                    seed=args.seed)
    # per-iteration logging when asked for (--log-file) OR when a metrics
    # run dir is live (--output/--obs-dir): the run dir's iteration
    # events are what `observe summarize` renders as the convergence
    # table, so an observed run always records them
    logger = fit_cb = None
    if args.log_file or obs.active():
        logger = IterationLogger(
            probe=_train_probe(train, test), path=args.log_file,
            stream=sys.stderr if args.log_file else None)
        fit_cb = _iteration_cb(logger)
    als = ALS(rank=args.rank, maxIter=args.max_iter, regParam=args.reg_param,
              implicitPrefs=args.implicit, alpha=args.alpha,
              nonnegative=args.nonnegative, seed=args.seed,
              coldStartStrategy="drop", fitCallback=fit_cb,
              mesh=mesh, gatherStrategy=args.gather_strategy,
              cgIters=args.cg_iters,
              checkpointDir=args.checkpoint_dir,
              checkpointInterval=args.checkpoint_interval,
              resumeFrom=_resolve_resume(args),
              guardrails=args.guardrails,
              elastic=getattr(args, "elastic", False))
    print(f"training on {len(train):,} ratings "
          f"({len(test):,} held out)", file=sys.stderr)
    try:
        # SIGTERM/SIGINT: finish the in-flight iteration, checkpoint,
        # exit with the distinct EXIT_PREEMPTED status (resume with
        # `--resume auto`)
        with preempt.PreemptionGuard():
            if args.profile_dir:
                from tpu_als.utils.observe import trace

                with trace(args.profile_dir):
                    model = als.fit(train)
                print(f"profiler trace written to {args.profile_dir}",
                      file=sys.stderr)
            else:
                model = als.fit(train)
    except preempt.Preempted as p:
        print(f"preempted — {p}; rerun with --resume auto to continue",
              file=sys.stderr)
        raise  # SystemExit(EXIT_PREEMPTED); obs still finalizes in main
    finally:
        if logger is not None:
            logger.close()
    if getattr(als, "lastFitCommBytes", None):
        print(f"collective traffic: {als.lastFitCommBytes / 1e6:.3g} "
              f"MB/device/iteration ({als.lastFitStrategy})",
              file=sys.stderr)
    if len(test):
        rmse = RegressionEvaluator(labelCol="rating").evaluate(
            model.transform(test))
        print(json.dumps({"holdout_rmse": round(rmse, 4)}))
    if args.output:
        # CLI --output semantics: replace (atomically) — a rerun must not
        # crash after the whole training finished
        model.write().overwrite().save(args.output)
        if stream_labels is not None:
            _save_stream_labels(args.output, *stream_labels)
        print(f"model saved to {args.output}", file=sys.stderr)
    return model


def _train_multiprocess(args):
    """Multi-process training path (every pod host runs the same command).

    Convention: every host loads ``--data`` and calls the same
    ``ALS(mesh=...).fit`` — its multi-process branch blocks only the
    shards each host's devices own and trains with cross-host
    collectives.  Default is a replicated load (every host reads the same
    file); with ``--per-host-data`` each host reads its OWN split — any
    ``{proc}`` placeholder in the spec expands to the process index (e.g.
    ``csv:/data/part-{proc}.csv``) and the Estimator runs in
    ``dataMode='per_host'``.  ``--log-file`` logs from process 0 (the
    per-iteration probe gathers factors collectively).  Process 0
    evaluates the holdout (its local split in per-host mode) and saves
    the model.
    """
    import contextlib

    import jax

    from tpu_als import RegressionEvaluator
    from tpu_als.api.estimator import ALS
    from tpu_als.parallel.mesh import make_mesh
    from tpu_als.utils.observe import IterationLogger

    pid, pcount = jax.process_index(), jax.process_count()
    visible = len(jax.devices())
    if args.devices not in (0, visible):
        raise SystemExit(
            f"--devices {args.devices} under {pcount} processes: the "
            f"multi-process path always uses the full deployment "
            f"({visible} devices); pass --devices 0")

    spec = args.data.replace("{proc}", str(pid))
    if (args.per_host_data and args.data == spec and pcount > 1
            and spec.partition(":")[0] != "stream"):
        # a stream: spec needs no placeholder — it splits by byte range
        print(f"[proc {pid}] warning: --per-host-data without a {{proc}} "
              "placeholder in --data — every host loads the same path "
              "(valid only for host-LOCAL disks holding different "
              "splits; identical content is rejected at train time)",
              file=sys.stderr)
    frame, stream_labels = _load_train_data(args, pid, pcount)
    # the split seed is deliberately IDENTICAL across hosts: per-host
    # data is disjoint anyway, and a per-pid seed would decorrelate the
    # splits of an accidentally-shared file, defeating the trainer's
    # duplicated-content rejection (code-review r3)
    train, test = frame.randomSplit([1 - args.holdout, args.holdout],
                                    seed=args.seed)
    mesh = make_mesh()  # global mesh over every host's devices
    # a non-None fitCallback must be passed on EVERY process (the
    # per-iteration factor gather it triggers is collective); only
    # process 0's is ever invoked, so peers get an inert stand-in rather
    # than an IterationLogger that would open the shared log file
    logger = fit_cb = None
    if args.log_file:
        if pid == 0:
            logger = IterationLogger(path=args.log_file)
            fit_cb = _iteration_cb(logger)
        else:
            fit_cb = (lambda iteration, U, V: None)
    print(f"[proc {pid}/{pcount}] training {len(train):,} ratings "
          f"({'per-host' if args.per_host_data else 'replicated'} load) "
          f"over {mesh.devices.size} devices", file=sys.stderr)
    from tpu_als.resilience import preempt

    als = ALS(rank=args.rank, maxIter=args.max_iter,
              regParam=args.reg_param, implicitPrefs=args.implicit,
              alpha=args.alpha, nonnegative=args.nonnegative,
              seed=args.seed, coldStartStrategy="drop", mesh=mesh,
              gatherStrategy=args.gather_strategy, fitCallback=fit_cb,
              dataMode="per_host" if args.per_host_data else "replicated",
              cgIters=args.cg_iters,
              checkpointDir=args.checkpoint_dir,
              checkpointInterval=args.checkpoint_interval,
              resumeFrom=_resolve_resume(args),
              guardrails=args.guardrails,
              elastic=getattr(args, "elastic", False))
    ctx = contextlib.nullcontext()
    if args.profile_dir:
        from tpu_als.utils.observe import trace

        ctx = trace(f"{args.profile_dir}/proc{pid}")
    try:
        # the preemption decision is collective inside fit: a signal on
        # ANY host checkpoints and stops EVERY process at the same
        # iteration boundary
        with preempt.PreemptionGuard(), ctx:
            # fit's multi-process branch: per-host blocking, cross-host
            # collectives, replicated model on every host
            model = als.fit(train)
    except preempt.Preempted as p:
        print(f"[proc {pid}] preempted — {p}; rerun with --resume auto",
              file=sys.stderr)
        raise
    finally:
        if logger is not None:
            logger.close()

    if pid != 0:
        return None
    if len(test):
        rmse = RegressionEvaluator(labelCol="rating").evaluate(
            model.transform(test))
        print(json.dumps({"holdout_rmse": round(rmse, 4)}))
    if args.output:
        model.write().overwrite().save(args.output)
        if stream_labels is not None:
            _save_stream_labels(args.output, *stream_labels)
        print(f"model saved to {args.output}", file=sys.stderr)
    return model


def _load_model_any(path):
    """Load an ALSModel save, or fall back to a PipelineModel save (a
    user who persisted the whole fitted pipeline evaluates it with the
    same command).  Returns (model, is_pipeline)."""
    import os

    from tpu_als import ALSModel, PipelineModel

    if os.path.exists(os.path.join(path, "pipeline.json")):
        return PipelineModel.load(path), True
    return ALSModel.load(path), False


def cmd_evaluate(args):
    from tpu_als import RegressionEvaluator

    model, is_pipeline = _load_model_any(args.model)
    if is_pipeline and args.ranking_k > 0:
        raise SystemExit(
            "--ranking-k needs an ALSModel save (the ranking protocol "
            "runs recommendForUserSubset on raw ids); evaluate the "
            "pipeline's ALS stage directly, or drop --ranking-k for "
            "regression metrics through the full pipeline")
    frame = _load_eval_data(args.data, args.model)
    out = model.transform(frame)
    result = {}
    for metric in ("rmse", "mae", "r2"):
        ev = RegressionEvaluator(labelCol="rating", metricName=metric)
        v = ev.evaluate(out)
        # None, not NaN (every row unservable → all-NaN predictions):
        # json.dumps would emit the non-standard `NaN` token
        result[metric] = round(v, 4) if math.isfinite(v) else None
    if args.ranking_k > 0:
        # retrieval-quality protocol (SURVEY §2.B7): per test user,
        # ground truth = their test items rated >= --positive-threshold;
        # predictions = the model's top-k.  Vectorized top-k once for
        # the evaluated users, then the reference RankingMetrics math.
        from tpu_als.api.evaluation import RankingMetrics
        from tpu_als.utils.frame import ColumnarFrame

        k = args.ranking_k
        p = model._params
        u = np.asarray(frame[p["userCol"]])
        i = np.asarray(frame[p["itemCol"]])
        pos = np.asarray(frame[p["ratingCol"]],
                         np.float32) >= args.positive_threshold
        truth = {}
        for uu, ii in zip(u[pos], i[pos]):
            truth.setdefault(int(uu), set()).add(int(ii))
        users = np.array(sorted(truth), dtype=u.dtype)
        recs = model.recommendForUserSubset(
            ColumnarFrame({p["userCol"]: users}), k)
        key = recs.columns[0]
        pairs = [
            ([int(iid) for iid, _ in recs["recommendations"][row]],
             truth[int(recs[key][row])])
            for row in range(len(recs))
        ]
        # test users the model cannot serve (absent from training) are
        # filtered out by recommendForUserSubset; the reference protocol
        # scores them as an EMPTY prediction list (zero contribution),
        # not as excluded — dropping them silently would bias every
        # ranking metric upward whenever the split has cold users
        served = {int(recs[key][row]) for row in range(len(recs))}
        cold = [uu for uu in truth if uu not in served]
        pairs.extend(([], truth[uu]) for uu in cold)
        rm = RankingMetrics(pairs)
        result.update({
            f"precision_at_{k}": round(rm.precisionAt(k), 4),
            f"recall_at_{k}": round(rm.recallAt(k), 4),
            "map": round(rm.meanAveragePrecision, 4),
            f"ndcg_at_{k}": round(rm.ndcgAt(k), 4),
            "ranking_users": len(pairs),
            "ranking_users_cold": len(cold),
        })
    print(json.dumps(result))


def cmd_recommend(args):
    from tpu_als.utils.frame import ColumnarFrame

    model, is_pipeline = _load_model_any(args.model)
    if is_pipeline:
        raise SystemExit(
            f"{args.model} holds a PipelineModel save; `recommend` "
            "serves an ALSModel (its ids are the raw id space). Load "
            "the pipeline in Python and serve its ALS stage "
            "(PipelineModel.load(path).stages[-1]), mapping indices "
            "back with IndexToString — see "
            "examples/02_pipeline_string_ids.py")
    new_user_labels, new_item_labels = [], []
    if (getattr(args, "foldin_data", None)
            or getattr(args, "foldin_items_data", None)):
        # the full serving flow in one command (SURVEY.md §3.5): fold the
        # new ratings into the loaded model, then recommend — new users
        # (and, via the symmetric item direction, new items) become
        # recommendable without a refit
        from tpu_als.stream.microbatch import FoldInServer

        srv = FoldInServer(model)
        if getattr(args, "foldin_items_data", None):
            batch, new_item_labels = _load_foldin_data(
                args.foldin_items_data, args.model, "item")
            touched = srv.update_items(batch)
            print(f"folded in {len(batch)} ratings touching "
                  f"{len(touched)} items", file=sys.stderr)
        if getattr(args, "foldin_data", None):
            batch, new_user_labels = _load_foldin_data(
                args.foldin_data, args.model, "user")
            touched = srv.update(batch)
            print(f"folded in {len(batch)} ratings touching "
                  f"{len(touched)} users", file=sys.stderr)
    titles = None
    if getattr(args, "titles", None):
        from tpu_als.io.movielens import load_movielens_movies

        t = load_movielens_movies(args.titles)
        titles = dict(zip(t["item"].tolist(), t["title"].tolist()))
    devices = getattr(args, "devices", 1)
    if devices < 0:
        raise SystemExit(f"--devices must be >= 0, got {devices}")
    mesh = None
    if devices != 1:
        # serving sharded over the mesh — applies to the subset path
        # too (the catalog side is what outgrows one device's HBM);
        # make_mesh raises when the request exceeds visible devices
        from tpu_als.parallel.mesh import make_mesh

        mesh = make_mesh(devices if devices > 0 else None)
    strategy = getattr(args, "gather_strategy", "all_gather")
    stream_names = None   # (user dense->label, item labels) for output
    if args.users:
        toks = args.users.split(",")
        try:
            ids = np.array([int(x) for x in toks])
        except ValueError:
            # string ids: resolve via the stream-trained model's label
            # sidecar, plus any users just folded in this invocation
            g_ul, g_il = _model_vocab(args.model)
            index = {s.decode(): k for k, s in enumerate(g_ul.tolist())}
            for j, lab in enumerate(new_user_labels):
                index.setdefault(lab, len(g_ul) + j)

            def resolve(t):
                if t not in index:
                    raise SystemExit(
                        f"unknown user id {t!r} (not in the model's "
                        "stream_labels sidecar nor in --foldin-data)")
                return index[t]

            ids = np.array([resolve(t) for t in toks])
            stream_names = ({v: k for k, v in index.items()}, g_il)
        recs = model.recommendForUserSubset(
            ColumnarFrame({model._params["userCol"]: ids}), args.k,
            mesh=mesh, gatherStrategy=strategy)
    else:
        recs = model.recommendForAllUsers(args.k, mesh=mesh,
                                          gatherStrategy=strategy)
    key = recs.columns[0]
    limit = args.limit if args.limit > 0 else len(recs)
    for row in range(min(limit, len(recs))):
        out = {"user": int(recs[key][row]),
               "items": [[int(i), round(float(s), 4)]
                         for i, s in recs["recommendations"][row]]}
        if stream_names is not None:
            rev_u, g_il = stream_names

            def item_name(i):
                if i < len(g_il):
                    return g_il[i].decode()
                j = i - len(g_il)   # freshly folded-in item this call
                return (new_item_labels[j]
                        if j < len(new_item_labels) else None)

            out["user_id"] = rev_u.get(int(recs[key][row]))
            out["item_ids"] = [item_name(int(i))
                               for i, _ in recs["recommendations"][row]]
        if titles is not None:
            out["titles"] = [titles.get(int(i))
                             for i, _ in recs["recommendations"][row]]
        print(json.dumps(out))


def cmd_tune(args):
    """Grid search over rank/regParam with CrossValidator — the reference
    app layer's tuning step (SURVEY.md §2.A6) as a CLI command."""
    from tpu_als import ALS, RegressionEvaluator
    from tpu_als.api.tuning import CrossValidator, ParamGridBuilder

    frame, stream_labels = _load_train_data(args)
    als = ALS(maxIter=args.max_iter, implicitPrefs=args.implicit,
              alpha=args.alpha, seed=args.seed, coldStartStrategy="drop",
              cgIters=args.cg_iters)
    gb = (ParamGridBuilder()
          .addGrid(als.rank, [int(x) for x in args.ranks.split(",")])
          .addGrid(als.regParam,
                   [float(x) for x in args.reg_params.split(",")]))
    if args.alphas:
        # regParam and alpha are traced through the compiled step
        # (core/als.py), so widening the grid over them adds fit time
        # but NO extra compiles at fixed rank
        gb = gb.addGrid(als.alpha,
                        [float(x) for x in args.alphas.split(",")])
    grid = gb.build()
    cv = CrossValidator(
        estimator=als,
        estimatorParamMaps=grid,
        evaluator=RegressionEvaluator(labelCol="rating"),
        numFolds=args.folds,
        seed=args.seed,
    )
    cv_model = cv.fit(frame)
    best = cv_model.bestModel
    out = {
        "best_rank": int(best._params["rank"]),
        "best_regParam": float(best._params["regParam"]),
        "avg_metrics": [round(float(m), 4) for m in cv_model.avgMetrics],
        "grid_size": len(grid),
    }
    if args.alphas:
        out["best_alpha"] = float(best._params["alpha"])
    print(json.dumps(out))
    if args.output:
        cv_model.write().overwrite().save(args.output)
        if stream_labels is not None:
            _save_stream_labels(args.output, *stream_labels)
        print(f"best model saved to {args.output}", file=sys.stderr)


def cmd_foldin_bench(args):
    import time

    from tpu_als import ALSModel
    from tpu_als.stream.microbatch import FoldInServer
    from tpu_als.utils.frame import ColumnarFrame

    model = ALSModel.load(args.model)
    srv = FoldInServer(model)
    rng = np.random.default_rng(0)
    item_ids = model._item_map.ids
    p = model._params
    base_user = int(model._user_map.ids.max()) + 1
    for b in range(args.batches):
        n = args.batch_size
        batch = ColumnarFrame({
            p["userCol"]: rng.integers(base_user, base_user + 1000, n),
            p["itemCol"]: rng.choice(item_ids, n),
            p["ratingCol"]: rng.uniform(0.5, 5.0, n).astype(np.float32),
        })
        t0 = time.perf_counter()
        srv.update(batch)
        if b == 0:
            print(f"warmup batch: {time.perf_counter()-t0:.3f}s",
                  file=sys.stderr)
    print(json.dumps({
        "metric": "foldin_p50_latency",
        "value": round(srv.latency(0.5, skip_warmup=True), 4),
        "unit": "seconds",
        "batches": args.batches,
        "batch_size": args.batch_size,
    }))


def _serve_bench_tenants(args):
    """The ``--tenants N`` branch: N same-shaped models behind one
    :class:`MultiTenantEngine`, equal open-loop load per tenant, judged
    per tenant from the LABELED obs series.

    Headline metric is ``tenancy_worst_p99_ms`` — the worst per-tenant
    e2e p99 — and ``slo_met`` requires BOTH every tenant's p99 within
    ``--slo-ms`` AND the weighted goodput fairness ratio (max/min of
    served-rows-per-weight) within ``--fairness-bound``: a report where
    one tenant starves is a failing report even if the aggregate tail
    looks healthy.  ``--update-qps > 0`` gives every tenant its own
    live fold-in stream (per-tenant publish-mode histograms in the
    report).  Same-shaped tenants share compiled executables — warmup
    cost is paid once, not N times (docs/tenancy.md).
    """
    import datetime as _dt
    import threading
    import time

    from tpu_als import obs
    from tpu_als.tenancy import (MultiTenantEngine, TenantOverloaded,
                                 TenantSpec)

    if args.tenants < 2:
        raise SystemExit("serve-bench: --tenants needs >= 2")
    rng = np.random.default_rng(args.seed)
    names = [f"t{i}" for i in range(args.tenants)]
    weights = ([float(w) for w in args.tenant_weights.split(",")]
               if args.tenant_weights else [1.0] * args.tenants)
    if len(weights) != args.tenants:
        raise SystemExit("serve-bench: --tenant-weights needs exactly "
                         f"{args.tenants} comma-separated weights")
    buckets = (tuple(int(b) for b in args.buckets.split(","))
               if args.buckets else None)

    eng = MultiTenantEngine()
    factors = {}
    for name, w in zip(names, weights):
        U = rng.normal(size=(args.users, args.rank)).astype(np.float32)
        V = rng.normal(size=(args.items, args.rank)).astype(np.float32)
        factors[name] = (U, V)
        eng.add_tenant(
            TenantSpec(name=name, weight=w, k=args.k,
                       shortlist_k=args.shortlist_k, buckets=buckets,
                       max_queue=args.max_queue,
                       max_wait_s=args.max_wait_ms / 1e3,
                       default_deadline_s=(args.deadline_ms / 1e3
                                           if args.deadline_ms
                                           else None),
                       slo_s=args.slo_ms / 1e3),
            U, V, quantize=not args.exact)
    with obs.span("serve_bench.warmup"):
        # tenant 0 pays the compiles; the rest hit the process-global
        # cache (same shape-class, same rank)
        eng.warmup()

    updaters = {}
    if args.update_qps > 0:
        from tpu_als.api.estimator import ALSModel
        from tpu_als.core.ratings import IdMap
        from tpu_als.stream.microbatch import FoldInServer

        with obs.span("serve_bench.live_prewarm"):
            for name in names:
                U, V = factors[name]
                model = ALSModel(
                    args.rank, IdMap(ids=np.arange(args.users)),
                    IdMap(ids=np.arange(args.items)), U.copy(),
                    V.copy(),
                    {"userCol": "user", "itemCol": "item",
                     "ratingCol": "rating", "regParam": 0.05,
                     "implicitPrefs": False, "alpha": 1.0,
                     "nonnegative": False})
                srv = FoldInServer(model, keep_history=False)
                upd = eng.attach_live(
                    name, srv, max_batch=args.update_max_batch,
                    max_wait_ms=args.update_max_wait_ms,
                    slo_s=args.freshness_slo_ms / 1e3)
                if name == names[0]:
                    srv.prewarm(rows=(upd.max_batch,), widths=(2,),
                                sides=("user",))
                updaters[name] = upd

    per_qps = args.qps / args.tenants
    n_req = max(1, int(per_qps * args.duration))
    path = "exact" if args.exact else "int8"
    print(f"serve-bench: {args.tenants} tenants x {n_req} requests at "
          f"{per_qps:g} rps each over {args.duration:g}s ({path} path, "
          f"{args.items:,} items, rank {args.rank})", file=sys.stderr)

    shed = {name: 0 for name in names}

    def _drive(name, seed):
        trng = np.random.default_rng(seed)
        uids = trng.integers(0, args.users, n_req)
        tickets = []
        t0 = time.perf_counter()
        for j in range(n_req):
            delay = (t0 + j / per_qps) - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                tickets.append(eng.submit(name, int(uids[j])))
            except TenantOverloaded:
                shed[name] += 1
        for t in tickets:
            try:
                t.result(timeout=max(5.0, 10 * args.slo_ms / 1e3))
            except Exception:   # noqa: BLE001 — counted from obs below
                pass

    def _drive_updates(name, seed):
        urng = np.random.default_rng(seed)
        n_upd = max(1, int(args.update_qps / args.tenants
                           * args.duration))
        uu = urng.integers(0, args.users, n_upd)
        ii = urng.integers(0, args.items, n_upd)
        rr = urng.uniform(0.5, 5.0, n_upd).astype(np.float32)
        tu = time.perf_counter()
        for j in range(n_upd):
            delay = (tu + j / (args.update_qps / args.tenants)
                     - time.perf_counter())
            if delay > 0:
                time.sleep(delay)
            try:
                updaters[name].submit(int(uu[j]), int(ii[j]),
                                      float(rr[j]))
            except Exception:   # noqa: BLE001 — live.shed counts it
                pass

    eng.start()
    try:
        with obs.span("serve_bench.drive"):
            threads = [threading.Thread(
                target=_drive, args=(name, args.seed + 100 + i),
                name=f"serve-bench-{name}")
                for i, name in enumerate(names)]
            threads += [threading.Thread(
                target=_drive_updates, args=(name, args.seed + 200 + i),
                name=f"serve-bench-upd-{name}")
                for i, name in enumerate(updaters)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            deadline = time.perf_counter() + 30.0
            while (any(u.queue_depth for u in updaters.values())
                   and time.perf_counter() < deadline):
                time.sleep(0.02)
    finally:
        eng.stop()

    per_tenant, worst_p99, modes_all = {}, 0.0, {}
    goodput = []
    events = obs.default_registry()._events
    for name, w in zip(names, weights):
        p50 = obs.histogram_quantile("serving.e2e_seconds", 0.5,
                                     tenant=name)
        p99 = obs.histogram_quantile("serving.e2e_seconds", 0.99,
                                     tenant=name)
        scored = obs.histogram_count("serving.e2e_seconds", tenant=name)
        if scored == 0:
            raise SystemExit(f"serve-bench: tenant {name!r} completed "
                             "no request — its histogram is empty")
        shed_obs = obs.counter_value("serving.shed", tenant=name)
        admitted = obs.counter_value("serving.requests", tenant=name)
        assert shed[name] == shed_obs, (name, shed[name], shed_obs)
        served = obs.counter_value("tenancy.served_rows", tenant=name)
        goodput.append(served / w)
        modes = {}
        for e in events:
            if (e.get("type") == "live_update"
                    and e.get("tenant") == name):
                modes[e["mode"]] = modes.get(e["mode"], 0) + 1
        for m, c in modes.items():
            modes_all[m] = modes_all.get(m, 0) + c
        worst_p99 = max(worst_p99, p99)
        per_tenant[name] = {
            "p50_ms": round(p50 * 1e3, 3),
            "p99_ms": round(p99 * 1e3, 3),
            "slo_met": bool(p99 * 1e3 <= args.slo_ms),
            "scored": int(scored),
            "shed_rate": (round(shed_obs / (admitted + shed_obs), 4)
                          if admitted + shed_obs else 0.0),
            "served_rows": int(served),
            "weight": w,
            **({"publish_modes": modes} if modes else {}),
        }
    fairness = (max(goodput) / min(goodput)) if min(goodput) else None
    all_in_slo = all(t["slo_met"] for t in per_tenant.values())
    # Fairness is a CONTENTION property: weighted goodput (served/weight)
    # can only equalize when the scheduler actually arbitrates.  An
    # unsaturated bench serves every tenant's full demand, so unequal
    # weights read as an "unfair" ratio while nobody was refused
    # anything — judge the ratio only when some tenant shed (always
    # report it).
    contended = any(shed[name] > 0 for name in names)
    fair_ok = (not contended or (fairness is not None
                                 and fairness <= args.fairness_bound))
    result = {
        "metric": "tenancy_worst_p99_ms",
        "value": round(worst_p99 * 1e3, 3),
        "unit": "ms",
        "slo_ms": args.slo_ms,
        "fairness_ratio": (round(fairness, 3)
                           if fairness is not None else None),
        "fairness_bound": args.fairness_bound,
        "fairness_judged": contended,
        "slo_met": bool(all_in_slo and fairness is not None
                        and fair_ok),
        "tenants": per_tenant,
        "shape_classes": {k: sorted(v) for k, v in
                          eng.registry.shape_classes().items()},
        **({"publish_modes": modes_all} if modes_all else {}),
        "config": {
            "path": path, "tenants": args.tenants,
            "tenant_weights": weights, "users": args.users,
            "items": args.items, "rank": args.rank, "k": args.k,
            "shortlist_k": args.shortlist_k, "qps": args.qps,
            "qps_per_tenant": per_qps, "duration_s": args.duration,
            "max_queue": args.max_queue,
            "max_wait_ms": args.max_wait_ms,
            "deadline_ms": args.deadline_ms,
            "update_qps": args.update_qps,
        },
    }
    print(json.dumps(result))
    if args.bench_json:
        with open(args.bench_json, "w") as f:
            json.dump({
                **result,
                "banked_by": "tpu_als serve-bench --tenants",
                "banked_at": _dt.datetime.now(
                    _dt.timezone.utc).isoformat(timespec="seconds"),
            }, f, indent=2)
            f.write("\n")
        print(f"result banked to {args.bench_json}", file=sys.stderr)
    return result


def cmd_serve_bench(args):
    """Open-loop serving latency benchmark: synthetic factors, a fixed
    request rate for a fixed window, p50/p99/shed-rate read back from
    the obs histograms and judged against ``--slo-ms``.

    Open-loop means arrivals are scheduled by the clock, not by
    completions — the honest load model for online serving (a closed
    loop self-throttles and hides queueing collapse).  Results can be
    banked as ``BENCH_serve_*.json`` with the same ``banked_at``
    provenance stamp bench.py uses (``--bench-json``).

    ``--update-qps > 0`` additionally drives the LIVE pipeline
    (tpu_als/live/) during the window: a concurrent rating-event
    stream through a LiveUpdater — fold-in, incremental publish,
    freshness measured per event — and the report's headline metric
    becomes ``live_freshness_p99_ms`` judged against
    ``--freshness-slo-ms``, with an O(touched)-vs-O(catalog)
    publish-cost probe (min-of-3, device-fenced) alongside.

    ``--tenants N`` switches to the multi-tenant variant: N same-shaped
    models behind one MultiTenantEngine, judged per tenant
    (see :func:`_serve_bench_tenants`).
    """
    import datetime as _dt
    import threading
    import time

    from tpu_als import obs
    from tpu_als.serving import Overloaded, ServingEngine

    if args.tenants:
        return _serve_bench_tenants(args)

    rng = np.random.default_rng(args.seed)
    U = rng.normal(size=(args.users, args.rank)).astype(np.float32)
    V = rng.normal(size=(args.items, args.rank)).astype(np.float32)
    # no --buckets: the execution planner supplies the ladder (a banked
    # plan for this device/jax key, else the DEFAULT_BUCKETS walk)
    buckets = (tuple(int(b) for b in args.buckets.split(","))
               if args.buckets else None)
    mesh = None
    if args.mesh_devices:
        from tpu_als.parallel.mesh import make_mesh

        mesh = make_mesh(args.mesh_devices)
    engine = ServingEngine(
        k=args.k, buckets=buckets, shortlist_k=args.shortlist_k,
        mesh=mesh,
        max_queue=args.max_queue, max_wait_s=args.max_wait_ms / 1e3,
        default_deadline_s=(args.deadline_ms / 1e3
                            if args.deadline_ms else None),
        # the SLO is also the flight-recorder breach trigger: a request
        # slower than this dumps the last N per-request traces as
        # flight_record events (docs/observability.md)
        slo_s=args.slo_ms / 1e3)
    engine.publish(U, V, quantize=not args.exact)
    with obs.span("serve_bench.warmup"):
        engine.warmup()

    updater, model, upd_stats = None, None, {"shed": 0}
    if args.update_qps > 0:
        from tpu_als.api.estimator import ALSModel
        from tpu_als.core.ratings import IdMap
        from tpu_als.live import LiveUpdater
        from tpu_als.stream.microbatch import FoldInServer

        model = ALSModel(
            args.rank, IdMap(ids=np.arange(args.users)),
            IdMap(ids=np.arange(args.items)), U.copy(), V.copy(),
            {"userCol": "user", "itemCol": "item",
             "ratingCol": "rating", "regParam": 0.05,
             "implicitPrefs": False, "alpha": 1.0,
             "nonnegative": False})
        # keep_history=False: widths stay the per-batch multiplicity
        # (1-2), so the prewarm grid below covers every shape the
        # stream can produce — a history merge would grow widths over
        # the window and pay compiles against the freshness SLO
        srv = FoldInServer(model, keep_history=False)
        updater = LiveUpdater(
            engine, srv, max_batch=args.update_max_batch,
            max_wait_ms=args.update_max_wait_ms,
            slo_s=args.freshness_slo_ms / 1e3,
            fold_items=args.update_items)
        with obs.span("serve_bench.live_prewarm"):
            srv.prewarm(
                rows=(updater.max_batch,), widths=(2,),
                sides=(("user", "item") if args.update_items
                       else ("user",)))
            # (with --update-items ``updater.start()`` runs
            # ``engine.warmup_live``: spare catalog rows, the segment's
            # slots, the with-segment programs)

    path = "exact" if args.exact else "int8"
    n_req = max(1, int(args.qps * args.duration))
    print(f"serve-bench: {n_req} requests at {args.qps:g} rps over "
          f"{args.duration:g}s ({path} path, "
          f"{args.items:,} items, rank {args.rank})", file=sys.stderr)
    foldin_ids = rng.random(n_req) < args.foldin_frac
    uids = rng.integers(0, args.users, n_req)

    upd_thread = None
    if updater is not None:
        n_upd = max(1, int(args.update_qps * args.duration))
        upd_u = rng.integers(0, args.users, n_upd)
        upd_i = rng.integers(0, args.items, n_upd)
        upd_r = rng.uniform(0.5, 5.0, n_upd).astype(np.float32)
        upd_r[rng.random(n_upd) < args.update_poison_frac] = np.nan
        print(f"serve-bench: +{n_upd} rating events at "
              f"{args.update_qps:g}/s (live fold-in → publish, "
              f"freshness SLO {args.freshness_slo_ms:g}ms)",
              file=sys.stderr)

        def _drive_updates():
            tu = time.perf_counter()
            for j in range(n_upd):
                delay = tu + j / args.update_qps - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                try:
                    updater.submit(int(upd_u[j]), int(upd_i[j]),
                                   float(upd_r[j]))
                except Overloaded:
                    upd_stats["shed"] += 1

        updater.start()
        upd_thread = threading.Thread(
            target=_drive_updates, name="serve-bench-updates")

    tickets, shed = [], 0
    engine.start()
    try:
        with obs.span("serve_bench.drive"):
            # pacing epoch starts inside the span: the span-enter
            # emission must not make request 0 late against its target
            if upd_thread is not None:
                upd_thread.start()
            t0 = time.perf_counter()
            for j in range(n_req):
                target = t0 + j / args.qps
                delay = target - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                payload = (U[uids[j]] if foldin_ids[j]
                           else int(uids[j]))
                try:
                    tickets.append(engine.submit(payload))
                except Overloaded:
                    shed += 1
            for t in tickets:
                try:
                    t.result(timeout=max(5.0, 10 * args.slo_ms / 1e3))
                except Exception:
                    pass   # expired/failed requests are counted below
            if upd_thread is not None:
                upd_thread.join()
                # freshness is judged on a DRAINED queue: every event
                # that was admitted must reach a publish before the
                # histograms are read
                updater.stop(drain_timeout_s=max(
                    30.0, 10 * args.freshness_slo_ms / 1e3))
    finally:
        if updater is not None:
            updater.stop()
        engine.stop()

    p50 = obs.histogram_quantile("serving.e2e_seconds", 0.5)
    p99 = obs.histogram_quantile("serving.e2e_seconds", 0.99)
    scored = obs.histogram_count("serving.e2e_seconds")
    admitted = obs.counter_value("serving.requests")
    shed_obs = obs.counter_value("serving.shed")
    expired = obs.counter_value("serving.expired")
    attempted = admitted + shed_obs
    if scored == 0:
        raise SystemExit("serve-bench: no request completed — the "
                         "latency histograms are empty")
    assert shed == shed_obs, (shed, shed_obs)  # driver and obs agree
    result = {
        "metric": "serve_e2e_p99_ms",
        "value": round(p99 * 1e3, 3),
        "unit": "ms",
        "slo_ms": args.slo_ms,
        "slo_met": bool(p99 * 1e3 <= args.slo_ms),
        "p50_ms": round(p50 * 1e3, 3),
        "shed_rate": round(shed_obs / attempted, 4) if attempted else 0.0,
        "expired": int(expired),
        "scored": int(scored),
        "queue_wait_p99_ms": round(
            obs.histogram_quantile("serving.enqueue_seconds", 0.99) * 1e3,
            3),
        "flight_records": sum(
            1 for e in obs.default_registry()._events
            if e.get("type") == "flight_record"),
        "config": {
            "path": path, "users": args.users, "items": args.items,
            "rank": args.rank, "k": args.k,
            "shortlist_k": args.shortlist_k, "qps": args.qps,
            "duration_s": args.duration,
            "buckets": list(engine.batcher.buckets),
            "max_queue": args.max_queue, "max_wait_ms": args.max_wait_ms,
            "deadline_ms": args.deadline_ms,
            "foldin_frac": args.foldin_frac,
        },
    }
    if mesh is not None:
        result["backend"] = "sharded"
        result["config"]["mesh_devices"] = int(args.mesh_devices)
    # feed the OBSERVED request-size mix back into the planner: the
    # batch_rows histogram's {p50,p90,p99,max}, weight-reconstructed
    # into a sample so the planner's own quantiles land on the same
    # rungs, become the banked pow2 ladder for this device/rank key
    # (quantiles are bucketed UPPER bounds — the derived ladder can
    # only over-provision, never undersize a bucket)
    if obs.histogram_count("serving.batch_rows"):
        from tpu_als import plan

        bq = [obs.histogram_quantile("serving.batch_rows", q)
              for q in (0.5, 0.9, 0.99, 1.0)]
        sample = ([bq[0]] * 50 + [bq[1]] * 40 + [bq[2]] * 9 + [bq[3]])
        result["derived_buckets"] = list(plan.resolve_serving_buckets(
            rank=args.rank, observed=sample))
    if updater is not None:
        from tpu_als.serving import build_index

        fr_p50 = obs.histogram_quantile("live.freshness_seconds", 0.5)
        fr_p99 = obs.histogram_quantile("live.freshness_seconds", 0.99)
        fr_n = obs.histogram_count("live.freshness_seconds")
        if fr_n == 0:
            raise SystemExit("serve-bench: no update event reached a "
                             "publish — the freshness histogram is "
                             "empty")
        modes = {}
        for e in obs.default_registry()._events:
            if e.get("type") == "live_update":
                modes[e["mode"]] = modes.get(e["mode"], 0) + 1

        # publish-cost probe: the incremental path must price as
        # O(touched rows), not O(catalog).  min-of-3 with device
        # fencing (rep 1 eats any quantize compile), same touched-row
        # count a steady-state micro-batch produces.
        probe = {}
        idx = engine.published_index
        if idx is not None:
            Vcur = np.asarray(model._V, dtype=np.float32)
            pr = np.arange(min(64, idx.n_items), dtype=np.int64)
            vr = np.ascontiguousarray(Vcur[pr])

            def _min3(fn):
                best = float("inf")
                for _ in range(3):
                    tp = time.perf_counter()
                    fn().block_until_ready()
                    best = min(best, time.perf_counter() - tp)
                return best

            d_s = _min3(lambda: idx.with_updates(
                pr, vr, seq=idx.seq + 1))
            f_s = _min3(lambda: build_index(
                Vcur, shortlist_k=idx.shortlist_k))
            probe = {
                "publish_delta_ms": round(d_s * 1e3, 3),
                "publish_full_ms": round(f_s * 1e3, 3),
                "publish_speedup": round(f_s / d_s, 2) if d_s else None,
                "probe_rows": int(pr.size),
                "catalog_rows": int(idx.n_items),
            }

        result.update({
            "metric": "live_freshness_p99_ms",
            "value": round(fr_p99 * 1e3, 3),
            "slo_ms": args.freshness_slo_ms,
            "slo_met": bool(fr_p99 * 1e3 <= args.freshness_slo_ms),
            "p50_ms": round(fr_p50 * 1e3, 3),
            "serve": {
                "p99_ms": round(p99 * 1e3, 3),
                "p50_ms": round(p50 * 1e3, 3),
                "slo_ms": args.slo_ms,
                "slo_met": bool(p99 * 1e3 <= args.slo_ms),
            },
            "live": {
                "events_scored": int(fr_n),
                "updates_shed": int(upd_stats["shed"]),
                "quarantined_rows": int(
                    obs.counter_value("ingest.quarantined_rows")),
                "publish_modes": modes,
                **probe,
            },
        })
        result["config"].update({
            "update_qps": args.update_qps,
            "update_items": bool(args.update_items),
            "update_poison_frac": args.update_poison_frac,
            "update_max_batch": updater.max_batch,
            "update_max_wait_ms": updater.max_wait_s * 1e3,
        })
    print(json.dumps(result))
    if args.bench_json:
        # same provenance contract as bench.py's banked variants: an
        # absolute UTC stamp, never a relative phrase
        with open(args.bench_json, "w") as f:
            json.dump({
                **result,
                "banked_by": "tpu_als serve-bench",
                "banked_at": _dt.datetime.now(
                    _dt.timezone.utc).isoformat(timespec="seconds"),
            }, f, indent=2)
            f.write("\n")
        print(f"result banked to {args.bench_json}", file=sys.stderr)
    return result


def cmd_tt_train(args):
    """Train the two-tower retrieval model (BASELINE config 5) from a
    ratings file: ALS warm start (unless --cold), filtered-recall holdout
    report, persisted towers."""
    from tpu_als.core.als import AlsConfig, train as als_train
    from tpu_als.core.ratings import build_csr_buckets, remap_ids
    from tpu_als.models.two_tower import (
        TwoTowerConfig,
        recall_at_k,
        save_two_tower,
        train_two_tower,
    )

    frame = _load_data(args.data)
    u_raw = np.asarray(frame["user"])
    i_raw = np.asarray(frame["item"])
    r = np.asarray(frame["rating"], dtype=np.float32)
    u, umap = remap_ids(u_raw)
    i, imap = remap_ids(i_raw)
    nU, nI = len(umap), len(imap)
    pos = r >= args.positive_threshold
    u, i, r = u[pos], i[pos], r[pos]
    rng = np.random.default_rng(args.seed)
    test = rng.random(len(u)) < args.holdout
    ut, it_ = u[test], i[test]
    u2, i2 = u[~test], i[~test]

    warm_kw = {}
    if not args.cold:
        als_cfg = AlsConfig(rank=args.als_rank, max_iter=args.als_iters,
                            reg_param=0.005, implicit_prefs=True,
                            alpha=20.0, seed=args.seed)
        ucsr = build_csr_buckets(u2, i2, r[~test], nU)
        icsr = build_csr_buckets(i2, u2, r[~test], nI)
        U, V = als_train(ucsr, icsr, als_cfg)
        warm_kw = {"als_user_factors": np.asarray(U),
                   "als_item_factors": np.asarray(V)}
        print("ALS warm-start factors trained", file=sys.stderr)

    cfg = TwoTowerConfig(embed_dim=args.embed_dim, out_dim=args.embed_dim,
                         epochs=args.epochs, seed=args.seed)
    params = train_two_tower(u2, i2, nU, nI, cfg, **warm_kw)
    # None, not NaN: json.dumps would emit the non-standard `NaN` token
    # that strict parsers (jq etc.) reject
    rec = (round(recall_at_k(params, ut, it_, k=args.k, exclude=(u2, i2)),
                 4) if len(ut) else None)
    out = {"filtered_recall_at_%d" % args.k: rec,
           "train_pairs": int(len(u2)), "test_pairs": int(len(ut)),
           "users": nU, "items": nI, "epochs": cfg.epochs,
           "warm_start": not args.cold}
    if args.output:
        save_two_tower(args.output, params, cfg, nU, nI)
        out["saved"] = args.output
    print(json.dumps(out))


def cmd_observe(args):
    """Inspect a run directory written by the other subcommands — the
    analog of pointing the Spark UI at an event-log directory — or run
    one of the measurement-side tools: ``roofline`` (the analytical
    per-stage floor), ``attribution`` (measured per-stage seconds
    joined against that floor), ``regress`` (the bench-series gate)."""
    if args.action == "regress":
        from tpu_als.obs import regress as regress_mod

        result = regress_mod.check(args.root, noise=args.noise,
                                   strict=args.strict, trend=args.trend,
                                   trend_window=args.trend_window)
        if args.as_json:
            print(json.dumps(result))
        else:
            print(regress_mod.render(result))
        if result["exit_code"]:
            raise SystemExit(result["exit_code"])
        return result

    if args.action == "attribution":
        from tpu_als import obs
        from tpu_als.core.als import AlsConfig
        from tpu_als.core.ratings import build_csr_buckets, remap_ids
        from tpu_als.perf.attribution import (
            attribution_report,
            measure_attributed,
            render_attribution,
        )
        from tpu_als.perf.roofline import roofline

        if args.obs_dir:
            from tpu_als import obs as _obs

            _obs.configure(args.obs_dir,
                           config={k: v for k, v in vars(args).items()
                                   if k != "fn"})
        frame = _load_data(args.data)
        u, _ = remap_ids(np.asarray(frame["user"]))
        i, _ = remap_ids(np.asarray(frame["item"]))
        r = np.asarray(frame["rating"], dtype=np.float32)
        nU, nI = int(u.max()) + 1, int(i.max()) + 1
        ucsr = build_csr_buckets(u, i, r, nU)
        icsr = build_csr_buckets(i, u, r, nI)
        cfg = AlsConfig(rank=args.rank, implicit_prefs=not args.explicit,
                        reg_param=args.reg, alpha=args.alpha,
                        compute_dtype=args.dtype,
                        solve_backend=args.solve_backend)
        measured = measure_attributed(ucsr, icsr, cfg, iters=args.iters,
                                      warmup=args.warmup)
        path = measured["resolved_solve_path"]
        ne_path = ("gather_fused_solve" if path == "gatherfused_solve"
                   else "gather_fused" if path.startswith("gatherfused")
                   else "einsum")
        rl = roofline(nU, nI, len(r), args.rank, dtype=args.dtype,
                      implicit=not args.explicit, ne_path=ne_path,
                      user_counts=ucsr.counts, item_counts=icsr.counts)
        rep = attribution_report(measured, rl)
        obs.emit("attribution", stages=rep["rows"],
                 wall_s_per_iter=rep["wall_s_per_iter"],
                 coverage=rep["coverage"],
                 resolved_solve_path=rep["resolved_solve_path"],
                 config=rl["config"])
        if args.as_json:
            print(json.dumps(rep))
        else:
            print(render_attribution(rep))
        if args.obs_dir:
            obs.finalize()
            obs.deconfigure()
        return rep

    if args.action == "roofline":
        from tpu_als.perf.roofline import (
            HEADLINE,
            HEADLINE_MEASURED_S_PER_ITER,
            render,
            roofline,
        )

        kwargs = dict(
            n_users=args.users, n_items=args.items, nnz=args.ratings,
            rank=args.rank, dtype=args.dtype,
            implicit=not args.explicit,
            padding_waste=args.padding_waste, devices=args.devices,
            strategy=args.strategy,
            tiles_user=args.tiles, tiles_item=args.tiles,
            ne_path=args.ne_path,
        )
        measured = args.measured_s_per_iter
        if measured is None and kwargs == dict(
                HEADLINE, strategy=None, tiles_user=1, tiles_item=1,
                ne_path="einsum"):
            # the measured point belongs to the einsum-path headline; a
            # --ne-path gather_fused render shows the revised floor
            # without pretending the old measurement sits on it
            measured = HEADLINE_MEASURED_S_PER_ITER
        report_d = roofline(**kwargs, measured_s_per_iter=measured)
        if args.as_json:
            print(json.dumps(report_d))
        else:
            print(render(report_d))
        return

    if args.action == "explain":
        from tpu_als.obs import explain as explain_mod

        try:
            print(explain_mod.explain(args.run_dir, trace=args.trace,
                                      breach=args.breach))
        except (FileNotFoundError, ValueError) as err:
            raise SystemExit(str(err))
        except BrokenPipeError:
            # `observe explain RUN | head` closing the pipe early is
            # normal; point stdout at devnull so the interpreter's
            # exit-time flush doesn't raise a second time
            import os

            os.dup2(os.open(os.devnull, os.O_WRONLY),
                    sys.stdout.fileno())
        return

    from tpu_als.obs import report

    try:
        if args.action == "summarize":
            print(report.cmd_summarize(args.run_dir, as_json=args.as_json,
                                       since=args.since,
                                       window=args.window))
        else:
            print(report.cmd_tail(args.run_dir, n=args.lines,
                                  event=args.event, tenant=args.tenant,
                                  trace=args.trace))
    except (FileNotFoundError, ValueError) as err:
        raise SystemExit(str(err))


def cmd_scenario(args):
    """Run (or list) a production-day scenario — composed chaos over
    train + serve + stream with hard assertions judged from the obs
    trail (tpu_als.scenario; docs/scenarios.md)."""
    from tpu_als import scenario

    if args.action == "list":
        for name in scenario.names():
            spec = scenario.SCENARIOS[name]
            chaos = f"  [faults: {spec.fault_spec}]" if spec.fault_spec \
                else ""
            print(f"{name}{chaos}")
            print(f"    {' '.join(spec.doc.split())}")
            for p in spec.phases:
                print(f"      - {p.name}: {p.doc}")
        return

    try:
        spec = scenario.get_scenario(args.name)
    except scenario.UnknownScenario as e:
        print(f"tpu_als scenario: {e}", file=sys.stderr)
        raise SystemExit(2) from e
    overrides = {"slo_ms": args.slo_ms,
                 "freshness_slo_ms": args.freshness_slo_ms,
                 "seed": args.seed}
    try:
        result = scenario.run_scenario(spec, config=overrides)
    except scenario.PhaseFailed as e:
        # harness breakage (a phase body raised), as opposed to a judged
        # assertion failure — still one clean line, still non-zero
        print(f"tpu_als scenario: {e}", file=sys.stderr)
        raise SystemExit(1) from e
    print(scenario.render_result(result))
    if args.as_json:
        print(json.dumps(result, default=str))
    if args.bench_json:
        scenario.bank_result(result, args.bench_json)
        print(f"banked {args.bench_json}", file=sys.stderr)
    if not result["passed"]:
        raise SystemExit(1)


def cmd_soak(args):
    """Run the production-week soak (tpu_als.soak): seeded zipfian/
    diurnal traffic over a multi-tenant fleet with live fold-in and
    periodic refit, under the declarative chaos schedule; exit 0 only
    when the SLO verdict passes.  The verdict re-derives offline from
    the run dir alone: ``python tpu_als/soak/verdict.py <obs-dir>``."""
    from tpu_als.soak import chaos, orchestrator, traffic

    cfg = traffic.TrafficConfig(
        seed=args.seed, windows=args.windows, window_s=args.window_s,
        base_qps=args.base_qps, update_qps=args.update_qps,
        poison_frac=args.poison_frac)
    schedule = chaos.default_schedule(
        cfg.windows, victim=cfg.tenants[0][0],
        subprocesses=not args.no_subprocess_chaos)
    if args.plan:
        print(f"{cfg.windows} windows x {cfg.window_s}s "
              f"(~{cfg.windows * cfg.window_s / 60.0:.2f} scheduled "
              f"minutes), tenants "
              + ", ".join(f"{n}:{w:g}" for n, w in cfg.tenants))
        print(schedule.describe())
        return
    result = orchestrator.run_soak(
        cfg, schedule, rank=args.rank, refit_every=args.refit_every,
        judge_config={"slo_ms": args.slo_ms,
                      "freshness_slo_ms": args.freshness_slo_ms,
                      "fairness_max": args.fairness_max,
                      "shed_max": args.shed_max})
    print(orchestrator.render(result))
    if args.as_json:
        print(json.dumps(result, default=str))
    if args.bench_json:
        orchestrator.bank_result(result, args.bench_json)
        print(f"banked {args.bench_json}", file=sys.stderr)
    if not result["passed"]:
        raise SystemExit(1)


def _validate_fault_spec():
    """Fail LOUDLY (typed one-liner, exit 2) on an unparseable
    ``TPU_ALS_FAULT_SPEC`` before any command body imports the faults
    module — whose import-time ``install_from_env()`` would otherwise
    surface the same mistake as a raw traceback mid-command."""
    import os

    spec = os.environ.get("TPU_ALS_FAULT_SPEC", "").strip()
    if not spec:
        return
    try:
        # the import itself arms (and validates) the env spec
        from tpu_als.resilience import faults

        faults.parse_spec(spec)
    except ValueError as e:   # FaultSpecError subclasses ValueError
        print(f"tpu_als: FaultSpecError: TPU_ALS_FAULT_SPEC is "
              f"unparseable: {e}", file=sys.stderr)
        raise SystemExit(2) from e


def cmd_plan(args):
    """Execution-planner verbs (docs/planner.md): ``show`` renders the
    persistent autotune cache (mode, entries, provenance — corrupt
    files included, flagged); ``warm`` resolves the full ExecutionPlan
    for one configuration eagerly (cold: probes run and the verdicts
    bank; warm: zero probe executions) and prints it with the resolve
    wall-clock; ``tune`` runs the measured-timing kernel autotune
    (cold: real kernel timings bank; warm: pure cache read with zero
    tuning executions; ``--force`` re-tunes, ``--bank-out`` writes the
    regress/floor_audit direct bank); ``clear`` drops the on-disk
    entries and the in-process probe registry."""
    import time

    from tpu_als import plan as plan_pkg
    from tpu_als.plan import cache as plan_cache

    if args.plan_cmd == "show":
        entries = []
        for path, doc in plan_cache.list_entries():
            if isinstance(doc, dict):
                comps = {}
                for name, comp in doc["components"].items():
                    prov = comp["provenance"]
                    comps[name] = {
                        "resolved": comp["resolved"],
                        "banked_at": prov["banked_at"],
                        "walk_seconds": prov.get("walk_seconds"),
                        "probes_executed": prov.get("probes_executed"),
                        "model": prov.get("model"),
                    }
                    # the model-vs-measured column the re-plan loop
                    # reads: present on measured-timing components
                    # (kernel_config), rendered from the provenance the
                    # cache already banks
                    if prov.get("measured_seconds") is not None:
                        comps[name]["model_vs_measured"] = {
                            "prediction_s": prov.get("model_seconds"),
                            "measured_s": prov.get("measured_seconds"),
                            "ratio": prov.get("ratio"),
                            "source": prov.get("source"),
                            "tuned_config": comp["resolved"],
                            "invalidated": prov.get("invalidated"),
                        }
                entries.append({"path": path, "plan_key": doc["plan_key"],
                                "probes": doc["probes"],
                                "components": comps})
            else:                       # PlanCacheCorrupt — show, don't die
                entries.append({"path": path, "corrupt": str(doc)})
        print(json.dumps({"mode": plan_pkg.mode(),
                          "cache_dir": plan_cache.cache_dir(),
                          "entries": entries}, indent=2, default=str))
        return

    if args.plan_cmd == "warm":
        t0 = time.perf_counter()
        ep = plan_pkg.resolve_execution_plan(
            rank=args.rank, compute_dtype=args.dtype,
            solve_backend=args.solve_backend, cg_iters=args.cg_iters,
            k=args.k, n_users=args.users, n_items=args.items,
            n_devices=args.devices)
        out = ep.summary()
        out["resolve_seconds"] = round(time.perf_counter() - t0, 4)
        out["mode"] = plan_pkg.mode()
        print(json.dumps(out, default=str))
        return out

    if args.plan_cmd == "tune":
        if not plan_pkg.armed():
            print(json.dumps({"error": "plan cache is off "
                              "(TPU_ALS_PLAN_CACHE=off) — nothing to "
                              "tune against"}))
            raise SystemExit(2)
        space = None
        if args.space is not None:
            try:
                space = json.loads(args.space)
            except json.JSONDecodeError as e:
                print(f"tpu_als: --space is not valid JSON: {e}",
                      file=sys.stderr)
                raise SystemExit(2) from e
        t0 = time.perf_counter()
        config = plan_pkg.resolve_kernel_config(
            rank=args.rank, compute_dtype=args.dtype, tune=True,
            force=args.force, budget_s=args.budget_s, space=space,
            n=args.n, w=args.w, k=args.reps, seed=args.seed)
        key = plan_pkg.plan_key(rank=int(args.rank),
                                dtype=str(args.dtype))
        entry = plan_cache.load_entry(key)
        comp = (entry or {}).get("components", {}).get("kernel_config")
        prov = (comp or {}).get("provenance") or {}
        out = {"mode": plan_pkg.mode(), "config": config,
               "provenance": prov,
               "resolve_seconds": round(time.perf_counter() - t0, 4)}
        if args.bank_out is not None and prov:
            bank = {"metric": "autotune_fused_solve_speedup_"
                              + ("cpu" if prov["source"] == "interpret"
                                 else "tpu"),
                    "value": (prov["default_seconds"]
                              / prov["measured_seconds"]),
                    "unit": "x",
                    "kernel": "gather_solve",
                    "source": prov["source"],
                    "config": comp["resolved"],
                    "default_seconds": prov["default_seconds"],
                    "tuned_seconds": prov["measured_seconds"],
                    "model_seconds": prov["model_seconds"],
                    "tune_seconds": prov["tune_seconds"],
                    "shape": prov["model"]["shape"],
                    "banked_at": prov["banked_at"]}
            with open(args.bank_out, "w") as f:
                json.dump(bank, f, indent=2)
                f.write("\n")
            out["bank_out"] = args.bank_out
        print(json.dumps(out, default=str))
        return out

    if args.plan_cmd == "clear":
        root = plan_cache.cache_dir()
        n = plan_pkg.clear()
        print(json.dumps({"cleared_entries": n, "cache_dir": root}))
        return


def cmd_lint(args):
    """Delegate to the analysis linter (docs/analysis.md), rebuilding
    its argv — the engine owns the argument semantics and the direct
    ``python tpu_als/analysis/lint.py`` invocation (jax-free) must stay
    the single source of truth for both."""
    from tpu_als.analysis import lint as _lint

    argv = []
    if args.paths is not None:
        argv += ["--paths", *args.paths]
    if args.baseline is not None:
        argv += ["--baseline", args.baseline]
    if args.write_baseline:
        argv.append("--write-baseline")
    if args.rules:
        argv.append("--rules")
    if args.contracts:
        argv.append("--contracts")
    for name in args.contract or ():
        argv += ["--contract", name]
    return _lint.main(argv)


def main(argv=None):
    # choices + help for every strategy flag come from THE table in
    # parallel.trainer (running `python -m tpu_als.cli` already paid the
    # package import, so this is free here)
    from tpu_als.parallel.trainer import (EXECUTABLE_STRATEGIES,
                                          GATHER_STRATEGIES, strategy_help)

    ap = argparse.ArgumentParser(prog="tpu_als")
    sub = ap.add_subparsers(dest="cmd", required=True)

    # every run-producing subcommand can write a metrics/events run dir;
    # default (when only --output is given) is <output>/obs
    obs_common = argparse.ArgumentParser(add_help=False)
    obs_common.add_argument(
        "--obs-dir", default=None,
        help="write metrics/tracing events for this run here "
             "(default: <--output>/obs when --output is set; "
             "inspect with `tpu_als observe summarize DIR`)")

    t = sub.add_parser("train", help="fit an ALS model",
                       parents=[obs_common])
    t.add_argument("--data", required=True)
    t.add_argument("--rank", type=int, default=10)
    t.add_argument("--max-iter", type=int, default=10)
    t.add_argument("--reg-param", type=float, default=0.1)
    t.add_argument("--implicit", action="store_true")
    t.add_argument("--alpha", type=float, default=1.0)
    t.add_argument("--nonnegative", action="store_true")
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--holdout", type=float, default=0.2)
    t.add_argument("--output", default=None)
    t.add_argument("--log-file", default=None,
                   help="write per-iteration JSON log lines here")
    t.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the fit "
                        "(TensorBoard/Perfetto-readable)")
    t.add_argument("--devices", type=int, default=1,
                   help="train sharded over N devices (0 = all visible; "
                        "1 = single device, the default)")
    t.add_argument("--gather-strategy", default="all_gather",
                   choices=list(GATHER_STRATEGIES),
                   help="how sharded half-steps move the opposite factors "
                        "(authoritative table: parallel.trainer."
                        f"GATHER_STRATEGIES — {strategy_help()})")
    t.add_argument("--per-host-data", action="store_true",
                   help="multi-process only: each process loads its OWN "
                        "--data split ('{proc}' in the spec expands to "
                        "the process index) instead of a replicated load")
    t.add_argument("--cg-iters", type=int, default=0,
                   help="> 0: inexact ALS — warm-started CG solve with "
                        "this many steps per half-step (0 = exact "
                        "batched Cholesky)")
    t.add_argument("--checkpoint-dir", default=None,
                   help="write atomic factor checkpoints under this "
                        "directory every --checkpoint-interval "
                        "iterations (also the preemption save target: "
                        "SIGTERM checkpoints here and exits 43)")
    t.add_argument("--checkpoint-interval", type=int, default=10,
                   help="iterations between checkpoints (with "
                        "--checkpoint-dir)")
    t.add_argument("--resume", default=None, metavar="PATH|auto",
                   help="warm-start from a checkpoint: a directory "
                        "path, or 'auto' to discover the newest VALID "
                        "generation under --checkpoint-dir (corrupt "
                        "generations are quarantined to .corrupt/)")
    t.add_argument("--guardrails", default=None,
                   choices=("off", "warn", "recover"),
                   help="numerical-health guardrails (docs/resilience.md):"
                        " 'warn' reads divergence sentinels each "
                        "iteration and emits guardrail_tripped events; "
                        "'recover' adds adaptive solve-jitter escalation "
                        "and bounded rollback from the last-good factor "
                        "snapshot; default inherits TPU_ALS_GUARDRAILS "
                        "(unset = off)")
    t.add_argument("--elastic", action="store_true",
                   help="elastic mesh training (needs --devices > 1): "
                        "device loss becomes a rescheduling event — a "
                        "failed step is health-probed, the mesh re-forms "
                        "on the surviving devices and training resumes "
                        "from the last atomic checkpoint "
                        "(docs/resilience.md)")
    t.set_defaults(fn=cmd_train)

    e = sub.add_parser("evaluate", help="score a dataset with a saved model",
                       parents=[obs_common])
    e.add_argument("--model", required=True)
    e.add_argument("--data", required=True)
    e.add_argument("--ranking-k", type=int, default=0,
                   help="> 0: also report precision/recall@k, MAP, and "
                        "NDCG@k (test items rated >= --positive-threshold "
                        "are the per-user ground truth)")
    e.add_argument("--positive-threshold", type=float, default=3.5)
    e.set_defaults(fn=cmd_evaluate)

    r = sub.add_parser("recommend", help="top-k recommendations",
                       parents=[obs_common])
    r.add_argument("--model", required=True)
    r.add_argument("--users", default=None,
                   help="comma-separated original user ids (default: all)")
    r.add_argument("--k", type=int, default=10)
    r.add_argument("--limit", type=int, default=20,
                   help="max users to print (0 = all)")
    r.add_argument("--foldin-data", default=None,
                   help="ratings (csv:path / ml-100k:path) to fold into "
                        "the user factors before recommending — serves "
                        "new ratings/users without a refit")
    r.add_argument("--foldin-items-data", default=None,
                   help="ratings whose ITEMS are folded in against the "
                        "fixed user factors (new catalog entries served "
                        "without a refit); applied before --foldin-data")
    r.add_argument("--titles", default=None,
                   help="movie metadata path (u.item / movies.dat / "
                        "movies.csv, or their directory): join titles "
                        "into the output")
    r.add_argument("--devices", type=int, default=1,
                   help="serve all-users top-k sharded over N devices "
                        "(0 = all visible; 1 = single device)")
    r.add_argument("--gather-strategy", default="all_gather",
                   choices=["all_gather", "ring"],
                   help="sharded serving: gather the catalog once, or "
                        "ring-stream shards (catalog larger than one "
                        "device's HBM)")
    r.set_defaults(fn=cmd_recommend)

    g = sub.add_parser("tune", help="cross-validated grid search",
                       parents=[obs_common])
    g.add_argument("--data", required=True)
    g.add_argument("--ranks", default="8,16,32",
                   help="comma-separated rank grid")
    g.add_argument("--reg-params", default="0.01,0.05,0.1",
                   help="comma-separated regParam grid")
    g.add_argument("--max-iter", type=int, default=10)
    g.add_argument("--folds", type=int, default=3)
    g.add_argument("--implicit", action="store_true")
    g.add_argument("--alpha", type=float, default=1.0)
    g.add_argument("--alphas", default=None,
                   help="comma-separated alpha grid (implicit feedback); "
                        "alpha is traced, so the wider grid costs no "
                        "extra compiles")
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--output", default=None,
                   help="save the best model here")
    g.add_argument("--cg-iters", type=int, default=0,
                   help="> 0: inexact-ALS CG solve for every grid fit "
                        "(k x numFolds fits amortize the speedup)")
    g.set_defaults(fn=cmd_tune)

    tt = sub.add_parser("tt-train",
                        help="train + persist the two-tower retrieval "
                             "model (ALS warm start by default)",
                        parents=[obs_common])
    tt.add_argument("--data", required=True)
    tt.add_argument("--output", default=None,
                    help="save the trained towers here")
    tt.add_argument("--epochs", type=int, default=5)
    tt.add_argument("--embed-dim", type=int, default=32)
    tt.add_argument("--als-rank", type=int, default=32)
    tt.add_argument("--als-iters", type=int, default=8)
    tt.add_argument("--cold", action="store_true",
                    help="skip the ALS warm start")
    tt.add_argument("--holdout", type=float, default=0.1)
    tt.add_argument("--positive-threshold", type=float, default=3.5)
    tt.add_argument("--k", type=int, default=10)
    tt.add_argument("--seed", type=int, default=0)
    tt.set_defaults(fn=cmd_tt_train)

    sb = sub.add_parser(
        "serve-bench",
        help="open-loop serving latency benchmark against an SLO "
             "(micro-batched engine, int8 index unless --exact)",
        parents=[obs_common])
    sb.add_argument("--users", type=int, default=20_000)
    sb.add_argument("--items", type=int, default=50_000)
    sb.add_argument("--rank", type=int, default=64)
    sb.add_argument("--k", type=int, default=10)
    sb.add_argument("--shortlist-k", type=int, default=64,
                    help="int8 shortlist rescored exactly in f32 "
                         "(>= items makes the match unconditional)")
    sb.add_argument("--exact", action="store_true",
                    help="skip the int8 index; score every request on "
                         "the exact chunked kernel")
    sb.add_argument("--qps", type=float, default=200.0,
                    help="open-loop arrival rate (requests/second)")
    sb.add_argument("--duration", type=float, default=5.0,
                    help="measured window in seconds")
    sb.add_argument("--slo-ms", type=float, default=50.0,
                    help="end-to-end p99 target the report is judged "
                         "against")
    sb.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; requests that exceed it "
                         "while queued fail instead of being scored")
    sb.add_argument("--max-queue", type=int, default=1024,
                    help="admission-queue depth beyond which requests "
                         "are shed (typed Overloaded)")
    sb.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="upper bound on a request's wait for company "
                         "in the admission queue (the started engine "
                         "closes a batch as soon as its pipeline has a "
                         "free slot, so it binds only for schedulers "
                         "that dequeue by themselves)")
    sb.add_argument("--buckets", default=None,
                    help="comma-separated padded batch sizes (one "
                         "compiled program each); default: the "
                         "execution planner's bucket plan (a banked "
                         "ladder for this device, else 8,32,128)")
    sb.add_argument("--foldin-frac", type=float, default=0.0,
                    help="fraction of requests carrying a fold-in "
                         "factor row instead of a user id")
    sb.add_argument("--mesh-devices", type=int, default=0,
                    help="> 0 serves from a device mesh of this many "
                         "shards: the catalog lives shard-resident "
                         "(never committed whole to one device) and "
                         "scoring runs the sharded fabric "
                         "(docs/serving.md)")
    sb.add_argument("--update-qps", type=float, default=0.0,
                    help="concurrent rating-event rate through the "
                         "live fold-in → publish pipeline; >0 makes "
                         "the headline metric live_freshness_p99_ms")
    sb.add_argument("--freshness-slo-ms", type=float, default=5000.0,
                    help="arrival → servable p99 target for the live "
                         "stream (breach dumps the updater's flight "
                         "ring)")
    sb.add_argument("--update-poison-frac", type=float, default=0.0,
                    help="fraction of update events with a non-finite "
                         "rating — must be quarantined, never folded")
    sb.add_argument("--update-items", action="store_true",
                    help="also fold the ITEM side of each micro-batch "
                         "(exercises the index's incremental delta "
                         "re-quantization)")
    sb.add_argument("--update-max-batch", type=int, default=None,
                    help="live micro-batch cap (default: the "
                         "planner's live cadence)")
    sb.add_argument("--update-max-wait-ms", type=float, default=None,
                    help="live micro-batch deadline (default: the "
                         "planner's live cadence)")
    sb.add_argument("--tenants", type=int, default=0,
                    help=">= 2 runs the multi-tenant variant: N "
                         "same-shaped models behind one "
                         "MultiTenantEngine, equal open-loop load per "
                         "tenant, headline tenancy_worst_p99_ms judged "
                         "per tenant plus a goodput fairness ratio "
                         "(docs/tenancy.md)")
    sb.add_argument("--tenant-weights", default=None,
                    help="comma-separated fair-share weights, one per "
                         "tenant (default: all 1.0); the fairness "
                         "ratio is computed on served rows per weight")
    sb.add_argument("--fairness-bound", type=float, default=1.5,
                    help="max/min weighted-goodput ratio above which "
                         "the multi-tenant report fails its SLO")
    sb.add_argument("--seed", type=int, default=0)
    sb.add_argument("--bench-json", default=None, metavar="PATH",
                    help="also bank the result JSON (with banked_at "
                         "provenance) here, e.g. BENCH_serve_cpu.json")
    sb.set_defaults(fn=cmd_serve_bench)

    sc = sub.add_parser(
        "scenario",
        help="scripted production-day scenarios: composed chaos over "
             "train + serve + stream, judged by hard assertions "
             "evaluated from the obs trail (docs/scenarios.md)")
    scsub = sc.add_subparsers(dest="action", required=True)
    scr = scsub.add_parser(
        "run", help="run one named scenario; exit 0 only if every "
                    "assertion holds", parents=[obs_common])
    scr.add_argument("name",
                     help="scenario name (see `tpu_als scenario list`)")
    scr.add_argument("--slo-ms", type=float, default=None,
                     help="override the latency-SLO bound scenarios "
                          "judge p99 against (traffic-spike)")
    scr.add_argument("--freshness-slo-ms", type=float, default=None,
                     help="override the rating-arrival -> servable "
                          "bound (cold-start)")
    scr.add_argument("--seed", type=int, default=None,
                     help="override the scenario's default seed")
    scr.add_argument("--bench-json", default=None, metavar="PATH",
                     help="also bank the result JSON (with banked_at "
                          "provenance) here, e.g. "
                          "BENCH_scenario_traffic-spike.json")
    scr.add_argument("--json", dest="as_json", action="store_true",
                     help="also print the result as one JSON object")
    scr.set_defaults(fn=cmd_scenario)
    scl = scsub.add_parser(
        "list", help="list the scenarios, their chaos and their phases")
    scl.set_defaults(fn=cmd_scenario, obs_dir=None)

    sk = sub.add_parser(
        "soak",
        help="the production week at compressed timescale: synthetic "
             "zipfian/diurnal traffic drives multi-tenant serve + live "
             "fold-in + refit under a chaos schedule; exit 0 only when "
             "the SLO verdict passes (tpu_als.soak; docs/soak.md)",
        parents=[obs_common])
    sk.add_argument("--windows", type=int, default=8,
                    help="soak windows (the compressed week's length)")
    sk.add_argument("--window-s", type=float, default=3.0,
                    help="wall seconds per window")
    sk.add_argument("--base-qps", type=float, default=40.0,
                    help="serve queries/sec at the diurnal mean")
    sk.add_argument("--update-qps", type=float, default=25.0,
                    help="rating arrivals/sec at the diurnal mean")
    sk.add_argument("--poison-frac", type=float, default=0.02,
                    help="per-event probability a rating arrives "
                         "poisoned (nan -> quarantine path)")
    sk.add_argument("--seed", type=int, default=17,
                    help="traffic seed; (seed, schedule) replays the "
                         "whole workload byte-for-byte")
    sk.add_argument("--rank", type=int, default=8)
    sk.add_argument("--refit-every", type=int, default=3,
                    help="periodic refit-and-republish cadence, in "
                         "windows (0 disables; chaos refits still run)")
    sk.add_argument("--no-subprocess-chaos", action="store_true",
                    help="drop the CLI-child injections (preempt, "
                         "device loss) for a fast in-process soak")
    sk.add_argument("--slo-ms", type=float, default=None,
                    help="serve p99 bound for victim-free tenants")
    sk.add_argument("--freshness-slo-ms", type=float, default=None,
                    help="rating-arrival -> servable p99 bound")
    sk.add_argument("--fairness-max", type=float, default=None,
                    help="max/min answered-rate ratio across tenants")
    sk.add_argument("--shed-max", type=float, default=None,
                    help="shed/offered ceiling over the whole soak")
    sk.add_argument("--plan", action="store_true",
                    help="print the chaos schedule and exit (no soak)")
    sk.add_argument("--bench-json", default=None, metavar="PATH",
                    help="bank the verdict (survived-minutes headline, "
                         "tz-aware banked_at) here, e.g. "
                         "BENCH_soak_cpu.json")
    sk.add_argument("--json", dest="as_json", action="store_true",
                    help="also print the result as one JSON object")
    sk.set_defaults(fn=cmd_soak)

    f = sub.add_parser("foldin-bench", help="fold-in latency micro-benchmark",
                       parents=[obs_common])
    f.add_argument("--model", required=True)
    f.add_argument("--batches", type=int, default=20)
    f.add_argument("--batch-size", type=int, default=512)
    f.set_defaults(fn=cmd_foldin_bench)

    o = sub.add_parser("observe",
                       help="inspect a run directory's metrics/events")
    osub = o.add_subparsers(dest="action", required=True)
    os1 = osub.add_parser("summarize",
                          help="per-phase timings, per-iteration RMSE, "
                               "comm-bytes gauges, throughput")
    os1.add_argument("run_dir",
                     help="run dir (--output / --obs-dir of a past run)")
    os1.add_argument("--json", dest="as_json", action="store_true",
                     help="emit the summary as one JSON object")
    os1.add_argument("--since", type=float, default=None, metavar="S",
                     help="only events at/after S seconds into the "
                          "trail (relative to its first event)")
    os1.add_argument("--window", default=None, metavar="A:B",
                     help="only events in [A, B) seconds into the "
                          "trail (either side may be empty) — slice a "
                          "soak trail per chaos window")
    os1.set_defaults(fn=cmd_observe)
    os2 = osub.add_parser("tail", help="print the last N raw events")
    os2.add_argument("run_dir")
    os2.add_argument("-n", "--lines", type=int, default=20)
    os2.add_argument("--event", default=None, metavar="TYPE",
                     help="only events of this type (e.g. flight_record, "
                          "scenario_assert) — the last N AFTER filtering")
    os2.add_argument("--tenant", default=None, metavar="NAME",
                     help="only events labeled tenant=NAME — the last N "
                          "AFTER filtering")
    os2.add_argument("--trace", default=None, metavar="ID",
                     help="only events of one causal trace (trace_id "
                          "match, or membership in an event's trace_ids)")
    os2.set_defaults(fn=cmd_observe)
    os3 = osub.add_parser(
        "roofline",
        help="analytical per-stage bytes/FLOPs floor for one ALS "
             "iteration (defaults: THE headline config, with its "
             "measured point; see docs/roofline.md)")
    from tpu_als.perf.roofline import HEADLINE as _RL_HEADLINE

    os3.add_argument("--users", type=int, default=_RL_HEADLINE["n_users"])
    os3.add_argument("--items", type=int, default=_RL_HEADLINE["n_items"])
    os3.add_argument("--ratings", type=int, default=_RL_HEADLINE["nnz"])
    os3.add_argument("--rank", type=int, default=_RL_HEADLINE["rank"])
    os3.add_argument("--dtype", default=_RL_HEADLINE["dtype"],
                     choices=["float32", "bfloat16"])
    os3.add_argument("--explicit", action="store_true",
                     help="explicit feedback (default: implicit)")
    os3.add_argument("--padding-waste", type=float,
                     default=_RL_HEADLINE["padding_waste"],
                     help="padded_nnz / nnz of the built containers")
    os3.add_argument("--devices", type=int,
                     default=_RL_HEADLINE["devices"])
    os3.add_argument("--strategy", default=None,
                     choices=list(EXECUTABLE_STRATEGIES),
                     help="price the collective stage too (sharded; "
                          "table: parallel.trainer.GATHER_STRATEGIES)")
    os3.add_argument("--tiles", type=int, default=1,
                     help="row-tile count (ring/chunked strategies "
                          "re-stream the opposite factors per tile)")
    os3.add_argument("--ne-path", default="einsum",
                     choices=["einsum", "gather_fused",
                              "gather_fused_solve"],
                     help="normal-equation build to price: the unfused "
                          "gather+einsum round-trip, or the DMA-gather "
                          "fused kernel (ops/pallas_gather_ne — factor "
                          "rows read once, Vg never in HBM)")
    os3.add_argument("--measured-s-per-iter", type=float, default=None,
                     help="overlay a measured point (default: the "
                          "headline 1.184 when the config is untouched)")
    os3.add_argument("--json", dest="as_json", action="store_true")
    os3.set_defaults(fn=cmd_observe)
    os4 = osub.add_parser(
        "attribution",
        help="MEASURE where an iteration's seconds go: fence-timed "
             "per-stage seconds joined against the roofline floor "
             "(the measured counterpart of `observe roofline`)")
    os4.add_argument("--data", default="synthetic:943x1682x100000",
                     help="same specs as train --data; default is the "
                          "ml-100k shape synthetically (CPU-friendly); "
                          "use ml-100k:PATH for the real ratings")
    os4.add_argument("--rank", type=int, default=16)
    os4.add_argument("--iters", type=int, default=3,
                     help="fence-timed iterations (after --warmup "
                          "compile-absorbing ones)")
    os4.add_argument("--warmup", type=int, default=1)
    os4.add_argument("--explicit", action="store_true",
                     help="explicit feedback (default: implicit)")
    os4.add_argument("--dtype", default="float32",
                     choices=["float32", "bfloat16"])
    os4.add_argument("--reg", type=float, default=0.1)
    os4.add_argument("--alpha", type=float, default=1.0)
    os4.add_argument("--solve-backend", default="auto",
                     choices=["auto", "unfused", "gather_fused",
                              "gather_fused_solve"],
                     help="exact paths only (the CG ablations have no "
                          "decomposed twin)")
    os4.add_argument("--obs-dir", default=None, metavar="DIR",
                     help="also write the stage histograms + "
                          "attribution event as a run dir")
    os4.add_argument("--json", dest="as_json", action="store_true")
    os4.set_defaults(fn=cmd_observe)
    os5 = osub.add_parser(
        "regress",
        help="bench regression gate over the committed BENCH_*/"
             "MULTICHIP_* series: regressions beyond a noise band, "
             "value:null banks, missing banked_at provenance; typed "
             "exit code (1=regression 2=null 3=provenance)")
    os5.add_argument("root", nargs="?", default=".",
                     help="directory holding the bench artifacts "
                          "(default: cwd)")
    os5.add_argument("--noise", type=float, default=0.10,
                     help="relative band a latest-vs-best-prior move "
                          "must exceed to count as a regression")
    os5.add_argument("--strict", action="store_true",
                     help="historical nulls/unparseable rounds become "
                          "errors instead of warnings")
    os5.add_argument("--trend", action="store_true",
                     help="also fit the last --trend-window rounds of "
                          "each series and fail on sustained drift in "
                          "the worse direction beyond the noise band "
                          "(catches a slow slide the latest-vs-best "
                          "check misses)")
    os5.add_argument("--trend-window", type=int, default=5,
                     metavar="N",
                     help="rounds in the trend fit (needs >= 3 "
                          "effective points; default 5)")
    os5.add_argument("--json", dest="as_json", action="store_true")
    os5.set_defaults(fn=cmd_observe)
    os6 = osub.add_parser(
        "explain",
        help="reconstruct a request/event's full causal tree (admit -> "
             "queue -> round -> score / fold-in -> publish -> visible) "
             "from the trail's trace_span events; --breach last starts "
             "from the latest freshness/SLO breach")
    os6.add_argument("run_dir",
                     help="run dir / obs dir / events.jsonl path")
    os6.add_argument("--trace", default=None, metavar="ID",
                     help="render one trace's tree")
    os6.add_argument("--breach", default=None, choices=("last",),
                     help="start from the trail's last breach event and "
                          "render the trace it names")
    os6.set_defaults(fn=cmd_observe)

    pl = sub.add_parser(
        "plan",
        help="execution planner: inspect, warm, or clear the "
             "persistent autotune cache (docs/planner.md; "
             "TPU_ALS_PLAN_CACHE overrides the location, 'off' "
             "disarms)")
    plsub = pl.add_subparsers(dest="plan_cmd", required=True)
    pls = plsub.add_parser(
        "show", help="render the cache: mode, entries, per-component "
                     "provenance (corrupt files flagged, not fatal)")
    pls.set_defaults(fn=cmd_plan, obs_dir=None)
    plw = plsub.add_parser(
        "warm", parents=[obs_common],
        help="resolve the full ExecutionPlan for one configuration "
             "eagerly — cold resolves probe and bank, warm resolves "
             "answer from the cache with zero probe executions")
    plw.add_argument("--rank", type=int, default=128)
    plw.add_argument("--dtype", default="float32",
                     choices=["float32", "bfloat16"])
    plw.add_argument("--solve-backend", default="auto",
                     choices=["auto", "unfused", "gather_fused",
                              "gather_fused_solve"])
    plw.add_argument("--cg-iters", type=int, default=0)
    plw.add_argument("--k", type=int, default=10,
                     help="serving top-k (the pallas_topk probe keys "
                          "on it)")
    plw.add_argument("--users", type=int, default=None,
                     help="with --items and --devices > 1: also "
                          "resolve the gather strategy for this shape")
    plw.add_argument("--items", type=int, default=None)
    plw.add_argument("--devices", type=int, default=1)
    plw.set_defaults(fn=cmd_plan)
    plt = plsub.add_parser(
        "tune", parents=[obs_common],
        help="measured-timing kernel autotune at one shape class — "
             "cold: times real kernels min-of-k and banks the winner "
             "into the plan entry; warm: reads the banked config with "
             "zero tuning executions (--force re-tunes)")
    plt.add_argument("--rank", type=int, default=128)
    plt.add_argument("--dtype", default="float32",
                     choices=["float32", "bfloat16"])
    plt.add_argument("--budget-s", type=float, default=None,
                     help="wall-clock tuning budget in seconds; the "
                          "trial loop stops when exceeded (default: "
                          "120)")
    plt.add_argument("--space", default=None,
                     help="JSON dict restricting the search space, "
                          "e.g. '{\"depth\": [2, 8]}' — unknown knobs "
                          "are a typed error")
    plt.add_argument("--n", type=int, default=256,
                     help="timing-harness item count")
    plt.add_argument("--w", type=int, default=64,
                     help="timing-harness gather width")
    plt.add_argument("--reps", type=int, default=3,
                     help="min-of-k repetitions per trial")
    plt.add_argument("--seed", type=int, default=0)
    plt.add_argument("--force", action="store_true",
                     help="re-tune even when a valid banked config "
                          "exists (device-sourced banks still refuse "
                          "interpret-mode overwrites)")
    plt.add_argument("--bank-out", default=None,
                     help="also write a BENCH-style direct bank "
                          "(regress/floor_audit format) to this path")
    plt.set_defaults(fn=cmd_plan)
    plc = plsub.add_parser(
        "clear", help="drop the on-disk entries and the in-process "
                      "probe registry (.corrupt/ evidence is kept)")
    plc.set_defaults(fn=cmd_plan, obs_dir=None)

    ln = sub.add_parser(
        "lint",
        help="tracer-safety linter + jaxpr contract registry "
             "(docs/analysis.md; the AST pass is stdlib-only, "
             "--contracts re-verifies the byte pins)")
    ln.add_argument("--paths", nargs="*", default=None,
                    help="files/dirs to lint (default: tpu_als/, "
                         "scripts/, bench.py)")
    ln.add_argument("--baseline", default=None,
                    help="baseline file of accepted findings "
                         "(default: lint_baseline.txt; 'none' disables)")
    ln.add_argument("--write-baseline", action="store_true",
                    help="write current findings to the baseline file")
    ln.add_argument("--rules", action="store_true",
                    help="print the rule catalog and exit")
    ln.add_argument("--contracts", action="store_true",
                    help="also re-verify every registered jaxpr "
                         "contract (guardrails_disarmed, plan_cache_off, "
                         "ne_audit, comm_audit)")
    ln.add_argument("--contract", action="append", default=None,
                    help="verify only this named contract (repeatable; "
                         "implies --contracts)")
    ln.set_defaults(fn=cmd_lint)

    args = ap.parse_args(argv)
    _validate_fault_spec()
    if getattr(args, "nonnegative", False) and \
            getattr(args, "cg_iters", 0) > 0:
        # solver precedence is nonnegative (NNLS) > cg (core/als.py);
        # refusing beats silently running the exact NNLS path under a
        # CG label (same stance as scripts/ablate.py's fused+cg guard)
        ap.error("--cg-iters cannot be combined with --nonnegative "
                 "(the NNLS solver takes precedence and the CG request "
                 "would be silently ignored)")
    if args.cmd in ("observe", "lint"):
        return args.fn(args)  # read-only commands must not write a run dir

    from tpu_als import obs
    from tpu_als.utils.platform import enable_persistent_compile_cache

    # every other command compiles: keep the executables across runs
    # (JAX_COMPILATION_CACHE_DIR, else .bench_cache/xla_cache in the
    # checkout — utils.platform says why the place is fixed)
    enable_persistent_compile_cache()

    run_dir = args.obs_dir
    if run_dir is None and getattr(args, "output", None):
        import os

        run_dir = os.path.join(args.output, "obs")
    if run_dir is not None:
        obs.configure(
            run_dir,
            config={k: v for k, v in vars(args).items() if k != "fn"},
            argv=list(argv) if argv is not None else sys.argv[1:])
        obs.emit("command", cmd=args.cmd,
                 argv=list(argv) if argv is not None else sys.argv[1:])
    try:
        with obs.span("cli." + args.cmd):
            return args.fn(args)
    finally:
        if run_dir is not None:
            # AFTER the command body: a train --output save atomically
            # REPLACES the output dir, so the run dir under it must be
            # written once the model is installed, not before.
            # deconfigure so a process issuing several commands (tests,
            # notebooks) never writes a later command's events here
            out = obs.finalize()
            obs.deconfigure()
            if out is not None:
                print(f"run metrics written to {out} "
                      f"(tpu_als observe summarize {out})",
                      file=sys.stderr)


if __name__ == "__main__":
    # several commands return report objects for in-process callers;
    # only integer returns are exit codes (lint findings, contract fails)
    _rc = main()
    sys.exit(_rc if isinstance(_rc, int) else 0)
