"""``kind: serve_live_implicit`` — ``serve_live_items``' two streams, parameter
for parameter, against a live deployment under IMPLICIT feedback
(``implicitPrefs``: Hu, Koren and Volinsky's rule, an event's 1-5 value the
observation's strength): every batch folds its users AND its items, and
every fold reads ``F^T F`` of its whole fixed table — the Gram matrix the
fold-in server keeps beside each table and moves by the rows a fold wrote
(``FoldInServer.yty``), never recomputed over the table in the window.

Everything the sibling already does is the sibling's (imported, nothing of it
changed): ``SeqLoop``, ``PublishTap``, ``open_streams``, ``published_rows``,
``fold_checks``, ``window_checks``, ``read_your_writes``.  New here: the
model's parameters carry the configuration's ``alpha`` (:func:`start_live`),
the replay is ``reference/foldin_implicit_replay.py``'s, which keeps its own
float64 Gram matrices, and ``correct`` gains:

(f) EVERY fold of the run is held to the float64 IMPLICIT fold of the same
ratings over the same published rows WITH the replay's float64 ``G`` (the
sibling's check (a), under this rule: a stale, frozen or lower-precision
``G`` shows in every fold after it); (g) after the drain the program's two
Gram matrices are read back and held to float64 ``gram()`` of the final
published tables, Frobenius, by side (``gram_user_rel_err`` /
``gram_item_rel_err``), and the replay's own moved matrices to the same
(``replay_gram_drift``); (h) no whole-table Gram program ran between the
stream's start and the window's end (``foldin.yty_full``).  A program that
keeps no Gram matrix or no such counter is not ``correct`` and says why;
nothing here raises on one.

``run(cell, program_als=...)`` hands the PROGRAM other ALS parameters than
the configuration's, which the reference keeps: the explicit-rule control of
``tests/chip_readings_live_implicit.py``.  The benchmark never passes it.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from tpu_als import ALSModel, FoldInServer, IdMap, LiveUpdater, obs

from benchmark import datagen
from benchmark.harness import Outcome, at_least, at_most
from benchmark.reference import foldin_implicit as ref_rule
from benchmark.reference import foldin_implicit_replay as ref_replay
from benchmark.reference.foldin_replay import published_of
from benchmark.runners import serve
from benchmark.runners.serve_live import freshness_ms
from benchmark.runners.serve_live_items import (
    PublishTap,
    fold_checks,
    open_streams,
    published_rows,
    read_your_writes,
    window_checks,
)

YTY_FULL = "foldin.yty_full"


def start_live(engine, U, V, config, als=None):
    """``serve_live.start_live`` with the rule's ``alpha`` in the model's
    parameters (the sibling's writes 1.0: its rule has none).  ``als``:
    the parameters the PROGRAM is given, the configuration's unless a
    control says otherwise."""
    stamps = [time.perf_counter()]

    def lap():
        stamps.append(time.perf_counter())
        return stamps[-1] - stamps[-2]

    als, live = als or config["als"], config["live"]
    model = ALSModel(
        als["rank"], IdMap(ids=np.arange(config["num_users"])),
        IdMap(ids=np.arange(config["num_items"])), U, V,
        {"userCol": "user", "itemCol": "item", "ratingCol": "rating",
         "regParam": als["regParam"], "implicitPrefs": als["implicitPrefs"],
         "alpha": als.get("alpha", 1.0), "nonnegative": als["nonnegative"]})
    server = FoldInServer(model, keep_history=live["keep_history"])
    phases = {"foldin_server_s": lap()}
    server.prewarm()
    phases["foldin_prewarm_s"] = lap()
    updater = LiveUpdater(
        engine, server, max_queue=live["max_queue"],
        max_batch=live["max_batch"], max_wait_ms=live["max_wait_ms"],
        fold_items=live["fold_items"], flight_capacity=1 << 16)
    return model, server, updater, phases


def replay_of(streams, updater, tap, model, U, V, config,
              operand_dtype=None, gram_dtype=None):
    """``serve_live_items.replay_of`` under the implicit rule.
    ``operand_dtype`` / ``gram_dtype``: the CONTROL in the program's place
    — what a replay whose folds (whose Gram matrices) take operands of
    that precision would have published, held to the float64 folds the
    same way."""
    recs = [r for r in updater.flight.records() if r.get("status") == "ok"]
    users, items, stars = (np.concatenate(
        [getattr(ev, name)[ev.admitted] for _, ev in streams])
        for name in ("user", "item", "stars"))
    if (any("events" not in r or "seq" not in r for r in recs)
            or sum(r["events"] for r in recs) != len(users)):
        return None
    sizes = [r["events"] for r in recs]
    als = config["als"]
    rule = dict(reg=als["regParam"], alpha=als["alpha"],
                fold_items=config["live"]["fold_items"])
    if operand_dtype is None and gram_dtype is None:
        published = published_rows(tap, model, recs)
    else:
        published = published_of(ref_replay.replay(
            U, V, users, items, stars, sizes, operand_dtype=operand_dtype,
            gram_dtype=gram_dtype, **rule), len(sizes))
    rep = ref_replay.replay(U, V, users, items, stars, sizes,
                            published=published, **rule)
    return rep, (users, items, stars), np.array([r["seq"] for r in recs])


def yty_full_runs():
    """Whole-table Gram programs the program has counted so far, or
    ``None`` where it keeps no such counter."""
    if YTY_FULL not in obs.schema.METRICS:
        return None
    return sum(v for _, v in obs.counter_series(YTY_FULL))


def gram_checks(server, rep, lim, full_runs, kept=None):
    """(g) and (h), and the readings behind them.  ``kept``: the Gram
    matrices in the program's place (a control's), ``[users', items']``."""
    want = [ref_rule.gram(rep.final_table(side)) for side in (0, 1)]
    drift = [ref_replay.rel_err(rep.gram[side], want[side])
             for side in (0, 1)]
    if kept is None:
        read = getattr(server, "yty", None)
        kept = [None if read is None else read(items_side=side == 0)
                for side in (0, 1)]
    found = [None if G is None else ref_replay.rel_err(np.asarray(G), w)
             for G, w in zip(kept, want)]
    checks = [
        at_most("replay_gram_drift", max(drift), lim["replay_gram_drift"]),
        at_least("gram_matrices_kept", sum(e is not None for e in found), 2),
        at_least("yty_full_counter_kept", float(full_runs is not None), 1.0),
        at_most("yty_full_in_window", full_runs or 0, 0)]
    checks += [at_most(f"gram_{name}_rel_err", err, lim[f"gram_{name}_rel_err"])
               for name, err in zip(("user", "item"), found)
               if err is not None]
    return checks, {"gram_rel_err": dict(zip(("user", "item"), found)),
                    "replay_gram_drift": dict(zip(("user", "item"), drift))}


def run(cell, program_als=None):
    import jax

    cfg, mix = cell.config, cell.traffic
    k = cfg["serving"]["k"]
    t_start = time.perf_counter()
    engine, U, V, phases = serve.start_engine(cfg, mix, cell.seed)
    tap = PublishTap(engine)
    model, server, updater, live_phases = start_live(
        tap, U, V, cfg, program_als)
    phases.update(live_phases)
    t0 = time.perf_counter()
    # the item direction's programs and its fixed table, as docs/serving.md
    # tells whoever folds items
    server.prewarm(sides=("item",))
    phases["foldin_prewarm_items_s"] = time.perf_counter() - t0
    rng, ev_rng = datagen.rng_for(cell.seed, 2), datagen.rng_for(cell.seed, 5)
    streams = []        # [(loop, events)] — the window's, then the traced
    folded0 = obs.counter_value("foldin.ratings")
    sampled0 = obs.histogram_count("live.freshness_seconds")
    t0 = time.perf_counter()
    updater.start()     # the row writes; with fold_items the catalog's too
    phases["updater_start_s"] = time.perf_counter() - t0
    cell.say("engine_ready", **phases, k=engine.k,
             users=cfg["num_users"], items=cfg["num_items"],
             rank=cfg["als"]["rank"])
    try:
        loop, marks, events = open_streams(
            engine, updater, U, cfg, mix, rng, ev_rng, cell.seconds, k,
            cfg["num_users"], cfg["num_items"], clock=cell.clock)
        events.start()
        streams.append((loop, events))
        cell.say("ready", requests_s=time.perf_counter() - t_start
                 - sum(phases.values()), head=loop.head, events=events.n,
                 new_items=int(events.is_new_item.sum()))
        sent0 = {n: obs.counter_value(n) for n in (
            "live.publish_h2d_bytes", "live.catalog_h2d_bytes")}
        full0 = yty_full_runs()
        loop.run()
        in_window = cell.clock.since(marks["compile"])
        events.join(mix["answer_timeout_s"])
        full_runs = None if full0 is None else yty_full_runs() - full0
        setup_s = loop.t0 + mix["warmup_seconds"] - cell.t_process
        t_open, t_close = loop.t0 + mix["warmup_seconds"], loop.t_last_submit
        sent = {n: obs.counter_value(n) - v for n, v in sent0.items()}
        trace_dir = None
        if cell.trace:
            from benchmark.trace import profiler_options

            traced, _, traced_events = open_streams(
                engine, updater, U, cfg, mix, rng, ev_rng,
                mix["trace_seconds"], k,
                cfg["num_users"] + int(events.is_new.sum()),
                cfg["num_items"] + int(events.is_new_item.sum()))
            streams.append((traced, traced_events))
            trace_dir = cell.scratch("trace")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profiler_options())
            try:
                traced_events.start()
                traced.run()
                traced_events.join(mix["answer_timeout_s"])
            finally:
                jax.profiler.stop_trace()
        t0 = time.perf_counter()
        updater.stop(drain_timeout_s=mix["events"]["drain_timeout_s"])
        drain_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        replayed = replay_of(streams, updater, tap, model, U, V, cfg)
        replay_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ryw_checks, ryw = ([at_least("batches_in_the_records", 0, 1)], None)
        if replayed is not None:
            ryw_checks, ryw = read_your_writes(engine, model, replayed[0],
                                               cfg, mix, cell.seed)
        ryw_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        yty_checks, yty_said = ([], {})
        if replayed is not None:
            yty_checks, yty_said = gram_checks(server, replayed[0],
                                               cfg["correct"], full_runs)
        gram_s = time.perf_counter() - t0
    finally:
        updater.stop(drain_timeout_s=1.0)
        engine.stop()

    lat, late, queue = loop.latency_ms(), loop.late_ms(), loop.queue_ms()
    recs = [r for r in updater.flight.records() if r.get("status") == "ok"]
    in_win = [r for r in recs if t_open <= r.get("t_done", -1.0) < t_close]
    fresh = freshness_ms(updater, events)
    admitted = sum(int(ev.admitted.sum()) for _, ev in streams)
    shed = sum(ev.n for _, ev in streams) - admitted
    failed = (loop.n - len(loop.answered())
              + int((~events.admitted[events.head:]).sum()))
    attempted = loop.n + events.n - events.head
    for tr_loop, tr_events in streams[1:]:   # untimed, but a failure counts
        failed += (tr_loop.n - len(tr_loop.answered())
                   + int((~tr_events.admitted).sum()))
        attempted += tr_loop.n + tr_events.n
    cell.say("window", setup_s=setup_s, offered_per_s=mix["rate_per_s"],
             requests=loop.n, answered=len(lat),
             failed=loop.n - len(lat), errors=sorted(
                 collections.Counter(e for j, e in loop.errors.items()
                                     if j >= loop.head).items()),
             drain_s=loop.t_end - loop.t_last_submit,
             batches=loop.batches(), batch_sizes=loop.batch_sizes(),
             compile_in_window=in_window,
             gc=loop.gc_clock.summary(), slowest=loop.slowest(),
             latency_ms={q: float(np.percentile(lat, q)) if len(lat) else None
                         for q in (50, 90, 95, 99, 99.9, 100)},
             late_ms={q: float(np.percentile(late, q)) for q in (50, 99, 100)})
    rep = replayed[0] if replayed is not None else None
    compactions = [r for r in in_win if r.get("mode") == "compact"]
    cell.say("live", events_per_s=mix["events"]["rate_per_s"],
             events=events.n, in_window=events.n - events.head,
             admitted=admitted, shed=shed, errors=sorted(
                 collections.Counter(events.errors.values()).items()),
             new_users=len(model._user_map) - cfg["num_users"],
             new_items=len(model._item_map) - cfg["num_items"],
             new_items_in_window=int(sum(r.get("new_items", 0)
                                         for r in in_win)),
             publishes=len(recs), publishes_in_window=len(in_win),
             events_per_publish=(float(np.mean([r["events"] for r in in_win]))
                                 if in_win else None),
             items_per_publish=(float(np.mean([r.get("items", 0)
                                               for r in in_win]))
                                if in_win else None),
             publish_modes=sorted(collections.Counter(
                 r.get("mode") for r in recs).items()),
             compactions_in_window=len(compactions),
             segment_rows_max=max([r.get("segment_rows", 0) for r in in_win]
                                  or [0]),
             widest_fold=rep.widest if rep else None,
             waiting=rep.waiting if rep else None,
             h2d_bytes_in_window=sent, updater_drain_s=drain_s,
             replay_s=replay_s, read_your_writes_s=ryw_s, gram_s=gram_s,
             yty_full_in_window=full_runs,
             phase_ms={key: (float(np.median([r["spans"][key]
                                              for r in in_win])) * 1e3
                             if in_win else None)
                       for key in ("queue_wait", "foldin", "publish")},
             freshness_ms=(None if fresh is None or not len(fresh) else
                           {q: float(np.percentile(fresh, q))
                            for q in (50, 90, 99, 100)}))

    t0 = time.perf_counter()
    said = {}
    if rep is None:
        checks = [at_least("batches_in_the_records", 0, 1)]
    elif len(loop.answered()):
        touched = set(replayed[1][0].tolist())
        checks, said = window_checks(loop, U, rep, replayed[2], mix,
                                     cell.seed, touched, cfg["correct"], k)
    else:
        checks = [at_least("answered_requests", 0, 1)]
    cell.say("reference", seconds=time.perf_counter() - t0,
             requests=mix["check_requests"],
             users=mix["events"]["check_users"],
             items=mix["events"]["check_items"],
             fold_row_rel_err={
                 side: {q: float(np.percentile(errs, q))
                        for q in (50, 90, 100)}
                 for side, errs in zip(("user", "item"), rep.fold_err)
                 if errs} if rep else None,
             **yty_said, **said)
    folded = obs.counter_value("foldin.ratings") - folded0
    sampled = obs.histogram_count("live.freshness_seconds") - sampled0
    checks += [
        at_most("events_shed", shed, 0),
        # one per rating and side that entered a fold, as the replay counts
        at_most("events_folded_off_by",
                abs(folded - (rep.entered if rep else -1)), 0),
        at_most("events_admitted_without_freshness",
                abs(admitted - sampled), 0),
        at_most("events_admitted_not_in_a_publish",
                abs(admitted - sum(r.get("events", 0) for r in recs)), 0),
    ] + (fold_checks(rep, cfg["correct"]) if rep else []) + ryw_checks
    checks += yty_checks
    checks.append(at_most("compilations_in_window",
                          in_window["compilations"], 0))
    metrics = {"setup_s": setup_s}
    if len(lat):
        for q in (50, 90, 95):
            metrics[f"serve_p{q}_ms"] = float(np.percentile(lat, q))
    traced = streams[1][0] if cell.trace else None
    return Outcome(
        metrics=metrics, attempted=attempted, failed=failed, checks=checks,
        counters={"queue_ms": queue, "late_ms": late, "latency_ms": lat,
                  "batches": traced.batches(head_too=True) if traced
                  else None,
                  "freshness_ms": fresh,
                  "publish_h2d_bytes": sent["live.publish_h2d_bytes"],
                  "catalog_h2d_bytes": sent["live.catalog_h2d_bytes"],
                  "publishes": len(in_win),
                  "yty_full_in_window": full_runs},
        trace_dir=trace_dir,
        artifacts={"loop": loop, "U": U, "V": V, "model": model,
                   "streams": streams, "updater": updater, "tap": tap,
                   "server": server, "replay": rep, "read_your_writes": ryw,
                   "yty_full_in_window": full_runs})
