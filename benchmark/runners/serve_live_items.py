"""``kind: serve_live_items`` — ``serve_live``'s two streams, parameter for
parameter, against a ``LiveUpdater`` that folds the ITEM side of every batch
too: each event re-folds its user AND its item, a share of the events
(``new_item_share``) rate an item the catalog does not hold, so the catalog
moves under the requests all through the run.

Everything the sibling already does is the sibling's (imported, nothing of it
changed): ``serve.start_engine``, ``serve.open_stream``, ``serve_live``'s
``start_live`` (which reads ``fold_items`` from the configuration),
``EventStream``'s pacing, ``freshness_ms``, ``untouched_sample``,
``serve.compare_answers``.  New here: the new-item draw (after all of the
sibling's draws, so a seed's users, items and stars are the sibling's), the
generation of every answer (``Ticket.seq``), a tap on the updater's
``publish_update`` calls (:class:`PublishTap`: the rows each generation
published), and ``correct`` against the REPLAY
(``reference/foldin_replay.py``): with the catalog moving no answer can be
held to one catalog.

``correct``, outside the window: (a) EVERY fold of the run: the row the
program published against the float64 fold of the ratings the rule gives
that entity, over the other side's rows as the program had published them
(the replay follows the program's rows from fold to fold — folds chain and
the chain amplifies, see the reference — so an error is one fold's): median
and largest by side, no fold the rule asks for without a published row and
none unasked; (b) on a seeded sample of in-window answers by vector or by id
of a user no event touched, each against the catalog OF THE GENERATION THAT
ANSWERED IT (its published rows, in float64): every returned score against
the dot product with that generation's row of its id, order, recall@k
against that generation's exact top-k, ids inside that generation's
catalog; (c) the sibling's event accounting, with the program's count of
folded ratings against the replay's (one per rating and side that entered a
fold); (d) after the drain, read-your-writes for a seeded sample of touched
USERS (one request by id each, against its last published row and the
exact top-k over the final catalog) and of touched ITEMS, new ones among
them: the rows the index serves for them read back
(``published_index.rows``) against the last published, and one request each
by a vector that points at the item (its row at a user's length) against
the exact top-k over the final catalog; the catalog's size; (e) no
compilation in the window.  Every wait has a limit, so the run ends on any
program: one without ``Ticket.seq`` or ``rows`` is not ``correct`` and says
why.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from tpu_als import obs

from benchmark import datagen
from benchmark.harness import Check, Outcome, at_least, at_most
from benchmark.reference import foldin_replay as ref_replay
from benchmark.reference import topk as ref_topk
from benchmark.runners import serve, serve_live


class SeqLoop(serve.OpenLoop):
    """``OpenLoop`` that also keeps which generation answered each request
    (``Ticket.seq``; -1 where the program stamps none)."""

    def __init__(self, loop):
        super().__init__(loop.engine, loop.payloads, loop.due,
                         loop.answer_timeout_s, loop.scores.shape[1],
                         head=loop.head, at_head=loop.at_head)
        self.seq = np.full(self.n_all, -1, np.int64)

    def _wait(self):
        # ``OpenLoop._wait`` (which lets each ticket go as it is answered
        # and cannot be edited here) with the one line that keeps ``seq``
        import jax

        for _ in range(self.n_all):
            self._more.acquire()
            j, ticket = self._handed.popleft()
            if ticket is None:
                continue
            try:
                with jax.profiler.TraceAnnotation("bench.wait_answer"):
                    scores, ids = ticket.result(timeout=self.answer_timeout_s)
                self.t_done[j] = time.perf_counter()
                self.scores[j, :len(scores)] = scores
                self.ids[j, :len(ids)] = ids
                self.t_queued[j] = ticket.t_submit
                self.t_dequeue[j] = ticket.t_dequeue
                seq = getattr(ticket, "seq", None)
                self.seq[j] = -1 if seq is None else seq
            except Exception as e:   # noqa: BLE001 — every failure is counted
                self.errors[j] = type(e).__name__


class PublishTap:
    """Stands between the updater and the engine: every ``publish_update``
    goes through unchanged, and the rows of ``U`` and ``V`` it named, as the
    updater's host tables held them, are kept with the seq the engine gave
    the publish (copied after the call, ~10 rows).  Everything else is the
    engine's."""

    def __init__(self, engine):
        self._engine = engine
        self.log = {}       # seq -> (user rows, U[rows], item rows, V[rows])

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def publish_update(self, U, V, *, touched_items=None, touched_users=None,
                       **kw):
        out = self._engine.publish_update(
            U, V, touched_items=touched_items, touched_users=touched_users,
            **kw)
        tu, ti = (np.empty(0, np.int64) if t is None
                  else np.unique(np.asarray(t, np.int64))
                  for t in (touched_users, touched_items))
        seq = out[0] if isinstance(out, tuple) else out
        self.log[int(seq)] = (tu, np.array(U[tu]), ti, np.array(V[ti]))
        return out


class ItemEventStream(serve_live.EventStream):
    """The sibling's stream with ``new_item_share`` of the events, drawn
    among those of users the model held at start, rating an item the
    catalog does not hold: ids ``first_new_item``, + 1, ... in arrival
    order."""

    def __init__(self, updater, loop, rng, config, mix, seconds, first_new,
                 first_new_item):
        super().__init__(updater, loop, rng, config, mix, seconds, first_new)
        ev = mix["events"]
        self.is_new_item = ~self.is_new & (
            rng.random(self.n)
            < ev["new_item_share"] / (1.0 - ev["new_user_share"]))
        self.item[self.is_new_item] = first_new_item + np.arange(
            self.is_new_item.sum())


def open_streams(engine, updater, U, cfg, mix, rng, ev_rng, seconds, k,
                 first_new, first_new_item, clock=None):
    loop, marks = serve.open_stream(engine, U, mix, rng, seconds, k,
                                    clock=clock)
    loop = SeqLoop(loop)
    events = ItemEventStream(updater, loop, ev_rng, cfg, mix, seconds,
                             first_new, first_new_item)
    return loop, marks, events


def published_rows(tap, model, recs):
    """``[({user id: row}, {item id: row})]``, one per batch of ``recs``:
    what the tap saw that batch's publish carry."""
    out = []
    for r in recs:
        none = np.empty(0, np.int64)
        tu, Ur, ti, Vr = tap.log.get(r["seq"], (none, (), none, ()))
        out.append((dict(zip(model._user_map.to_original(tu).tolist(), Ur)),
                    dict(zip(model._item_map.to_original(ti).tolist(), Vr))))
    return out


def replay_of(streams, updater, tap, model, U, V, config,
              operand_dtype=None):
    """(the replay of every admitted event in the updater's batches, each
    fold from the rows the program had published; the events' users / items
    / stars; the records' publish seqs) — or ``None`` where the program's
    records do not give the batches.  ``operand_dtype``: the CONTROL in the
    program's place — what a replay with operands of that precision would
    have published, held to the float64 folds the same way."""
    recs = [r for r in updater.flight.records() if r.get("status") == "ok"]
    users, items, stars = (np.concatenate(
        [getattr(ev, name)[ev.admitted] for _, ev in streams])
        for name in ("user", "item", "stars"))
    if (any("events" not in r or "seq" not in r for r in recs)
            or sum(r["events"] for r in recs) != len(users)):
        return None
    sizes = [r["events"] for r in recs]
    rule = dict(fold_items=config["live"]["fold_items"])
    reg = config["als"]["regParam"]
    if operand_dtype is None:
        published = published_rows(tap, model, recs)
    else:
        published = ref_replay.published_of(ref_replay.replay(
            U, V, users, items, stars, sizes, reg,
            operand_dtype=operand_dtype, **rule), len(sizes))
    rep = ref_replay.replay(U, V, users, items, stars, sizes, reg,
                            published=published, **rule)
    return rep, (users, items, stars), np.array([r["seq"] for r in recs])


def fold_checks(rep, lim):
    """(a): every fold of the run, the published row against the float64
    fold of the same ratings over the same published rows."""
    checks = [at_most("folds_without_a_published_row", rep.missing, 0),
              at_most("rows_published_without_a_fold", rep.unasked, 0)]
    for side, errs in zip(("user", "item"), rep.fold_err):
        if not errs:       # a side that folds nothing is compared on nothing
            continue
        for what, value in (("median", np.median(errs)), ("max", max(errs))):
            name = f"fold_{side}_row_rel_err_{what}"
            checks.append(at_most(name, float(value), lim[name]))
    return checks


def window_checks(loop, U, rep, seqs, mix, seed, touched_users, lim, k):
    """(a): the sampled in-window answers, each against its generation."""
    sample, Q = serve_live.untouched_sample(loop, U, mix, seed, touched_users)
    stamped = loop.seq[sample] >= 0
    checks = [at_least("untouched_requests_compared", len(sample),
                       mix["check_requests"]),
              at_least("answers_with_a_generation", float(stamped.all()),
                       1.0)]
    if not stamped.any():
        return checks, {}
    sample, Q = sample[stamped], Q[stamped]
    # a ticket's seq is a publish seq; the generation is how many of the
    # updater's batches had been published by then
    gens = np.searchsorted(seqs, loop.seq[sample], side="right")
    scores = loop.scores[sample].astype(np.float64)
    ids = loop.ids[sample]
    ref_s, ref_i, sizes = ref_replay.generation_topk(Q, gens, rep, k)
    largest = float(np.abs(ref_s).max())
    own = ref_replay.own_scores(Q, gens, ids, rep)
    inside = (ids >= 0) & (ids < sizes[:, None])
    score_err = float(np.nanmax(np.abs(scores - own))) / largest
    unsorted = float(np.maximum(np.diff(scores, axis=1), 0).max()) / largest
    checks += [
        at_most("score_rel_err", score_err, lim["score_rel_err"]),
        at_most("scores_ascending_by", unsorted, lim["score_rel_err"]),
        at_least("recall_at_k", ref_topk.recall(ids, ref_i),
                 lim["recall_at_k"]),
        at_least("ids_in_catalog", float(inside.all()), 1.0)]
    return checks, {"generations": int(len(np.unique(gens))),
                    "first": int(gens.min()), "last": int(gens.max())}


def read_your_writes(engine, model, rep, config, mix, seed):
    """The checks of (d), and what they compared."""
    k, lim = config["serving"]["k"], config["correct"]
    ev = mix["events"]
    Vf = rep.final_catalog()
    rng = datagen.rng_for(seed, 6)
    users = rng.permutation(sorted(rep.user_rows))[:ev["check_users"]]
    moved = rep.moved_items()
    new = moved[moved >= len(rep.V0)]
    # new items among them: half of the sample where there are as many
    items = np.concatenate([
        rng.permutation(new)[:ev["check_items"] // 2],
        rng.permutation(moved[moved < len(rep.V0)])])[:ev["check_items"]]
    X = np.stack([rep.user_rows[u] for u in users.tolist()])
    Y = np.stack([rep.item_rows[i] for i in items.tolist()])
    # a query that points at the item, at a user's length
    P = Y / np.linalg.norm(Y, axis=1, keepdims=True) * np.sqrt(Y.shape[1])
    du = model._user_map.to_dense(users)
    di = model._item_map.to_dense(items)
    tickets = [engine.submit(int(d)) if d >= 0 else None for d in du]
    tickets += [engine.submit(p.astype(np.float32)) for p in P]
    scores = np.zeros((len(tickets), k), np.float64)
    ids = np.full((len(tickets), k), -1, np.int64)
    unanswered = 0
    for j, t in enumerate(tickets):
        try:
            s, i = t.result(timeout=mix["answer_timeout_s"])
            scores[j, :len(s)], ids[j, :len(i)] = s, i
        except Exception:   # noqa: BLE001 — counted, and compared as -1
            unanswered += 1
    index = engine.published_index
    read = getattr(index, "rows", None)
    if read is not None and (di >= 0).all():
        rows, ok = read(di)
    else:                       # a program without the read-back
        rows, ok = np.zeros_like(Y), np.zeros(len(Y), bool)
    QP = np.concatenate([X, P])
    exact = ref_topk.exact_topk(QP, Vf, k)
    n = len(users)
    checks = []
    for name, part in (("foldin_", slice(0, n)), ("foldin_item_",
                                                  slice(n, None))):
        found = serve.compare_answers(
            scores[part], ids[part], QP[part], Vf, k,
            {"score_rel_err": lim[name + "score_rel_err"],
             "recall_at_k": lim[name + "recall_at_k"]},
            exact=tuple(x[part] for x in exact))
        checks += [Check(name + c.name, c.value, c.limit, c.holds)
                   for c in found if c.name != "scores_ascending_by"]
    # the index serves the rows the last publish of each item carried
    row_err = np.linalg.norm(rows - Y, axis=1) / np.linalg.norm(Y, axis=1)
    n_items = index.n_items if index is not None else -1
    checks += [
        at_most("foldin_item_row_rel_err_max", float(row_err.max()),
                lim["foldin_item_row_rel_err_max"]),
        at_least("foldin_item_rows_served", float(ok.all()), 1.0),
        at_most("foldin_unanswered", unanswered, 0),
        at_most("catalog_size_off_by", abs(n_items - len(Vf)), 0)]
    return checks, {"users": users, "items": items, "scores": scores,
                    "ids": ids, "rows": rows}


def run(cell):
    import jax

    cfg, mix = cell.config, cell.traffic
    k = cfg["serving"]["k"]
    t_start = time.perf_counter()
    engine, U, V, phases = serve.start_engine(cfg, mix, cell.seed)
    tap = PublishTap(engine)
    model, server, updater, live_phases = serve_live.start_live(
        tap, U, V, cfg)
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
        loop.run()
        in_window = cell.clock.since(marks["compile"])
        events.join(mix["answer_timeout_s"])
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
    finally:
        updater.stop(drain_timeout_s=1.0)
        engine.stop()

    lat, late, queue = loop.latency_ms(), loop.late_ms(), loop.queue_ms()
    recs = [r for r in updater.flight.records() if r.get("status") == "ok"]
    in_win = [r for r in recs if t_open <= r.get("t_done", -1.0) < t_close]
    fresh = serve_live.freshness_ms(updater, events)
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
             replay_s=replay_s, read_your_writes_s=ryw_s,
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
             **said)
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
                  "publishes": len(in_win)},
        trace_dir=trace_dir,
        artifacts={"loop": loop, "U": U, "V": V, "model": model,
                   "streams": streams, "updater": updater, "tap": tap,
                   "replay": rep, "read_your_writes": ryw})
