"""``kind: serve_live_unseen`` — ``serve_unseen``'s open loop (users asking in
proportion to what they have rated, the rule in every request) against a
``ServingEngine`` whose histories MOVE: beside the requests ``serve_live``'s
stream of rating events, each folded over its user's WHOLE history (the
resident ratings and the run's) and joined to that history in the publish
that makes it servable.

The package's live path wired as its users wire it, at its defaults:
``publish(U, V, user_seen=)`` of the resident histories, ``ALSModel`` +
``FoldInServer(base_history=)`` (``prewarm``ed) + ``LiveUpdater`` (whose
``start`` has the engine lay the histories out to grow and pin and run what
it will run), then the engine started.  The loop, the stream's pacing and the
clocks are the siblings' (imported, nothing of them changed):
``serve_unseen.open_stream`` / ``compare`` / ``Asker``,
``serve_live.EventStream``'s pacing and ``freshness_ms``,
``serve_live_items.SeqLoop`` (which generation answered each request) and
``PublishTap`` (the rows each generation published; here also the ids it
appended).  New here: the event draw (an event's user from the stream's own
by-id requests, its item redrawn while that user has already rated it) and
``correct`` against ``reference/live_unseen.py``.

``correct``, outside the window: (1) NO answered request of head and window
returned an id it was to exclude, each by-id request against its user's
history AS OF THE GENERATION THAT ANSWERED IT (resident + every event
published at or before ``Ticket.seq``), limit 0; (2) on a seeded sample of
distinct clients: recall@k against the float64 top-k of the ids left as of
that generation, the scores against float64 dot products with that
generation's row of the user; (3) counts: nothing shed, every admitted event
folded once, with a freshness sample and in a publish record; the pairs each
publish appended are its batch's events'; (4) EVERY fold of the run: the
row the program published against the float64 fold of all that user's
ratings as of that publish; (5) after the drain one request by id for each
of a seeded sample of touched users and for the touched users with the
longest histories: no rated id, the events' items among them (limit 0),
recall and scores as above; (6) no compilation in the window.  The mix's
``"appends": false`` (histories frozen at publish) and ``"fold_base":
false`` (the fold over the run's events alone) are the CONTROLS
(``benchmark/tests/chip_readings_live_unseen.py``): the same checks must
fail.  Every wait has a limit, so the run ends on any program.
"""

from __future__ import annotations

import collections
import inspect
import threading
import time

import numpy as np

from benchmark import datagen, histories
from benchmark.harness import BenchmarkError, Outcome, at_least, at_most
from benchmark.reference import live_unseen as ref
from benchmark.runners import serve, serve_live, serve_live_items, serve_unseen


def refuse_a_program_without_the_path():
    """``BenchmarkError`` before any set-up where the program cannot run the
    deployment at all (the parent of PR 42: ``publish_update`` refuses a
    generation that holds histories, ``FoldInServer`` knows no base)."""
    from tpu_als import FoldInServer
    from tpu_als.serving.engine import ServingEngine

    for fn, arg in ((ServingEngine.publish_update, "seen_appended"),
                    (FoldInServer.__init__, "base_history")):
        if arg not in inspect.signature(fn).parameters:
            raise BenchmarkError(
                f"this program's {fn.__qualname__} takes no {arg}: it cannot "
                "fold a rating over its user's resident history and append "
                "it to that history in the same publish")


class HistoryTap(serve_live_items.PublishTap):
    """``PublishTap`` that also keeps the ``(user rows, item ids)`` each
    publish appended to the histories; with ``appends`` off (the CONTROL)
    the engine is handed none: its histories stay as published."""

    def __init__(self, engine, appends=True):
        super().__init__(engine)
        self.appends, self.appended = appends, {}

    def publish_update(self, U, V, *, seen_appended=None, **kw):
        out = super().publish_update(
            U, V, **kw, **({"seen_appended": seen_appended}
                           if self.appends else {}))
        if seen_appended is not None:
            self.appended[int(out[0])] = tuple(
                np.array(a, np.int64) for a in seen_appended)
        return out


class HistoryEventStream(serve_live.EventStream):
    """``serve_live.EventStream`` (its pacing, its thread, its account)
    with this cell's draw: an existing user from the stream's own by-id
    requests, items zipf over a seeded relabelling and REDRAWN while the
    user has already rated them (``taken``: the pairs of the streams before
    this one), stars from the histogram."""

    def __init__(self, updater, loop, rng, config, mix, seconds, first_new,
                 hist, taken):
        ev = mix["events"]
        self.due = datagen.poisson_arrivals(rng, ev["rate_per_s"],
                                            mix["warmup_seconds"] + seconds)
        n = len(self.due)
        asked = np.array([p for p in loop.payloads if isinstance(p, int)])
        self.is_new = rng.random(n) < ev["new_user_share"]
        self.user = asked[rng.integers(0, len(asked), n)]
        self.user[self.is_new] = first_new + np.arange(self.is_new.sum())
        n_items = config["num_items"]
        relabel = rng.permutation(n_items)
        weights = datagen.zipf_weights(n_items, ev["item_zipf_s"])
        self.item = relabel[rng.choice(n_items, size=n, p=weights)]
        indptr, indices = hist
        for j in range(n):
            u = int(self.user[j])
            mine = (indices[indptr[u]:indptr[u + 1]]
                    if u < len(indptr) - 1 else indices[:0])
            while (int(self.item[j]) in mine
                   or (u, int(self.item[j])) in taken):
                self.item[j] = relabel[rng.choice(n_items, p=weights)]
            taken.add((u, int(self.item[j])))
        lo, hi = config["live"]["rating_range"]
        self.stars = rng.choice(np.arange(lo, hi + 1, dtype=np.float32),
                                size=n, p=config["live"]["star_shares"])
        self.updater, self.loop = updater, loop
        self.t_submit = np.full(n, np.nan)
        self.admitted = np.zeros(n, bool)
        self.errors = {}
        self.head = int(np.searchsorted(self.due, mix["warmup_seconds"]))
        self._thread = threading.Thread(target=self._drive,
                                        name="bench-events")


def start(config, mix, seed):
    """``(asker, tap, updater started, model, U, V, (indptr, indices,
    stars), seconds by phase)``: the deployment set up and warm."""
    from tpu_als import ALSModel, FoldInServer, IdMap, LiveUpdater
    from tpu_als.serving.engine import ServingEngine

    stamps = [time.perf_counter()]
    phases = {}

    def lap(name):
        stamps.append(time.perf_counter())
        phases[name] = stamps[-1] - stamps[-2]
        phases[name[:-2] + "_peak_gb"] = 1e-9 * serve_unseen.memory_peak_bytes()

    indptr, indices, stars = histories.seeded_histories(config, seed)
    lap("histories_s")
    _, V = serve.seeded_factors(1, config["num_items"],
                                config["als"]["rank"], seed)
    U = histories.planted_user_factors(indptr, indices, stars, V)
    lap("factors_s")
    engine = ServingEngine(k=config["serving"]["k"])
    engine.publish(U, V, user_seen=(indptr, indices))
    lap("publish_s")
    als, live = config["als"], config["live"]
    model = ALSModel(
        als["rank"], IdMap(ids=np.arange(config["num_users"])),
        IdMap(ids=np.arange(config["num_items"])), U, V,
        {"userCol": "user", "itemCol": "item", "ratingCol": "rating",
         "regParam": als["regParam"], "implicitPrefs": als["implicitPrefs"],
         "alpha": 1.0, "nonnegative": als["nonnegative"]})
    server = FoldInServer(
        model, keep_history=live["keep_history"],
        base_history=((indptr, indices, stars)
                      if mix.get("fold_base", True) else None))
    lap("foldin_server_s")
    server.prewarm()
    lap("foldin_prewarm_s")
    tap = HistoryTap(engine, appends=mix.get("appends", True))
    updater = LiveUpdater(
        tap, server, max_queue=live["max_queue"],
        max_batch=live["max_batch"], max_wait_ms=live["max_wait_ms"],
        fold_items=live["fold_items"], flight_capacity=1 << 16)
    # the row writes, the histories laid out to grow, every program that
    # excludes pinned and run: LiveUpdater.start has the engine do it
    updater.start()
    engine.start()
    lap("live_warmup_s")
    asker = serve_unseen.Asker(engine)
    rng = datagen.rng_for(seed, 4)
    for n in mix["warm_batches"]:
        tickets = [asker.submit(p) for p in serve_unseen.make_requests(
            rng, U, (indptr, indices), mix, n)[0]]
        for t in tickets:
            t.result(timeout=120.0)
    lap("warm_batches_s")
    return asker, tap, updater, model, U, V, (indptr, indices, stars), phases


def open_streams(asker, updater, U, hist, cfg, mix, rng, ev_rng, seconds, k,
                 first_new, taken, clock=None):
    loop, marks, users = serve_unseen.open_stream(
        asker, U, hist[:2], mix, rng, seconds, k, clock=clock)
    loop = serve_live_items.SeqLoop(loop)
    events = HistoryEventStream(updater, loop, ev_rng, cfg, mix, seconds,
                                first_new, hist[:2], taken)
    return loop, marks, users, events


def replayed(streams, updater, tap, model, hist):
    """``(ref.Histories with every admitted event published under its
    batch's seq, the records, pairs the program appended that are not its
    batch's events' or the other way round)`` — ``None`` first where the
    program's records do not give the batches."""
    recs = [r for r in updater.flight.records() if r.get("status") == "ok"]
    users, items, stars = (np.concatenate(
        [getattr(ev, name)[ev.admitted] for _, _, ev in streams])
        for name in ("user", "item", "stars"))
    if (any("events" not in r or "seq" not in r for r in recs)
            or sum(r["events"] for r in recs) != len(users)):
        return None, recs, len(users)
    rep, at, wrong = ref.Histories(*hist), 0, 0
    for r in recs:
        sl = slice(at, at + r["events"])
        at += r["events"]
        rep.publish(r["seq"], users[sl], items[sl], stars[sl])
        rows = model._user_map.to_dense(users[sl])
        said = tap.appended.get(r["seq"], (np.empty(0, np.int64),) * 2)
        wrong += len(set(zip(rows.tolist(), items[sl].tolist()))
                     ^ set(zip(*(a.tolist() for a in said))))
    return rep, recs, wrong


def rows_as_of(tap):
    """``{user row: [(seq, published row)]}`` by seq, from the tap."""
    out = {}
    for seq in sorted(tap.log):
        tu, Ur, _, _ = tap.log[seq]
        for u, x in zip(tu.tolist(), Ur):
            out.setdefault(u, []).append((seq, x))
    return out


def row_of(published, U, row, seq):
    """The factor row of user ``row`` as generation ``seq`` served it."""
    mine = [x for s, x in published.get(int(row), ()) if s <= seq]
    return mine[-1] if mine else U[row]


def seen_in_answers(loop, users, rep):
    """Over EVERY answered request of the stream, head and window:
    ``(served ids that were to be excluded, by-id requests served at least
    one, by-id requests answered)``, each by-id request against its user's
    history as of the generation that answered it."""
    pairs = with_seen = by_id = 0
    for j in loop.answered(head_too=True):
        real = loop.scores[j] > serve_unseen.NO_ANSWER_BELOW
        p = loop.payloads[j]
        mine = p[1] if isinstance(p, tuple) else rep.ids(
            int(users[j]), int(loop.seq[j]))
        hit = int(np.isin(loop.ids[j][real], mine).sum())
        pairs += hit
        if not isinstance(p, tuple):
            by_id += 1
            with_seen += hit > 0
    return pairs, int(with_seen), by_id


def fold_checks(tap, model, rep, V, config, fold=ref.fold):
    """(4): every row every publish carried against the float64 fold of
    ALL that user's ratings as of that publish; ``(checks, errors)``."""
    reg, errs, unasked = config["als"]["regParam"], [], 0
    for seq in sorted(tap.log):
        tu, Ur, _, _ = tap.log[seq]
        for row, x in zip(tu.tolist(), Ur):
            user = int(model._user_map.to_original([row])[0])
            if not any(s == seq for s, _, _ in rep.events.get(user, ())):
                unasked += 1            # a row no event of the batch asked for
                continue
            errs.append(ref.row_rel_err(x, fold(V, rep, user, reg, seq)))
    asked = len({(u, e[0]) for u, evs in rep.events.items() for e in evs})
    lim = config["correct"]["fold_row_rel_err_max"]
    return [
        at_most("folds_without_a_published_row", asked - len(errs), 0),
        at_most("rows_published_without_a_fold", unasked, 0),
        at_most("fold_row_rel_err_max", max(errs, default=np.inf), lim),
    ], errs


def ask_after_drain(asker, model, rep, mix, seed, k):
    """(5): one request by id for each of a seeded sample of touched users
    and for the touched users with the longest histories:
    ``(users, rows, scores, ids, seqs, unanswered, how many are the
    sample)``."""
    ev = mix["events"]
    touched = np.array(rep.touched())
    sample = datagen.rng_for(seed, 6).permutation(touched)[:ev["check_users"]]
    lengths = np.array([len(rep.ids(u)) for u in touched])
    longest = touched[np.argsort(-lengths, kind="stable")[:ev["check_longest"]]]
    users = np.concatenate([sample, longest])
    rows = model._user_map.to_dense(users)
    scores = np.full((len(users), k), -np.inf)
    ids = np.full((len(users), k), -1, np.int64)
    seqs = np.full(len(users), -1, np.int64)
    tickets = [asker.submit(int(r)) if r >= 0 else None for r in rows]
    unanswered = 0
    for j, t in enumerate(tickets):
        try:
            s, i = t.result(timeout=mix["answer_timeout_s"])
            scores[j, :len(s)], ids[j, :len(i)] = s, i
            seqs[j] = t.seq
        except Exception:   # noqa: BLE001 — counted, and compared as empty
            unanswered += 1
    return users, rows, scores, ids, seqs, unanswered, len(sample)


def answer_checks(loop, users, after, rep, tap, U, V, config, mix, seed):
    """(1), (2) and (5)."""
    if not len(loop.answered()):
        return [at_least("answered_requests", 0, 1)], {}
    k, lim = config["serving"]["k"], config["correct"]
    published = rows_as_of(tap)
    sample = serve_unseen.sampled(loop, users, mix, seed)
    Q, excluded = [], []
    for j in sample:
        p, seq = loop.payloads[j], int(loop.seq[j])
        if isinstance(p, tuple):
            Q.append(p[0])
            excluded.append(p[1])
        else:
            Q.append(row_of(published, U, p, seq))
            excluded.append(rep.ids(int(users[j]), seq))
    a_users, a_rows, a_scores, a_ids, a_seqs, unanswered, n_sample = after
    a_Q = [row_of(published, U, r, s) if r >= 0 else U[0]
           for r, s in zip(a_rows, a_seqs)]
    a_excluded = [rep.ids(int(u), int(s)) for u, s in zip(a_users, a_seqs)]
    # one pass over the catalog for all three sets of queries
    Qall = np.stack(Q + a_Q)
    ex_s, ex_i = ref.exact_topk_left(Qall, V, k, excluded + a_excluded)
    n, m = len(sample), len(sample) + n_sample
    checks = serve_unseen.compare(
        "", loop.scores[sample].astype(np.float64), loop.ids[sample],
        Qall[:n], V, excluded, k, lim, (ex_s[:n], ex_i[:n]))
    checks += serve_unseen.compare(
        "_after_drain", a_scores[:n_sample], a_ids[:n_sample], Qall[n:m], V,
        a_excluded[:n_sample], k, lim, (ex_s[n:m], ex_i[n:m]))
    # the few longest swing further: a limit of their own, as the sibling's
    checks += serve_unseen.compare(
        "_longest", a_scores[n_sample:], a_ids[n_sample:], Qall[m:], V,
        a_excluded[n_sample:], k,
        dict(lim, recall_at_k=lim["recall_at_k_longest"]),
        (ex_s[m:], ex_i[m:]))
    # read your writes, for the rule: what the run's events rated is gone
    # (the LAST generation's history: the drain published every event)
    back = sum(int(np.isin(i[s > serve_unseen.NO_ANSWER_BELOW],
                           [e[1] for e in rep.events[int(u)]]).sum())
               for u, s, i in zip(a_users, a_scores, a_ids))
    pairs, with_seen, by_id = seen_in_answers(loop, users, rep)
    checks += [
        at_most("after_drain_unanswered", unanswered, 0),
        at_most("rated_in_the_run_returned_after_drain", back,
                lim["seen_returned"]),
        at_most("seen_returned_all_answers", pairs, lim["seen_returned"]),
        at_least("answers_with_their_generation",
                 float((loop.seq[loop.answered(head_too=True)] > 0).all()),
                 1.0)]
    return checks, {"sample": sample, "Q": Qall[:n], "excluded": excluded,
                    "after_Q": Qall[n:], "after_excluded": a_excluded,
                    "after_sample": n_sample, "rated_back": back,
                    "by_id_with_seen_share": with_seen / max(by_id, 1)}


def run(cell):
    import jax

    from tpu_als import obs

    refuse_a_program_without_the_path()
    cfg, mix = cell.config, cell.traffic
    k = cfg["serving"]["k"]
    t_start = time.perf_counter()
    asker, tap, updater, model, U, V, hist, phases = start(cfg, mix,
                                                           cell.seed)
    cell.say("setup", process_to_runner_s=t_start - cell.t_process, **phases)
    rng, ev_rng = datagen.rng_for(cell.seed, 2), datagen.rng_for(cell.seed, 5)
    streams, taken, marks_at = [], set(), {}
    names = ("foldin.ratings", "live.publish_h2d_bytes",
             "live.history_h2d_bytes", "live.history_appended_ids",
             "live.history_relocations", "serving.exclusion_upload_bytes")

    def counters():
        return {n: obs.counter_value(n) or 0 for n in names}

    at_start = counters()
    sampled0 = obs.histogram_count("live.freshness_seconds")
    try:
        loop, marks, users, events = open_streams(
            asker, updater, U, hist, cfg, mix, rng, ev_rng, cell.seconds, k,
            cfg["num_users"], taken, clock=cell.clock)
        at_head = loop.at_head

        def window_opens():
            at_head()
            marks_at["head"] = counters()

        loop.at_head = window_opens
        events.start()
        streams.append((loop, users, events))
        cell.say("ready", requests_s=time.perf_counter() - t_start
                 - sum(v for p, v in phases.items() if p.endswith("_s")),
                 head=loop.head, events=events.n)
        loop.run()
        marks_at["end"] = counters()
        in_window = cell.clock.since(marks["compile"])
        events.join(mix["answer_timeout_s"])
        setup_s = loop.t0 + mix["warmup_seconds"] - cell.t_process
        t_open, t_close = loop.t0 + mix["warmup_seconds"], loop.t_last_submit
        trace_dir, traced = None, None
        if cell.trace:
            from benchmark.trace import profiler_options

            traced, _, t_users, t_events = open_streams(
                asker, updater, U, hist, cfg, mix, rng, ev_rng,
                mix["trace_seconds"], k,
                cfg["num_users"] + int(events.is_new.sum()), taken)
            streams.append((traced, t_users, t_events))
            trace_dir = cell.scratch("trace")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profiler_options())
            try:
                t_events.start()
                traced.run()
                t_events.join(mix["answer_timeout_s"])
            finally:
                jax.profiler.stop_trace()
        t0 = time.perf_counter()
        updater.stop(drain_timeout_s=mix["events"]["drain_timeout_s"])
        drain_s = time.perf_counter() - t0
        rep, recs, wrong_pairs = replayed(streams, updater, tap, model, hist)
        after = (None if rep is None or not rep.events else
                 ask_after_drain(asker, model, rep, mix, cell.seed, k))
    finally:
        updater.stop(drain_timeout_s=1.0)
        asker.stop()

    lat, late, queue = loop.latency_ms(), loop.late_ms(), loop.queue_ms()
    in_win = [r for r in recs if t_open <= r.get("t_done", -1.0) < t_close]
    fresh = serve_live.freshness_ms(updater, events)
    admitted = sum(int(ev.admitted.sum()) for _, _, ev in streams)
    shed = sum(ev.n for _, _, ev in streams) - admitted
    failed = (loop.n - len(loop.answered())
              + int((~events.admitted[events.head:]).sum()))
    attempted = loop.n + events.n - events.head
    for tr_loop, _, tr_events in streams[1:]:   # untimed, but a failure counts
        failed += (tr_loop.n - len(tr_loop.answered())
                   + int((~tr_events.admitted).sum()))
        attempted += tr_loop.n + tr_events.n
    moved = {n: marks_at["end"][n] - marks_at.get("head", at_start)[n]
             for n in names}
    lengths = np.diff(hist[0])[users[loop.head:]]
    cell.say("window", setup_s=setup_s, offered_per_s=mix["rate_per_s"],
             requests=loop.n, answered=len(lat), failed=loop.n - len(lat),
             errors=sorted(collections.Counter(
                 e for j, e in loop.errors.items() if j >= loop.head).items()),
             drain_s=loop.t_end - loop.t_last_submit,
             batches=loop.batches(), batch_sizes=loop.batch_sizes(),
             compile_in_window=in_window,
             history_ids={q: float(np.percentile(lengths, q))
                          for q in (10, 50, 90, 99, 100)},
             gc=loop.gc_clock.summary(), slowest=loop.slowest(),
             latency_ms={q: float(np.percentile(lat, q)) if len(lat) else None
                         for q in (50, 90, 95, 99, 99.9, 100)},
             late_ms={q: float(np.percentile(late, q)) for q in (50, 99, 100)})
    cell.say("live", events_per_s=mix["events"]["rate_per_s"],
             events=events.n, in_window=events.n - events.head,
             admitted=admitted, shed=shed, errors=sorted(
                 collections.Counter(events.errors.values()).items()),
             users_touched=0 if rep is None else len(rep.events),
             new_users=len(model._user_map) - cfg["num_users"],
             publishes=len(recs), publishes_in_window=len(in_win),
             events_per_publish=(float(np.mean([r["events"] for r in in_win]))
                                 if in_win else None),
             in_window_counters=moved, updater_drain_s=drain_s,
             busy_share_of_the_window=(
                 sum(r["spans"]["foldin"] + r["spans"]["publish"]
                     for r in in_win) / max(t_close - t_open, 1e-9)),
             phase_ms={key: (float(np.median([r["spans"][key]
                                              for r in in_win])) * 1e3
                             if in_win else None)
                       for key in ("queue_wait", "foldin", "publish")},
             fold_width={q: obs.histogram_quantile(
                 "foldin.history_width", q / 100, side="user")
                 for q in (50, 90, 100)},
             freshness_ms=(None if fresh is None or not len(fresh) else
                           {q: float(np.percentile(fresh, q))
                            for q in (50, 90, 99, 100)}))

    t0 = time.perf_counter()
    if rep is None or after is None:
        checks, found, errs = [at_least(
            "publish_records_that_give_the_batches", 0, 1)], {}, []
    else:
        checks, found = answer_checks(loop, users, after, rep, tap, U, V,
                                      cfg, mix, cell.seed)
        t1 = time.perf_counter()
        more, errs = fold_checks(tap, model, rep, V, cfg)
        checks += more
        cell.say("reference", seconds=time.perf_counter() - t0,
                 folds_s=time.perf_counter() - t1,
                 requests=mix["check_requests"], folds=len(errs),
                 fold_row_rel_err={q: float(np.percentile(errs, q))
                                   for q in (50, 99, 100)} if errs else None,
                 rated_back=found.get("rated_back"),
                 by_id_with_seen_share=found.get("by_id_with_seen_share"))
    folded = (obs.counter_value("foldin.ratings") or 0) \
        - at_start["foldin.ratings"]
    sampled = obs.histogram_count("live.freshness_seconds") - sampled0
    checks += [
        at_most("events_shed", shed, 0),
        at_most("events_admitted_not_folded", abs(admitted - folded), 0),
        at_most("events_admitted_without_freshness",
                abs(admitted - sampled), 0),
        at_most("events_admitted_not_in_a_publish",
                abs(admitted - sum(r.get("events", 0) for r in recs)), 0),
        at_most("appended_pairs_not_their_batchs_events", wrong_pairs, 0),
        at_most("compilations_in_window", in_window["compilations"], 0)]
    metrics = {"setup_s": setup_s}
    if len(lat):
        for q in (50, 90, 95):
            metrics[f"serve_p{q}_ms"] = float(np.percentile(lat, q))
    p90 = obs.histogram_quantile("serving.excluded_ids", 0.9,
                                 source="history")
    w90 = obs.histogram_quantile("foldin.history_width", 0.9, side="user")
    # what the traced seconds' folds HAD to read: their users' ratings
    t_recs = ([r for r in recs if streams[-1][0].t0 <= r.get("t_done", -1.0)]
              if traced is not None and rep is not None else [])
    t_rows = [(u, r["seq"]) for r in t_recs
              for u in model._user_map.to_original(
                  tap.log.get(r["seq"], (np.empty(0, np.int64),))[0]).tolist()]
    return Outcome(
        metrics=metrics, attempted=attempted, failed=failed, checks=checks,
        counters={"queue_ms": queue, "late_ms": late, "latency_ms": lat,
                  # the trace holds the traced stream's head too
                  "batches": traced.batches(head_too=True) if traced
                  else None,
                  "freshness_ms": fresh,
                  "publish_h2d_bytes": moved["live.publish_h2d_bytes"],
                  "history_h2d_bytes": moved["live.history_h2d_bytes"],
                  "publishes": len(in_win),
                  "fold_width_p90": None if np.isnan(w90) else w90,
                  "fold_rows_traced": len(t_rows) or None,
                  "fold_ratings_traced": sum(
                      len(rep.ids(int(u), s)) for u, s in t_rows) or None,
                  "excluded_ids_p90": None if np.isnan(p90) else p90,
                  "exclusion_upload_bytes":
                      moved["serving.exclusion_upload_bytes"],
                  "window_batches": loop.batches(),
                  "score_columns": cfg["num_items"],
                  "rank": cfg["als"]["rank"],
                  # what the traced stream's batches were to exclude (the
                  # resident ids: the least, the run's few are on top)
                  "excluded_ids_per_batch": None if not traced else sum(
                      len(serve_unseen.excluded_of(p, u, hist[:2]))
                      for p, u in zip(traced.payloads, streams[-1][1]))
                  / max(traced.batches(head_too=True), 1)},
        trace_dir=trace_dir,
        artifacts={"loop": loop, "users": users, "U": U, "V": V,
                   "hist": hist, "rep": rep, "tap": tap, "model": model,
                   "after": after, "fold_errs": errs, **found})
