"""``kind: serve_live`` — ``serve``'s open loop against ``ServingEngine``
while ``LiveUpdater`` folds a stream of rating events into the user factors.

The request side is ``runners/serve.py``'s own (imported, nothing of it
changed): seeded factors, ``publish``, ``warmup``, warm batches, one Poisson
stream of a head and a window.  Beside it the package's live path, wired as
its users wire it: ``ALSModel`` over the same factors, ``FoldInServer``
(``prewarm``ed), ``LiveUpdater`` at its default cadence.  A generator thread
of its own hands each event to ``LiveUpdater.submit`` when it is due, from
the head's first instant to the window's end; the count is fixed and the
seed moves ids and gaps.  Existing users are drawn from the stream's own
requests by id — the requests' zipf under the requests' relabelling: the hot
clients both ask and rate — new users take the next ids in arrival order,
items are zipf under their own relabelling, stars come from the
configuration's histogram.  After the window ``LiveUpdater.stop()`` drains.
Every wait has a limit, so the run ends on any program.

``correct``, outside the window: (a) ``serve``'s four checks on a seeded
sample of answered requests by vector or by ids of users NO event touched
(V never changes, so their float64 answer is known whichever generation
answered); (b) admitted events = freshness samples = ratings the program
reports folded, exactly; (c) read-your-writes: for a seeded sample of touched
users, new ones among them, one request by id each against the float64 fold
of ALL that user's events (``reference/foldin.py``) and its exact top-k;
(d) no compilation in the window, ids in the catalog.  ``attempted`` and
``failed`` count requests and events alike: a shed event is a failure.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

from tpu_als import ALSModel, FoldInServer, IdMap, LiveUpdater, obs

from benchmark import datagen
from benchmark.harness import Check, Outcome, at_least, at_most
from benchmark.reference import foldin as ref_foldin
from benchmark.runners import serve


def start_live(engine, U, V, config):
    """(model, fold-in server, updater not yet started, seconds by phase):
    the package's live path over the engine's factors."""
    stamps = [time.perf_counter()]

    def lap():
        stamps.append(time.perf_counter())
        return stamps[-1] - stamps[-2]

    als, live = config["als"], config["live"]
    model = ALSModel(
        als["rank"], IdMap(ids=np.arange(config["num_users"])),
        IdMap(ids=np.arange(config["num_items"])), U, V,
        {"userCol": "user", "itemCol": "item", "ratingCol": "rating",
         "regParam": als["regParam"], "implicitPrefs": als["implicitPrefs"],
         "alpha": 1.0, "nonnegative": als["nonnegative"]})
    server = FoldInServer(model, keep_history=live["keep_history"])
    phases = {"foldin_server_s": lap()}
    server.prewarm()
    phases["foldin_prewarm_s"] = lap()
    # every batch's record is kept, for the freshness of each event
    updater = LiveUpdater(
        engine, server, max_queue=live["max_queue"],
        max_batch=live["max_batch"], max_wait_ms=live["max_wait_ms"],
        fold_items=live["fold_items"], flight_capacity=1 << 16)
    return model, server, updater, phases


class EventStream:
    """``n`` rating events due at ``due`` seconds from the request loop's
    start, handed to ``updater.submit`` by a thread of its own."""

    def __init__(self, updater, loop, rng, config, mix, seconds, first_new):
        ev = mix["events"]
        span = mix["warmup_seconds"] + seconds
        self.due = datagen.poisson_arrivals(rng, ev["rate_per_s"], span)
        n = len(self.due)
        asked = np.array([p for p in loop.payloads if isinstance(p, int)])
        self.is_new = rng.random(n) < ev["new_user_share"]
        self.user = asked[rng.integers(0, len(asked), n)]
        self.user[self.is_new] = first_new + np.arange(self.is_new.sum())
        n_items = config["num_items"]
        self.item = rng.permutation(n_items)[rng.choice(
            n_items, size=n, p=datagen.zipf_weights(n_items,
                                                    ev["item_zipf_s"]))]
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

    @property
    def n(self):
        return len(self.due)

    def start(self):
        self._thread.start()
        return self

    def join(self, timeout):
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def _drive(self):
        import jax

        while getattr(self.loop, "t0", None) is None:   # the loop's start
            time.sleep(0.0005)
        t0 = self.loop.t0
        for j in range(self.n):
            wait = t0 + self.due[j] - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.wait_event"):
                    time.sleep(wait)
            try:
                with jax.profiler.TraceAnnotation("bench.submit_event"):
                    self.t_submit[j] = time.perf_counter()
                    self.updater.submit(int(self.user[j]), int(self.item[j]),
                                        float(self.stars[j]))
                self.admitted[j] = True
            except Exception as e:   # noqa: BLE001 — Overloaded is a shed
                self.errors[j] = type(e).__name__

    def by_user(self):
        """``{user id: (items, stars)}`` of the admitted events, each
        user's in arrival order."""
        out = {}
        for j in np.flatnonzero(self.admitted):
            items, stars = out.setdefault(int(self.user[j]), ([], []))
            items.append(int(self.item[j]))
            stars.append(float(self.stars[j]))
        return out


def freshness_ms(updater, events):
    """ms from each window event handed to ``submit`` to its batch's
    publish done.  The updater's per-batch records give ``events`` (how
    many admitted events the batch made visible, in admission order) and
    ``t_done``; ``None`` where the program's records hold neither."""
    recs = [r for r in updater.flight.records() if r.get("status") == "ok"]
    if not recs or any("t_done" not in r or "events" not in r for r in recs):
        return None
    done = np.repeat([r["t_done"] for r in recs], [r["events"] for r in recs])
    order = np.flatnonzero(events.admitted)
    if len(done) < len(order):     # records lost: the counts say so too
        order = order[:len(done)]
    fresh = (done[:len(order)] - events.t_submit[order]) * 1e3
    return fresh[order >= events.head]


def untouched_sample(loop, U, mix, seed, touched):
    """``serve.sampled_queries`` over the answered requests that are by
    vector or by the id of a user no event touched."""
    order = datagen.rng_for(seed, 3).permutation(loop.answered())
    keep = [j for j in order if not (isinstance(loop.payloads[j], int)
                                     and loop.payloads[j] in touched)]
    client = np.array([p if isinstance(p, int) else -1 - j
                       for j, p in ((j, loop.payloads[j]) for j in keep)])
    _, first = np.unique(client, return_index=True)
    sample = np.asarray(keep)[np.sort(first)[:mix["check_requests"]]]
    Q = np.stack([U[p] if isinstance(p, int) else p
                  for p in (loop.payloads[j] for j in sample)])
    return sample, Q


def read_your_writes(engine, model, by_user, V, config, mix, seed,
                     operand_dtype=None, answers=None):
    """The checks of (c), and the answers they compared.  One request by id
    for each of a seeded sample of touched users against the float64 fold
    of all that user's events.  ``operand_dtype`` puts the REFERENCE one
    precision step down in the program's place (the control):
    ``answers`` then takes the sample and float64 top-k already computed."""
    k, lim = config["serving"]["k"], config["correct"]
    if answers is None:
        users = datagen.rng_for(seed, 6).permutation(sorted(by_user))[
            :mix["events"]["check_users"]]
        dense = model._user_map.to_dense(users)
        tickets = [engine.submit(int(d)) if d >= 0 else None for d in dense]
        scores = np.zeros((len(users), k), np.float64)
        ids = np.full((len(users), k), -1, np.int64)
        unanswered = 0
        for j, t in enumerate(tickets):
            try:
                s, i = t.result(timeout=mix["answer_timeout_s"])
                scores[j, :len(s)], ids[j, :len(i)] = s, i
            except Exception:   # noqa: BLE001 — counted, and compared as -1
                unanswered += 1
        X = ref_foldin.fold_users(V, by_user, users, config["als"]["regParam"])
        answers = {"users": users, "X": X, "scores": scores, "ids": ids,
                   "unanswered": unanswered,
                   "exact": serve.ref.exact_topk(X, V, k)}
    else:
        users, X = answers["users"], answers["X"]
        Xl = ref_foldin.fold_users(V, by_user, users,
                                   config["als"]["regParam"],
                                   operand_dtype=operand_dtype)
        scores, ids = serve.ref.exact_topk(Xl, V, k)
        answers = dict(answers, scores=scores, ids=ids, unanswered=0)
    found = serve.compare_answers(
        answers["scores"], answers["ids"], X, V, k,
        {"score_rel_err": lim["foldin_score_rel_err"],
         "recall_at_k": lim["foldin_recall_at_k"]}, exact=answers["exact"])
    checks = [Check("foldin_" + c.name, c.value, c.limit, c.holds)
              for c in found if c.name != "scores_ascending_by"]
    checks.append(at_most("foldin_unanswered", answers["unanswered"], 0))
    return checks, answers


def run(cell):
    import jax

    cfg, mix = cell.config, cell.traffic
    k = cfg["serving"]["k"]
    t_start = time.perf_counter()
    engine, U, V, phases = serve.start_engine(cfg, mix, cell.seed)
    model, server, updater, live_phases = start_live(engine, U, V, cfg)
    phases.update(live_phases)
    cell.say("engine_ready", **phases, k=engine.k,
             users=cfg["num_users"], items=cfg["num_items"],
             rank=cfg["als"]["rank"])

    rng, ev_rng = datagen.rng_for(cell.seed, 2), datagen.rng_for(cell.seed, 5)
    streams = []        # [(loop, events)] — the window's, then the traced
    # the registry is the process's: a second run in it starts from these
    folded0 = obs.counter_value("foldin.ratings")
    sampled0 = obs.histogram_count("live.freshness_seconds")
    updater.start()
    try:
        loop, marks = serve.open_stream(engine, U, mix, rng, cell.seconds, k,
                                        clock=cell.clock)
        events = EventStream(updater, loop, ev_rng, cfg, mix, cell.seconds,
                             first_new=cfg["num_users"]).start()
        streams.append((loop, events))
        cell.say("ready", requests_s=time.perf_counter() - t_start
                 - sum(phases.values()), head=loop.head, events=events.n)
        sent0 = obs.counter_value("live.publish_h2d_bytes")
        loop.run()
        in_window = cell.clock.since(marks["compile"])
        events.join(mix["answer_timeout_s"])
        setup_s = loop.t0 + mix["warmup_seconds"] - cell.t_process
        t_open, t_close = loop.t0 + mix["warmup_seconds"], loop.t_last_submit
        sent = obs.counter_value("live.publish_h2d_bytes") - sent0
        trace_dir = None
        if cell.trace:
            from benchmark.trace import profiler_options

            traced, _ = serve.open_stream(engine, U, mix, rng,
                                          mix["trace_seconds"], k)
            traced_events = EventStream(
                updater, traced, ev_rng, cfg, mix, mix["trace_seconds"],
                first_new=cfg["num_users"] + int(events.is_new.sum()))
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

        by_user = {}
        for _, ev in streams:
            for user, (items, stars) in ev.by_user().items():
                have = by_user.setdefault(user, ([], []))
                have[0].extend(items)
                have[1].extend(stars)
        t0 = time.perf_counter()
        ryw_checks, ryw = read_your_writes(engine, model, by_user, V, cfg,
                                           mix, cell.seed)
        ryw_s = time.perf_counter() - t0
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
    cell.say("live", events_per_s=mix["events"]["rate_per_s"],
             events=events.n, in_window=events.n - events.head,
             admitted=admitted, shed=shed, errors=sorted(
                 collections.Counter(events.errors.values()).items()),
             users_touched=len(by_user),
             new_users=len(model._user_map) - cfg["num_users"],
             publishes=len(recs), publishes_in_window=len(in_win),
             events_per_publish=(float(np.mean([r["events"] for r in in_win]))
                                 if in_win else None),
             publish_modes=sorted(collections.Counter(
                 r.get("mode") for r in recs).items()),
             h2d_bytes_in_window=sent, updater_drain_s=drain_s,
             read_your_writes_s=ryw_s,
             phase_ms={key: (float(np.median([r["spans"][key]
                                              for r in in_win])) * 1e3
                             if in_win else None)
                       for key in ("queue_wait", "foldin", "publish")},
             freshness_ms=(None if fresh is None or not len(fresh) else
                           {q: float(np.percentile(fresh, q))
                            for q in (50, 90, 99, 100)}))

    t0 = time.perf_counter()
    if len(loop.answered()):
        sample, Q = untouched_sample(loop, U, mix, cell.seed, set(by_user))
        checks = serve.compare_answers(
            loop.scores[sample].astype(np.float64), loop.ids[sample], Q, V,
            k, cfg["correct"])
        checks.append(at_least("untouched_requests_compared", len(sample),
                               mix["check_requests"]))
    else:
        checks = [at_least("answered_requests", 0, 1)]
    cell.say("reference", seconds=time.perf_counter() - t0,
             requests=mix["check_requests"],
             users=mix["events"]["check_users"])
    folded = obs.counter_value("foldin.ratings") - folded0
    sampled = obs.histogram_count("live.freshness_seconds") - sampled0
    checks += [
        at_most("events_shed", shed, 0),
        at_most("events_admitted_not_folded", abs(admitted - folded), 0),
        at_most("events_admitted_without_freshness",
                abs(admitted - sampled), 0),
        at_most("events_admitted_not_in_a_publish",
                abs(admitted - sum(r.get("events", 0) for r in recs)), 0),
    ] + ryw_checks
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
                  "freshness_ms": fresh, "publish_h2d_bytes": sent,
                  "publishes": len(in_win)},
        trace_dir=trace_dir,
        artifacts={"loop": loop, "U": U, "V": V, "by_user": by_user,
                   "read_your_writes": ryw, "model": model})
