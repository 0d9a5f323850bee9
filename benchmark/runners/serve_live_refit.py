"""``kind: serve_live_refit`` — ``serve_live_items``' two streams, parameter
for parameter, while finished REFITS are handed to the running updater: at
``land_at_s`` seconds into the window a whole new model generation lands
(``LiveUpdater.land``), both tables replaced, the events admitted since the
refit's snapshot (``LiveUpdater.mark``, ``snapshot_lag_s`` earlier) folded
onto it again, one generation swapped in while the engine answers.

Everything the siblings already do is theirs (imported, nothing of it
changed): ``serve.start_engine`` / ``seeded_factors`` / ``compare_answers``,
``serve_live.start_live`` / ``freshness_ms`` / ``untouched_sample``,
``serve_live_items.open_streams`` / ``PublishTap`` / ``fold_checks``.  New
here: the refits' tables (seeded draws of every row the tables can come to
hold, made inside ``setup_s``; the rows held at the snapshot are cut from
them), the thread that takes each snapshot and hands each refit over
(:class:`Lander`), and ``correct`` against the replay that knows of
landings (``reference/refit_replay.py``).

``correct``, outside the window: (a) every fold of the run AND every fold
of every catch-up: the row the program made against the float64 fold of the
ratings the rule gives that entity, over the other side's rows as the
program had them (the replay follows the program's rows); no fold the rule
asks for without a row and none unasked, batches and catch-ups apart;
(b) on a seeded sample of in-window answers by vector or by id of a user no
event touched, each against the catalog — and, by id, the user table — OF
THE GENERATION THAT ANSWERED IT (``Ticket.seq``), the landed ones among
them — recall, here and in (d), with a tie at the k-th place counted as one
place (``refit_replay.recall_by_score``: a catch-up folds every item with
the same one rater and stars over the same row, and they come out equal
bit for bit); (c) the sibling's event accounting (none shed; every admitted event
in exactly one batch's record; the ratings folded as the replay counts
them); the landings: as many as the mix asks for, no program compiled in
any, none in the window; (d) after the drain, the served tables read back:
rows of entities no event touched since the last snapshot against the last
refit's rows, bit for bit; read-your-writes for a seeded sample of touched
users and touched items, new ones among them, as the sibling's.  Every wait
has a limit, so the run ends on any program.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

from tpu_als import ALSModel, IdMap, LiveUpdater, obs

from benchmark import datagen
from benchmark.harness import (
    BenchmarkError,
    Check,
    Outcome,
    at_least,
    at_most,
)
from benchmark.reference import refit_replay as ref_replay
from benchmark.reference import topk as ref_topk
from benchmark.runners import serve, serve_live
from benchmark.runners import serve_live_items as items

# rows drawn beyond the start's counts, of which a refit's tables are cut:
# more than a run's new users or items (about 330 and 280)
SPARE_ROWS = 1024
# a landing's numbers the layer readers and the ``landings`` line take
SAID = ("seq", "snapshot", "users", "items", "catchup_events",
        "catchup_users", "catchup_items", "rounds", "calls", "programs",
        "placed_bytes", "copied_bytes", "bytes_in_use", "peak_bytes",
        "seconds", "t_start", "t_done")


def draw_refits(cfg, seed, n):
    """``[(U', V')]`` for ``n`` landings: the start's own draw
    (``serve.seeded_factors``: on the device, read back once) from (seed,
    landing number), ``SPARE_ROWS`` more rows than the start holds."""
    return [serve.seeded_factors(
        cfg["num_users"] + SPARE_ROWS, cfg["num_items"] + SPARE_ROWS,
        cfg["als"]["rank"], int(datagen.rng_for(seed, 7 + l).integers(
            1, 2 ** 31 - 1))) for l in range(n)]


class Lander:
    """Takes each refit's snapshot and hands each refit over, on a thread
    of its own: landing ``l`` at ``land_at[l]`` seconds from the request
    loop's start, its snapshot ``lag`` seconds before (never before the
    loop's start).  The refit is the rows the model held at the snapshot,
    cut from ``tables[l]``."""

    def __init__(self, updater, model, loop, tables, land_at, lag):
        self.updater, self.model, self.loop = updater, model, loop
        self.tables, self.land_at, self.lag = tables, land_at, lag
        self.refits, self.errors = [], []
        self._thread = threading.Thread(target=self._drive,
                                        name="bench-lander")

    def start(self):
        self._thread.start()
        return self

    def join(self, timeout):
        self._thread.join(timeout)
        return not self._thread.is_alive()

    @staticmethod
    def _until(t):
        wait = t - time.perf_counter()
        if wait > 0:
            time.sleep(wait)

    def _drive(self):
        while getattr(self.loop, "t0", None) is None:
            time.sleep(0.0005)
        t0, m = self.loop.t0, self.model
        for (U, V), at in zip(self.tables, self.land_at):
            self._until(t0 + max(0.0, at - self.lag))
            snapshot = self.updater.mark()
            nu, ni = len(m._user_map), len(m._item_map)
            self._until(t0 + at)
            refit = ALSModel(
                m.rank, IdMap(ids=m._user_map.ids[:nu].copy()),
                IdMap(ids=m._item_map.ids[:ni].copy()), U[:nu], V[:ni],
                dict(m._params))
            try:
                self.updater.land(refit, snapshot)
                self.refits.append((U[:nu], V[:ni]))
            except Exception as e:   # noqa: BLE001 — counted, and said
                self.errors.append(f"{type(e).__name__}: {e}")


def steps_of(updater, tap, model, refits):
    """``(steps, published, seqs)`` for the replay: the updater's batches
    and landings in the order of their publish seqs — or ``None`` where
    the records do not give them."""
    recs = [r for r in updater.flight.records() if r.get("status") == "ok"]
    landings = getattr(updater, "landings", [])
    if (any("events" not in r or "seq" not in r for r in recs)
            or len(landings) != len(refits)):
        return None
    none = np.empty(0, np.int64)
    steps = []
    for r in recs:
        tu, Ur, ti, Vr = tap.log.get(r["seq"], (none, (), none, ()))
        steps.append((r["seq"], r["events"], (
            dict(zip(model._user_map.to_original(tu).tolist(), Ur)),
            dict(zip(model._item_map.to_original(ti).tolist(), Vr)))))
    for rec, (U, V) in zip(landings, refits):
        caught = ({}, {})       # id -> its rows, a fold each, in order
        for side, (ids, rows) in zip(caught, (rec["catchup"]["users"],
                                              rec["catchup"]["items"])):
            for e, x in zip(ids.tolist(), rows):
                side.setdefault(e, []).append(x)
        steps.append((rec["seq"], {"snapshot": rec["snapshot"], "U": U,
                                   "V": V}, caught))
    steps.sort(key=lambda s: s[0])
    return ([s[1] for s in steps], [s[2] for s in steps],
            np.array([s[0] for s in steps]))


def admitted_events(streams):
    """``(users, items, stars)`` of every admitted event of the run's
    streams, in admission order."""
    return tuple(np.concatenate(
        [getattr(ev, name)[ev.admitted] for _, ev in streams])
        for name in ("user", "item", "stars"))


def replay_of(streams, updater, tap, model, U, V, refits, config,
              journal=None, **how):
    """(the replay of every admitted event through the updater's batches
    and landings, each fold from the rows the program had published; the
    events; the steps' publish seqs) — or ``None``.  ``journal``: a
    CONTROL's rows in the program's place (``control_journal``)."""
    found = steps_of(updater, tap, model, refits)
    users, its, stars = admitted_events(streams)
    if found is None or sum(s for s in found[0]
                            if not isinstance(s, dict)) != len(users):
        return None
    steps, published, seqs = found
    rep = ref_replay.replay(
        U, V, users, its, stars, steps, config["als"]["regParam"],
        fold_items=config["live"]["fold_items"],
        published=published if journal is None else journal, **how)
    return rep, (users, its, stars), seqs


def control_journal(streams, updater, tap, model, U, V, refits, config,
                    **how):
    """What a program that follows ``how`` (``operand_dtype``, ``catchup``)
    from the PROGRAM's own state would have published, step by step: each
    step's folds from the rows the program had published until then."""
    steps, published, _ = steps_of(updater, tap, model, refits)
    users, its, stars = admitted_events(streams)
    return ref_replay.replay(
        U, V, users, its, stars, steps, config["als"]["regParam"],
        fold_items=config["live"]["fold_items"], **how).journal


def catchup_checks(rep, lim):
    """(a), the landings' part: every catch-up fold held to the float64
    fold of the same ratings over the same rows."""
    checks = [at_most("catchup_folds_without_a_row", rep.catchup_missing, 0),
              at_most("catchup_rows_without_a_fold", rep.catchup_unasked,
                      0)]
    for side, errs in zip(("user", "item"), rep.catchup_err):
        if not errs:
            continue
        for what, value in (("median", np.median(errs)), ("max", max(errs))):
            name = f"catchup_{side}_row_rel_err_{what}"
            checks.append(at_most(name, float(value), lim[name]))
    return checks


def window_checks(loop, U, rep, seqs, mix, seed, touched_users, lim, k):
    """(b): the sampled in-window answers, each against its generation's
    catalog and, by id, its generation's user row."""
    sample, Q = serve_live.untouched_sample(loop, U, mix, seed,
                                            touched_users)
    stamped = loop.seq[sample] >= 0
    checks = [at_least("untouched_requests_compared", len(sample),
                       mix["check_requests"]),
              at_least("answers_with_a_generation", float(stamped.all()),
                       1.0)]
    if not stamped.any():
        return checks, {}
    sample, Q = sample[stamped], Q[stamped].copy()
    gens = np.searchsorted(seqs, loop.seq[sample], side="right")
    for j, (n, g) in enumerate(zip(sample.tolist(), gens.tolist())):
        if isinstance(loop.payloads[n], int):
            Q[j] = ref_replay.query_of(rep, g, loop.payloads[n])
    scores = loop.scores[sample].astype(np.float64)
    ids = loop.ids[sample]
    ref_s, ref_i, sizes = ref_replay.generation_topk(Q, gens, rep, k)
    largest = float(np.abs(ref_s).max())
    own = ref_replay.own_scores(Q, gens, ids, rep)
    inside = (ids >= 0) & (ids < sizes[:, None])
    score_err = float(np.nanmax(np.abs(scores - own))) / largest
    unsorted = float(np.maximum(np.diff(scores, axis=1), 0).max()) / largest
    eras = sorted({rep.eras.index(rep.era_of(int(g))) for g in gens})
    checks += [
        at_most("score_rel_err", score_err, lim["score_rel_err"]),
        at_most("scores_ascending_by", unsorted, lim["score_rel_err"]),
        at_least("recall_at_k",
                 ref_replay.recall_by_score(own, ids, ref_s, largest),
                 lim["recall_at_k"]),
        at_least("ids_in_catalog", float(inside.all()), 1.0),
        at_least("answers_scored_of_their_generation",
                 float(np.isfinite(own).all()), 1.0)]
    return checks, {"generations": int(len(np.unique(gens))),
                    "first": int(gens.min()), "last": int(gens.max()),
                    "eras_sampled": eras}


def served_tables(engine, model, rep, refits, config, mix, seed):
    """The checks of (d), and what they compared: the served tables read
    back after the drain."""
    k, lim = config["serving"]["k"], config["correct"]
    ev = mix["events"]
    rng = datagen.rng_for(seed, 6)
    index = engine.published_index
    read_items = getattr(index, "rows", None)
    read_users = getattr(engine, "user_rows", None)
    checks = []
    # rows no event touched since the last snapshot: the last refit's
    if refits:
        U2, V2 = refits[-1]
        quiet_u = rng.permutation(np.setdiff1d(
            np.arange(len(U2)), np.fromiter(rep.touched[0], np.int64,
                                            len(rep.touched[0]))))[:128]
        quiet_i = rng.permutation(np.setdiff1d(
            np.arange(len(V2)), np.fromiter(rep.touched[1], np.int64,
                                            len(rep.touched[1]))))[:128]
        du = model._user_map.to_dense(quiet_u)
        di = model._item_map.to_dense(quiet_i)
        got_u = (read_users(du) if read_users is not None
                 else np.zeros_like(U2[quiet_u]))
        got_i = (read_items(di)[0] if read_items is not None
                 else np.zeros_like(V2[quiet_i]))
        got, want = (np.concatenate(x) for x in (
            (got_u, got_i), (U2[quiet_u], V2[quiet_i])))
        err = (np.linalg.norm(got.astype(np.float64) - want, axis=1)
               / np.linalg.norm(want, axis=1))
        checks += [
            at_least("refit_rows_compared", len(got), 256),
            at_most("refit_row_rel_err_max", float(err.max()),
                    lim["refit_row_rel_err_max"]),
            at_most("refit_rows_not_bit_for_bit",
                    int((got != want).any(axis=1).sum()), 0)]
    # read-your-writes, as the sibling's: touched users and items
    Vf = rep.final_catalog()
    users = rng.permutation(sorted(
        u for u in rep.touched[0] if rep.row(0, u) is not None))[
            :ev["check_users"]]
    moved = np.array(sorted(i for i in rep.touched[1]
                            if rep.row(1, i) is not None), np.int64)
    new = moved[moved >= config["num_items"]]
    its = np.concatenate([
        rng.permutation(new)[:ev["check_items"] // 2],
        rng.permutation(moved[moved < config["num_items"]])])[
            :ev["check_items"]]
    X = np.stack([np.asarray(rep.row(0, u), np.float64)
                  for u in users.tolist()])
    Y = np.stack([np.asarray(rep.row(1, i), np.float64)
                  for i in its.tolist()])
    P = Y / np.linalg.norm(Y, axis=1, keepdims=True) * np.sqrt(Y.shape[1])
    du = model._user_map.to_dense(users)
    di = model._item_map.to_dense(its)
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
    if read_items is not None and (di >= 0).all():
        rows, ok = read_items(di)
    else:                       # a program without the read-back
        rows, ok = np.zeros_like(Y), np.zeros(len(Y), bool)
    QP = np.concatenate([X, P])
    exact = ref_topk.exact_topk(QP, Vf, k)
    n = len(users)
    for name, part in (("foldin_", slice(0, n)), ("foldin_item_",
                                                  slice(n, None))):
        found = serve.compare_answers(
            scores[part], ids[part], QP[part], Vf, k,
            {"score_rel_err": lim[name + "score_rel_err"],
             "recall_at_k": lim[name + "recall_at_k"]},
            exact=tuple(x[part] for x in exact))
        checks += [Check(name + c.name, c.value, c.limit, c.holds)
                   for c in found if c.name not in ("scores_ascending_by",
                                                    "recall_at_k")]
        # recall with a tie at the k-th place counted as one place: a
        # catch-up leaves rows that are equal bit for bit
        inside = np.clip(ids[part], 0, len(Vf) - 1)
        own = np.where(ids[part] >= 0,
                       ref_topk.own_scores(QP[part], Vf, inside), np.nan)
        checks.append(at_least(
            name + "recall_at_k", ref_replay.recall_by_score(
                own, ids[part], exact[0][part],
                float(np.abs(exact[0][part]).max())),
            lim[name + "recall_at_k"]))
    row_err = np.linalg.norm(rows - Y, axis=1) / np.linalg.norm(Y, axis=1)
    n_items = index.n_items if index is not None else -1
    checks += [
        at_most("foldin_item_row_rel_err_max", float(row_err.max()),
                lim["foldin_item_row_rel_err_max"]),
        at_least("foldin_item_rows_served", float(ok.all()), 1.0),
        at_most("foldin_unanswered", unanswered, 0),
        at_most("catalog_size_off_by", abs(n_items - len(Vf)), 0)]
    return checks, {"users": users, "items": its, "scores": scores,
                    "ids": ids, "rows": rows}


def run(cell):
    import jax

    cfg, mix = cell.config, cell.traffic
    if not hasattr(LiveUpdater, "land"):
        # a program before ISSUE 59: it cannot run this configuration, and
        # says so before any work (exit code 1, no result line)
        raise BenchmarkError(
            "the program has no LiveUpdater.land: it cannot run a "
            "configuration on which refits land")
    k = cfg["serving"]["k"]
    t_start = time.perf_counter()
    engine, U, V, phases = serve.start_engine(cfg, mix, cell.seed)
    t0 = time.perf_counter()
    tables = draw_refits(cfg, cell.seed,
                         len(mix["land_at_s"]) + int(cell.trace))
    phases["refit_draws_s"] = time.perf_counter() - t0
    tap = items.PublishTap(engine)
    model, server, updater, live_phases = serve_live.start_live(
        tap, U, V, cfg)
    phases.update(live_phases)
    t0 = time.perf_counter()
    server.prewarm(sides=("item",))
    phases["foldin_prewarm_items_s"] = time.perf_counter() - t0
    rng, ev_rng = datagen.rng_for(cell.seed, 2), datagen.rng_for(cell.seed, 5)
    streams, landers = [], []
    folded0 = obs.counter_value("foldin.ratings")
    sampled0 = obs.histogram_count("live.freshness_seconds")
    t0 = time.perf_counter()
    updater.refits = cfg["refit"]["lands"]   # start() warms a landing too
    updater.start()
    phases["updater_start_s"] = time.perf_counter() - t0
    cell.say("engine_ready", **phases, k=engine.k,
             users=cfg["num_users"], items=cfg["num_items"],
             rank=cfg["als"]["rank"])
    warm_s, lag = mix["warmup_seconds"], mix["snapshot_lag_s"]
    try:
        loop, marks, events = items.open_streams(
            engine, updater, U, cfg, mix, rng, ev_rng, cell.seconds, k,
            cfg["num_users"], cfg["num_items"], clock=cell.clock)
        n_window = len(mix["land_at_s"])
        landers.append(Lander(
            updater, model, loop, tables[:n_window],
            [warm_s + at for at in mix["land_at_s"]], lag).start())
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
        landers[0].join(mix["events"]["drain_timeout_s"])
        setup_s = loop.t0 + warm_s - cell.t_process
        t_open, t_close = loop.t0 + warm_s, loop.t_last_submit
        sent = {n: obs.counter_value(n) - v for n, v in sent0.items()}
        trace_dir = None
        if cell.trace:
            from benchmark.trace import profiler_options

            traced, _, traced_events = items.open_streams(
                engine, updater, U, cfg, mix, rng, ev_rng,
                mix["trace_seconds"], k,
                cfg["num_users"] + int(events.is_new.sum()),
                cfg["num_items"] + int(events.is_new_item.sum()))
            streams.append((traced, traced_events))
            trace_dir = cell.scratch("trace")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profiler_options())
            try:
                landers.append(Lander(
                    updater, model, traced, tables[n_window:],
                    [warm_s + mix["trace_land_at_s"]], lag).start())
                traced_events.start()
                traced.run()
                traced_events.join(mix["answer_timeout_s"])
                landers[1].join(mix["events"]["drain_timeout_s"])
            finally:
                jax.profiler.stop_trace()
        t0 = time.perf_counter()
        updater.stop(drain_timeout_s=mix["events"]["drain_timeout_s"])
        drain_s = time.perf_counter() - t0

        refits = [r for lander in landers for r in lander.refits]
        t0 = time.perf_counter()
        replayed = replay_of(streams, updater, tap, model, U, V, refits, cfg)
        replay_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ryw_checks, ryw = ([at_least("batches_in_the_records", 0, 1)], None)
        if replayed is not None:
            ryw_checks, ryw = served_tables(engine, model, replayed[0],
                                            refits, cfg, mix, cell.seed)
        ryw_s = time.perf_counter() - t0
    finally:
        updater.stop(drain_timeout_s=1.0)
        engine.stop()

    lat, late, queue = loop.latency_ms(), loop.late_ms(), loop.queue_ms()
    recs = [r for r in updater.flight.records() if r.get("status") == "ok"]
    in_win = [r for r in recs if t_open <= r.get("t_done", -1.0) < t_close]
    landings = [{key: rec[key] for key in SAID}
                for rec in getattr(updater, "landings", [])]
    landed = [rec for rec in landings
              if t_open <= rec["t_done"] < t_close]
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
    cell.say("live", events_per_s=mix["events"]["rate_per_s"],
             events=events.n, in_window=events.n - events.head,
             admitted=admitted, shed=shed, errors=sorted(
                 collections.Counter(events.errors.values()).items()),
             new_users=len(model._user_map) - cfg["num_users"],
             new_items=len(model._item_map) - cfg["num_items"],
             publishes=len(recs), publishes_in_window=len(in_win),
             events_per_publish=(float(np.mean([r["events"] for r in in_win]))
                                 if in_win else None),
             publish_modes=sorted(collections.Counter(
                 r.get("mode") for r in recs).items()),
             widest_fold=rep.widest if rep else None,
             h2d_bytes_in_window=sent, updater_drain_s=drain_s,
             replay_s=replay_s, served_tables_s=ryw_s,
             phase_ms={key: (float(np.median([r["spans"][key]
                                              for r in in_win])) * 1e3
                             if in_win else None)
                       for key in ("queue_wait", "foldin", "publish")},
             freshness_ms=(None if fresh is None or not len(fresh) else
                           {q: float(np.percentile(fresh, q))
                            for q in (50, 90, 99, 100)}))
    # a landing's latency, request by request: those submitted while one
    # was under way beside the rest of the window
    during = np.zeros(loop.n_all, bool)
    for rec in landed:
        during |= (loop.t_queued >= rec["t_start"]) & (
            loop.t_queued < rec["t_done"])
    done = np.isfinite(loop.t_done) & (np.arange(loop.n_all) >= loop.head)
    ms = (loop.t_done - loop.t_queued) * 1e3
    cell.say("landings", asked=len(mix["land_at_s"]), landed=len(landed),
             errors=[e for lander in landers for e in lander.errors],
             catchup=rep.catchup_sizes if rep else None,
             requests_during=int((during & done).sum()),
             latency_ms_during={
                 q: float(np.percentile(ms[during & done], q))
                 for q in (50, 90, 99, 100)} if (during & done).any()
             else None,
             latency_ms_beside={
                 q: float(np.percentile(ms[~during & done], q))
                 for q in (50, 90, 99, 100)} if (~during & done).any()
             else None,
             records=[{**{key: rec[key] for key in SAID
                          if key != "seconds"},
                       "ms": {key: round(1e3 * s, 3)
                              for key, s in rec["seconds"].items()}}
                      for rec in landings])

    t0 = time.perf_counter()
    said = {}
    if rep is None:
        checks = [at_least("batches_and_landings_in_the_records", 0, 1)]
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
                 kind + side: {q: float(np.percentile(errs, q))
                               for q in (50, 90, 100)}
                 for kind, both in (("fold_", rep.fold_err),
                                    ("catchup_", rep.catchup_err))
                 for side, errs in zip(("user", "item"), both)
                 if errs} if rep else None,
             **said)
    folded = obs.counter_value("foldin.ratings") - folded0
    sampled = obs.histogram_count("live.freshness_seconds") - sampled0
    asked = len(mix["land_at_s"]) + int(cell.trace)
    checks += [
        at_most("events_shed", shed, 0),
        at_most("events_folded_off_by",
                abs(folded - (rep.entered if rep else -1)), 0),
        at_most("events_admitted_without_freshness",
                abs(admitted - sampled), 0),
        at_most("events_admitted_not_in_a_publish",
                abs(admitted - sum(r.get("events", 0) for r in recs)), 0),
        at_most("landings_off_by", abs(len(landings) - asked), 0),
        at_most("landings_in_window_off_by",
                abs(len(landed) - len(mix["land_at_s"])), 0),
        at_most("programs_compiled_in_landings",
                sum(rec["programs"] for rec in landings), 0),
    ] + ((items.fold_checks(rep, cfg["correct"])
          + catchup_checks(rep, cfg["correct"])) if rep else []) + ryw_checks
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
                  "publishes": len(in_win), "landings": landed},
        trace_dir=trace_dir,
        artifacts={"loop": loop, "U": U, "V": V, "model": model,
                   "streams": streams, "updater": updater, "tap": tap,
                   "refits": refits, "replay": rep, "served": ryw})
