"""``kind: serve_live_items_unseen`` — ``serve_live_unseen``'s two streams,
parameter for parameter (users asking in proportion to what they have rated,
the rule in every request; rating events folded over their user's WHOLE
history and joined to it in the same publish), against a ``LiveUpdater`` that
folds the ITEM side of every batch too: a share of the events
(``new_item_share``) rate an item the catalog does not hold, another
(``new_item_again_share``) one of the run's new items again, so the catalog
moves under requests every one of which excludes a history that grows.

The package's live path wired as its users wire it, at its defaults:
``publish(U, V, user_seen=)`` of the resident histories, ``ALSModel`` +
``FoldInServer(base_history=)`` (``prewarm``ed on both sides) +
``LiveUpdater(fold_items=True)`` (whose ``start`` has the engine give the
catalog its spare rows and its segment, lay the histories out to grow and
pin and run what it will run), then the engine started
(``serve_live_unseen.start`` does all of it from the configuration).  The loop, the
streams' pacing, the clocks and the tap are the siblings' (imported, nothing
of them changed): ``serve_unseen.open_stream`` / ``Asker`` / ``sampled``,
``serve_live.EventStream``'s pacing and ``freshness_ms``,
``serve_live_items.SeqLoop``, ``serve_live_unseen.HistoryTap`` and its event
draw.  New here: the two draws of new items and ``correct`` against
``reference/live_items_unseen.py``.

``correct``, outside the window (the configuration's guarantees): (1) NO
answered request of head and window returned an id it was to exclude, each
by-id request against what its user HAD RATED as of the generation that
answered it (``Ticket.seq``), limit 0; (2) on a seeded sample of distinct
clients: recall@k against the float64 top-k of the ids left over THAT
generation's catalog (segment and appended rows included), the scores
against float64 dot products of that generation's rows; (3) counts: nothing
shed, every admitted event in a publish and with a freshness sample, the
program's count of ratings that entered a fold, of item folds by kind and of
events whose item it left to the refit equal to the replay's, the pairs each
publish appended to the histories the replay's (an id joins with the publish
that first makes its item servable); (4) EVERY fold of the run, both sides:
the row the program published against the float64 fold of ALL of that
entity's ratings over the rows the program had published — a user's within
``fold_row_rel_err_max``, an item's within ``item_fold_c`` times its own
conditioning times 2^-24; none missing, none unasked; (5) after the drain
one request by id for a seeded sample of touched users, for the touched
users with the longest histories and for EVERY rater of a new item: nothing
rated returned, the run's items among it (limit 0), and in particular no new
item to any of its raters; recall and scores over the final catalog; the
rows the index serves for the moved items read back bit for bit, and for
the items a resident rating names that the run's events rated their SEEDED
rows, bit for bit; (6) no compilation in the window.  The mix's
``"appends": false`` and the two controls of
``benchmark/tests/chip_readings_live_items_unseen.py`` must fail them.
Every wait has a limit, so the run ends on any program.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from benchmark import datagen
from benchmark.harness import BenchmarkError, Outcome, at_least, at_most
from benchmark.reference import live_items_unseen as ref
from benchmark.reference import topk_unseen
from benchmark.runners import (
    serve,
    serve_live,
    serve_live_items,
    serve_live_unseen,
    serve_unseen,
)


# the widest fold of an item the runner prewarms (the ladder 8 / 64 / 512):
# the run's events of the hottest item, a few hundred
ITEM_FOLD_WIDTH = 512


def refuse_a_program_without_the_path():
    """``BenchmarkError`` before any set-up where the program cannot run
    the deployment at all: the parent of PR 47 refuses ``fold_items`` on an
    engine that holds histories (at ``LiveUpdater.start``, a minute of
    set-up in), and has no ``ServingEngine.warmup_histories`` to tell by."""
    from tpu_als.serving.engine import ServingEngine

    serve_live_unseen.refuse_a_program_without_the_path()
    if not hasattr(ServingEngine, "warmup_histories"):
        raise BenchmarkError(
            "this program's ServingEngine has no warmup_histories: its "
            "LiveUpdater.start refuses fold_items on an engine that holds "
            "users' histories (the catalog cannot move under them)")


class AllEventStream(serve_live_unseen.HistoryEventStream):
    """The sibling's stream with two more draws, both among the events of
    users the model held at start: ``new_item_share`` of the events rate an
    item the catalog does not hold (ids ``first_new_item``, + 1, ... in
    arrival order), ``new_item_again_share`` one of the run's new items so
    far (``new_items``, shared by the streams of a run), drawn uniformly
    among those that user has not rated (an event that finds none stays
    what the sibling drew)."""

    def __init__(self, updater, loop, rng, config, mix, seconds, first_new,
                 hist, taken, first_new_item, new_items):
        super().__init__(updater, loop, rng, config, mix, seconds, first_new,
                         hist, taken)
        ev = mix["events"]
        old = 1.0 - ev["new_user_share"]
        draw = rng.random(self.n)
        self.is_new_item = ~self.is_new & (draw < ev["new_item_share"] / old)
        self.is_again = ~self.is_new & ~self.is_new_item & (
            draw < (ev["new_item_share"] + ev["new_item_again_share"]) / old)
        pick = rng.random(self.n)
        for j in range(self.n):
            u = int(self.user[j])
            if self.is_new_item[j]:
                self.item[j] = first_new_item + len(new_items)
                new_items.append(int(self.item[j]))
            elif self.is_again[j]:
                left = [i for i in new_items if (u, i) not in taken]
                self.is_again[j] = bool(left)
                if left:
                    self.item[j] = left[int(pick[j] * len(left))]
            taken.add((u, int(self.item[j])))


def start(config, mix, seed):
    """``serve_live_unseen.start`` (``(asker, tap, updater started, model,
    U, V, (indptr, indices, stars), seconds and peak bytes by phase)``: its
    ``LiveUpdater`` reads ``fold_items`` from the configuration, and its
    ``start`` has the engine give the catalog its spare rows and its
    segment, lay the histories out to grow and pin and run every program
    that excludes AND scores the segment), then the item side's programs
    and fixed table prewarmed before any event arrives, as docs/serving.md
    tells whoever folds items."""
    out = serve_live_unseen.start(config, mix, seed)
    updater, phases = out[2], out[-1]
    t0 = time.perf_counter()
    updater.foldin.prewarm(sides=("item",), widths=(ITEM_FOLD_WIDTH,))
    phases["foldin_prewarm_items_s"] = time.perf_counter() - t0
    phases["foldin_prewarm_items_peak_gb"] = (
        1e-9 * serve_unseen.memory_peak_bytes())
    return out


def open_streams(asker, updater, U, hist, cfg, mix, rng, ev_rng, seconds, k,
                 first_new, taken, new_items, clock=None):
    loop, marks, users = serve_unseen.open_stream(
        asker, U, hist[:2], mix, rng, seconds, k, clock=clock)
    loop = serve_live_items.SeqLoop(loop)
    events = AllEventStream(updater, loop, ev_rng, cfg, mix, seconds,
                            first_new, hist[:2], taken, cfg["num_items"],
                            new_items)
    return loop, marks, users, events


def replayed(streams, updater, tap, model, U, V, hist, config,
             operand_dtype=None):
    """``(the replay of every admitted event in the updater's batches, each
    fold from the rows the program had published; the ok records; pairs the
    program appended to the histories that are not the replay's or the
    other way round)`` — the replay ``None`` where the program's records do
    not give the batches.  ``operand_dtype``: the CONTROL in the program's
    place for the item folds (``ref.Replay.step``)."""
    recs = [r for r in updater.flight.records() if r.get("status") == "ok"]
    users, items, stars = (np.concatenate(
        [getattr(ev, name)[ev.admitted] for _, _, ev in streams])
        for name in ("user", "item", "stars"))
    if (any("events" not in r or "seq" not in r for r in recs)
            or sum(r["events"] for r in recs) != len(users)):
        return None, recs, len(users)
    rep = ref.Replay(U, V, *hist, config["als"]["regParam"],
                     fold_items=config["live"]["fold_items"])
    none = np.empty(0, np.int64)
    at = wrong = 0
    published = serve_live_items.published_rows(tap, model, recs)
    for b, r in enumerate(recs):
        sl = slice(at, at + r["events"])
        at += r["events"]
        rep.step(users[sl], items[sl], stars[sl], published[b],
                 operand_dtype=operand_dtype)
        said = tap.appended.get(r["seq"], (none, none))
        theirs = set(zip(model._user_map.to_original(said[0]).tolist(),
                         model._item_map.to_original(said[1]).tolist()))
        wrong += len(theirs ^ rep.joined[b])
    return rep, recs, wrong


def generation_of(recs, seqs):
    """How many of the updater's batches had been published when the
    generation ``seq`` answered (a ticket's seq is a publish seq)."""
    return np.searchsorted(np.array([r["seq"] for r in recs]), seqs,
                           side="right")


def segment_by_generation(recs, rep):
    """``[set of item ids the index held in its segment after batch b]``,
    from the records (``mode``, ``segment_rows``) and the replay's moved
    items; and whether the count agreed with the program's in every
    batch."""
    held, out, agreed = set(), [], True
    moved = {b: set(ids) for b, ids, _ in rep.item_log}
    for b, r in enumerate(recs):
        now = moved.get(b, set())
        if r.get("mode") == "compact":
            # folded into the base before the rows went in, or after
            held = set() if not r.get("segment_rows") else set(now)
        else:
            held |= now
        agreed &= len(held) == r.get("segment_rows", 0)
        out.append(set(held))
    return out, agreed


def seen_in_answers(loop, users, rep, gens, segment):
    """Over EVERY answered request of the stream, head and window:
    ``(served ids that were to be excluded, by-id requests served at least
    one, by-id requests answered, by-id requests whose history named an
    item the segment held)``."""
    pairs = with_seen = by_id = named = 0
    touched = set(rep.user_events)
    for j, g in zip(loop.answered(head_too=True), gens.tolist()):
        real = loop.scores[j] > serve_unseen.NO_ANSWER_BELOW
        p = loop.payloads[j]
        u = int(users[j])
        if isinstance(p, tuple):
            mine = p[1]
        elif u in touched:
            mine = rep.ids(u, g)
            named += bool(g and segment[g - 1]
                          and segment[g - 1] & set(
                              e[1] for e in rep.user_events[u] if e[0] < g))
        else:
            mine = rep.resident(u)[0]
        hit = int(np.isin(loop.ids[j][real], mine).sum())
        pairs += hit
        if not isinstance(p, tuple):
            by_id += 1
            with_seen += hit > 0
    return pairs, int(with_seen), by_id, named


def largest_score_error(scores, ids, Q, gens, rep):
    """Which served id of a set of answers read the largest score error,
    whether a fold of the run moved it, and how long its row and its query
    are (an item folded from one rating of a user whose ridge row is short
    comes out LONG beside the seeded catalog's rows of length 1, and a
    rescore in one bfloat16 pass errs by the product's own scale)."""
    off = np.nan_to_num(np.abs(scores - ref.own_scores(Q, gens, ids, rep)))
    off[scores <= serve_unseen.NO_ANSWER_BELOW] = 0
    j, c = np.unravel_index(np.argmax(off), off.shape)
    worst = int(ids[j, c])
    row = rep.item_rows.get(worst)
    return {"id": worst, "abs_err": float(off[j, c]),
            "moved": row is not None,
            "row_norm": float(np.linalg.norm(
                rep.V0[worst] if row is None else row)),
            "query_norm": float(np.linalg.norm(Q[j]))}


def compare(name, scores, ids, Q, gens, rep, excluded, k, lim):
    """The checks of one set of answers, each against ITS generation:
    ``serve_unseen.compare``'s, with the float64 top-k of the ids left and
    the dot products over the catalog of the generation that answered."""
    ref_s, ref_i, sizes = ref.exact_topk_left(Q, gens, rep, k, excluded)
    real = scores > serve_unseen.NO_ANSWER_BELOW
    own = ref.own_scores(Q, gens, ids, rep)
    largest = np.abs(np.where(np.isfinite(ref_s), ref_s, 0)).max(axis=1)
    err = np.where(real, np.abs(scores - own), 0).max(axis=1) / largest
    masked = np.where(real, scores, -np.inf)
    rises = np.nan_to_num(np.maximum(np.diff(masked, axis=1), 0)).max(axis=1)
    return [
        at_most("seen_returned" + name,
                topk_unseen.seen_returned(ids, excluded, real),
                lim["seen_returned"]),
        at_most("score_rel_err" + name, float(np.nanmax(err)),
                lim["score_rel_err"]),
        at_most("scores_ascending_by" + name, float((rises / largest).max()),
                lim["score_rel_err"]),
        at_least("recall_at_k" + name,
                 topk_unseen.recall(np.where(real, ids, -1), ref_i),
                 lim["recall_at_k"]),
        # (a served id that generation's catalog did not hold scores nan)
        at_least("ids_in_catalog" + name,
                 float(((ids >= 0) & (ids < sizes[:, None])
                        & ~np.isnan(own))[real].all()), 1.0),
    ]


def ask_after_drain(asker, model, rep, mix, seed, k, num_items):
    """(5): one request by id for each of a seeded sample of touched users,
    for the touched users with the longest histories and for EVERY rater of
    a new item: ``(users, rows, scores, ids, seqs, unanswered, how many are
    the sample, how many the longest)``."""
    ev = mix["events"]
    touched = np.array(rep.touched())
    sample = datagen.rng_for(seed, 6).permutation(touched)[:ev["check_users"]]
    lengths = np.array([len(rep.ids(u)) for u in touched])
    longest = touched[np.argsort(-lengths, kind="stable")[:ev["check_longest"]]]
    raters = np.array(sorted(
        u for u, evs in rep.user_events.items()
        if any(e[1] >= num_items for e in evs)), np.int64)
    users = np.concatenate([sample, longest, raters])
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
    return (users, rows, scores, ids, seqs, unanswered, len(sample),
            len(longest))


def answer_checks(loop, users, after, rep, recs, segment, config, mix, seed):
    """(1), (2) and (5)."""
    if not len(loop.answered()):
        return [at_least("answered_requests", 0, 1)], {}
    k, lim = config["serving"]["k"], config["correct"]
    n_items = config["num_items"]
    sample = serve_unseen.sampled(loop, users, mix, seed)
    gens = generation_of(recs, loop.seq[sample])
    Q, excluded = [], []
    for j, g in zip(sample, gens.tolist()):
        p = loop.payloads[j]
        if isinstance(p, tuple):
            Q.append(np.asarray(p[0], np.float64))
            excluded.append(p[1])
        else:
            Q.append(rep.user_row(int(users[j]), g))
            excluded.append(rep.ids(int(users[j]), g))
    checks = compare("", loop.scores[sample].astype(np.float64),
                     loop.ids[sample], np.stack(Q), gens, rep, excluded, k,
                     lim)
    worst = largest_score_error(loop.scores[sample].astype(np.float64),
                                loop.ids[sample], np.stack(Q), gens, rep)
    (a_users, a_rows, a_scores, a_ids, a_seqs, unanswered, n_sample,
     n_longest) = after
    a_gens = generation_of(recs, a_seqs)
    a_Q = np.stack([rep.user_row(int(u), g) if r >= 0
                    else np.zeros(Q[0].shape)
                    for u, r, g in zip(a_users, a_rows, a_gens.tolist())])
    a_excluded = [rep.ids(int(u), g)
                  for u, g in zip(a_users, a_gens.tolist())]
    m = n_sample + n_longest
    for name, part, limits in (
            ("_after_drain", slice(0, n_sample), lim),
            # the few longest swing further: a limit of their own
            ("_longest", slice(n_sample, m),
             dict(lim, recall_at_k=lim["recall_at_k_longest"])),
            ("_raters", slice(m, None), lim)):
        if len(a_users[part]):
            checks += compare(name, a_scores[part], a_ids[part], a_Q[part],
                              a_gens[part], rep, a_excluded[part], k, limits)
    # read your writes, for the rule: what the run's events rated is gone —
    # and a new item from every one of its raters, in a slot or in the base
    real = a_scores > serve_unseen.NO_ANSWER_BELOW
    back = new_back = raters_with = 0
    for j, (u, s, i) in enumerate(zip(a_users, real, a_ids)):
        mine = rep.rated_in_the_run(int(u))
        hit = np.isin(i[s], mine)
        back += int(hit.sum())
        new = int((hit & (i[s] >= n_items)).sum())
        new_back += new
        raters_with += j >= m and new > 0
    gens_all = generation_of(recs, loop.seq[loop.answered(head_too=True)])
    pairs, with_seen, by_id, named = seen_in_answers(loop, users, rep,
                                                     gens_all, segment)
    checks += [
        at_most("after_drain_unanswered", unanswered, 0),
        at_most("rated_in_the_run_returned_after_drain", back,
                lim["seen_returned"]),
        at_most("new_item_returned_to_its_rater", new_back,
                lim["seen_returned"]),
        at_most("seen_returned_all_answers", pairs, lim["seen_returned"]),
        at_least("answers_with_their_generation",
                 float((loop.seq[loop.answered(head_too=True)] > 0).all()),
                 1.0)]
    return checks, {"sample": sample, "rated_back": back,
                    "after_Q": a_Q, "after_excluded": a_excluded,
                    "after_sample": n_sample,
                    "largest_score_err_at": worst,
                    "new_items_back": new_back,
                    "raters_given_a_new_item_back": raters_with,
                    "raters_asked": len(a_users) - m,
                    "by_id_with_seen_share": with_seen / max(by_id, 1),
                    "requests_whose_history_named_a_slot": named}


def fold_checks(rep, lim):
    """(4): every fold of the run, both sides."""
    over = rep.item_err_over_kappa()
    return [
        at_most("folds_without_a_published_row", rep.missing, 0),
        at_most("rows_published_without_a_fold", rep.unasked, 0),
        at_most("fold_row_rel_err_max", max(rep.fold_err[0], default=np.inf),
                lim["fold_row_rel_err_max"]),
        at_most("item_fold_err_over_kappa_max",
                float(over.max()) if len(over) else np.inf,
                lim["item_fold_c"])]


def catalog_checks(engine, model, rep, mix, seed, lim, V):
    """The rows the index serves, read back: for a seeded sample of the
    moved items the last published row, for the items a resident rating
    names that the run's events rated their SEEDED row — both bit for bit;
    the catalog's size."""
    index = engine.published_index
    rng = datagen.rng_for(seed, 7)
    moved = rng.permutation(rep.moved_items())[:mix["events"]["check_items"]]
    kept = np.array(sorted({
        e[1] for evs in rep.user_events.values() for e in evs
        if e[1] < len(rep.rated_before) and rep.rated_before[e[1]]}),
        np.int64)
    kept = rng.permutation(kept)[:mix["events"]["check_items"]]
    want = np.concatenate([
        np.stack([rep.item_rows[int(i)] for i in moved]).astype(np.float32)
        if len(moved) else np.zeros((0, V.shape[1]), np.float32), V[kept]])
    ids = model._item_map.to_dense(np.concatenate([moved, kept]))
    read = getattr(index, "rows", None)
    if read is None or (ids < 0).any():
        rows, ok = np.zeros_like(want), np.zeros(len(want), bool)
    else:
        rows, ok = read(ids)
    err = (np.linalg.norm(rows - want, axis=1)
           / np.maximum(np.linalg.norm(want, axis=1), 1e-30))
    n = len(moved)
    n_items = index.n_items if index is not None else -1
    new = rep.moved_items()
    new = new[new >= len(V)]
    return [
        # (every check on served ids reads them as the reference's ids)
        at_most("new_items_whose_row_is_not_their_id",
                int((model._item_map.to_dense(new) != new).sum()), 0),
        at_most("foldin_item_row_rel_err_max",
                float(err[:n].max(initial=0.0)),
                lim["foldin_item_row_rel_err_max"]),
        at_most("items_left_to_refit_row_moved_by",
                float(err[n:].max(initial=0.0)), 0),
        at_least("items_left_to_refit_compared", len(kept), 1),
        at_least("foldin_item_rows_served", float(ok.all()), 1.0),
        at_most("catalog_size_off_by",
                abs(n_items - (rep.n_items[-1] if rep.n_items else len(V))),
                0)]


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
    streams, taken, new_items, marks_at = [], set(), [], {}
    names = ("foldin.ratings", "live.publish_h2d_bytes",
             "live.catalog_h2d_bytes", "live.history_h2d_bytes",
             "live.history_appended_ids", "live.history_segment_ids",
             "live.history_relocations", "live.items_left_to_refit",
             "live.items_appended", "serving.exclusion_upload_bytes")

    def counters():
        out = {n: obs.counter_value(n) or 0 for n in names}
        for kind in ("first", "again"):
            out["live.items_folded." + kind] = obs.counter_value(
                "live.items_folded", kind=kind) or 0
        return out

    at_start = counters()
    sampled0 = obs.histogram_count("live.freshness_seconds")
    try:
        loop, marks, users, events = open_streams(
            asker, updater, U, hist, cfg, mix, rng, ev_rng, cell.seconds, k,
            cfg["num_users"], taken, new_items, clock=cell.clock)
        at_head = loop.at_head

        def window_opens():
            at_head()
            marks_at["head"] = counters()

        loop.at_head = window_opens
        events.start()
        streams.append((loop, users, events))
        cell.say("ready", requests_s=time.perf_counter() - t_start
                 - sum(v for p, v in phases.items() if p.endswith("_s")),
                 head=loop.head, events=events.n,
                 new_items=int(events.is_new_item.sum()),
                 rated_again=int(events.is_again.sum()))
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
                cfg["num_users"] + int(events.is_new.sum()), taken,
                new_items)
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
        t0 = time.perf_counter()
        rep, recs, wrong_pairs = replayed(streams, updater, tap, model, U, V,
                                          hist, cfg)
        replay_s = time.perf_counter() - t0
        after = (None if rep is None or not rep.user_events else
                 ask_after_drain(asker, model, rep, mix, cell.seed, k,
                                 cfg["num_items"]))
        catalog = ([] if rep is None else catalog_checks(
            tap, model, rep, mix, cell.seed, cfg["correct"], V))
        peak = serve_unseen.memory_peak_bytes()
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
             for n in marks_at["end"]}
    lengths = np.diff(hist[0])[users[loop.head:]]
    cell.say("window", setup_s=setup_s, offered_per_s=mix["rate_per_s"],
             requests=loop.n, answered=len(lat), failed=loop.n - len(lat),
             errors=sorted(collections.Counter(
                 e for j, e in loop.errors.items() if j >= loop.head).items()),
             drain_s=loop.t_end - loop.t_last_submit,
             batches=loop.batches(), batch_sizes=loop.batch_sizes(),
             compile_in_window=in_window, memory_peak_bytes=peak,
             history_ids={q: float(np.percentile(lengths, q))
                          for q in (10, 50, 90, 99, 100)},
             gc=loop.gc_clock.summary(), slowest=loop.slowest(),
             latency_ms={q: float(np.percentile(lat, q)) if len(lat) else None
                         for q in (50, 90, 95, 99, 99.9, 100)},
             late_ms={q: float(np.percentile(late, q)) for q in (50, 99, 100)})
    segment, agreed = (([], False) if rep is None
                       else segment_by_generation(recs, rep))
    cell.say("live", events_per_s=mix["events"]["rate_per_s"],
             events=events.n, in_window=events.n - events.head,
             admitted=admitted, shed=shed, errors=sorted(
                 collections.Counter(events.errors.values()).items()),
             users_touched=0 if rep is None else len(rep.user_events),
             new_users=len(model._user_map) - cfg["num_users"],
             new_items=len(model._item_map) - cfg["num_items"],
             publishes=len(recs), publishes_in_window=len(in_win),
             events_per_publish=(float(np.mean([r["events"] for r in in_win]))
                                 if in_win else None),
             items_per_publish=(float(np.mean([r.get("items", 0)
                                               for r in in_win]))
                                if in_win else None),
             publish_modes=sorted(collections.Counter(
                 r.get("mode") for r in recs).items()),
             compactions_in_window=sum(r.get("mode") == "compact"
                                       for r in in_win),
             segment_rows_max=max([r.get("segment_rows", 0) for r in in_win]
                                  or [0]),
             segment_counts_agree=agreed,
             in_window_counters=moved, updater_drain_s=drain_s,
             replay_s=replay_s,
             busy_share_of_the_window=(
                 sum(r["spans"]["foldin"] + r["spans"]["publish"]
                     for r in in_win) / max(t_close - t_open, 1e-9)),
             phase_ms={key: (float(np.median([r["spans"][key]
                                              for r in in_win])) * 1e3
                             if in_win else None)
                       for key in ("queue_wait", "foldin", "publish")},
             fold_width={side: {q: obs.histogram_quantile(
                 "foldin.history_width", q / 100, side=side)
                 for q in (50, 90, 100)} for side in ("user", "item")},
             freshness_ms=(None if fresh is None or not len(fresh) else
                           {q: float(np.percentile(fresh, q))
                            for q in (50, 90, 99, 100)}))

    t0 = time.perf_counter()
    found = {}
    if rep is None or after is None:
        checks = [at_least("publish_records_that_give_the_batches", 0, 1)]
    else:
        checks, found = answer_checks(loop, users, after, rep, recs, segment,
                                      cfg, mix, cell.seed)
        checks += fold_checks(rep, cfg["correct"]) + catalog
        over = rep.item_err_over_kappa()
        cell.say("reference", seconds=time.perf_counter() - t0,
                 requests=mix["check_requests"],
                 folds={"user": len(rep.fold_err[0]),
                        "item": len(rep.fold_err[1])},
                 fold_row_rel_err={
                     side: {q: float(np.percentile(errs, q))
                            for q in (50, 99, 100)}
                     for side, errs in zip(("user", "item"), rep.fold_err)
                     if errs},
                 item_kappa=({q: float(np.percentile(rep.item_kappa, q))
                              for q in (0, 50, 99, 100)}
                             if rep.item_kappa else None),
                 item_err_over_kappa=({q: float(np.percentile(over, q))
                                       for q in (50, 99, 100)}
                                      if len(over) else None),
                 item_folds=rep.folds, left_to_refit=rep.left_to_refit,
                 moved_item_row_norm={q: float(np.percentile(
                     [np.linalg.norm(x) for x in rep.item_rows.values()], q))
                     for q in (0, 50, 90, 100)} if rep.item_rows else None,
                 largest_score_err_at=found.get("largest_score_err_at"),
                 **{key: found.get(key) for key in (
                     "rated_back", "new_items_back", "raters_asked",
                     "raters_given_a_new_item_back", "by_id_with_seen_share",
                     "requests_whose_history_named_a_slot")})
    # (the traced seconds and the drain fold after ``end``)
    final = counters()
    sampled = obs.histogram_count("live.freshness_seconds") - sampled0
    if rep is not None:
        checks += [
            at_most("events_folded_off_by", abs(
                final["foldin.ratings"] - at_start["foldin.ratings"]
                - rep.entered), 0),
            at_most("items_left_to_refit_off_by", abs(
                final["live.items_left_to_refit"]
                - at_start["live.items_left_to_refit"]
                - rep.left_to_refit), 0),
            at_most("item_folds_off_by", sum(abs(
                final["live.items_folded." + kind]
                - at_start["live.items_folded." + kind] - n)
                for kind, n in rep.folds.items()), 0)]
    checks += [
        at_most("events_shed", shed, 0),
        at_most("events_admitted_without_freshness",
                abs(admitted - sampled), 0),
        at_most("events_admitted_not_in_a_publish",
                abs(admitted - sum(r.get("events", 0) for r in recs)), 0),
        at_most("appended_pairs_not_the_replays", wrong_pairs, 0),
        at_most("compilations_in_window", in_window["compilations"], 0)]
    metrics = {"setup_s": setup_s}
    if len(lat):
        for q in (50, 90, 95):
            metrics[f"serve_p{q}_ms"] = float(np.percentile(lat, q))
    p90 = obs.histogram_quantile("serving.excluded_ids", 0.9,
                                 source="history")
    w90 = obs.histogram_quantile("foldin.history_width", 0.9, side="user")
    # what the traced seconds' folds HAD to read: their users' ratings and
    # the rows of the users their items were folded over
    t_recs = ([(b, r) for b, r in enumerate(recs)
               if streams[-1][0].t0 <= r.get("t_done", -1.0)]
              if traced is not None and rep is not None else [])
    t_rows = t_ratings = 0
    for b, r in t_recs:
        tu, _, ti, _ = tap.log.get(r["seq"], (np.empty(0, np.int64), (),
                                              np.empty(0, np.int64), ()))
        for u in model._user_map.to_original(tu).tolist():
            t_ratings += len(rep.ids(int(u), b + 1))
        for i in model._item_map.to_original(ti).tolist():
            t_ratings += len(rep.item_events.get(int(i), ()))
        t_rows += len(tu) + len(ti)
    segment_slots = getattr(tap.published_index, "delta_slots", 0)
    return Outcome(
        metrics=metrics, attempted=attempted, failed=failed, checks=checks,
        counters={"queue_ms": queue, "late_ms": late, "latency_ms": lat,
                  # the trace holds the traced stream's head too
                  "batches": traced.batches(head_too=True) if traced
                  else None,
                  "freshness_ms": fresh,
                  "publish_h2d_bytes": moved["live.publish_h2d_bytes"],
                  "catalog_h2d_bytes": moved["live.catalog_h2d_bytes"],
                  "history_h2d_bytes": moved["live.history_h2d_bytes"],
                  "history_segment_ids": moved["live.history_segment_ids"],
                  "items_left_to_refit": moved["live.items_left_to_refit"],
                  "events_in_window": sum(r["events"] for r in in_win),
                  "publishes": len(in_win),
                  "fold_width_p90": None if np.isnan(w90) else w90,
                  "fold_rows_traced": t_rows or None,
                  "fold_ratings_traced": t_ratings or None,
                  "excluded_ids_p90": None if np.isnan(p90) else p90,
                  "exclusion_upload_bytes":
                      moved["serving.exclusion_upload_bytes"],
                  "window_batches": loop.batches(),
                  # the columns the program really scores: the catalog with
                  # its spare rows would flatter it; the segment's slots are
                  # scored every batch
                  "score_columns": cfg["num_items"] + segment_slots,
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
                   "after": after, "recs": recs, "streams": streams,
                   "updater": updater,
                   "memory_peak_bytes": peak, **found})
