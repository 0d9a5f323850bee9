"""``kind: serve_unseen`` — ``serve``'s open loop against a ``ServingEngine``
that recommends only what the asking user has not rated.

The loop, the stream and the clocks are ``runners/serve.py``'s own
(imported, nothing of them changed).  What differs is what is published
and what is asked:

- every user has a rating history from the seed (``benchmark/histories.py``:
  the multiset of lengths from no seed), published with the factors as
  ``publish(U, V, user_seen=(indptr, indices))`` and resident on the chip;
  the user factors are planted from those histories (``U[u] = sum stars *
  V[item]``), so that a user's own items DO score highest and an engine
  that ignored the rule would return them;
- a user asks in proportion to what that user has rated (a request is a
  uniformly drawn rating's user); by id (the engine takes the resident
  history out), or by vector: that user's row plus noise, carrying
  ``exclude`` = the first 64 ids of that user's history.

``correct``, outside the window, against ``reference/topk_unseen.py``:
(1) NO answered request of the whole stream returned an id it was to
exclude (limit 0; every answer, not a sample); (2) on a seeded sample of
distinct clients, and separately on the users with the longest histories
(asked once each, by id, after the window): recall@k against the float64
exact top-k OF THE IDS LEFT; (3) every returned score against the float64
dot product of its returned id, relative to the row's largest score, and
the scores descend; (4) ids inside the catalog, no compilation in the
window.  With ``"rule": false`` in the mix (the CONTROL:
``benchmark/tests/chip_readings_unseen.py``) nothing is published or sent
to exclude and the same checks must fail.
"""

from __future__ import annotations

import collections
import inspect
import time

import numpy as np

from benchmark import datagen, histories
from benchmark.harness import (
    BenchmarkError,
    Outcome,
    at_least,
    at_most,
    memory_peak_bytes,
)
from benchmark.reference import topk_unseen as ref
from benchmark.runners import serve

# a slot that holds no answer carries the program's sentinel score
NO_ANSWER_BELOW = -3e38


class Asker:
    """``engine.submit`` for ``serve.OpenLoop``: a payload that is a pair
    is a request by vector with its own list of ids to exclude."""

    def __init__(self, engine, rule=True):
        self.engine, self.rule = engine, rule

    def submit(self, payload):
        if not isinstance(payload, tuple):
            return self.engine.submit(payload)
        if not self.rule:
            return self.engine.submit(payload[0])
        return self.engine.submit(payload[0], exclude=payload[1])

    def stop(self):
        self.engine.stop()


def make_requests(rng, U, hist, mix, n):
    """``(payloads, users)``: ``n`` requests, each from the user of a
    uniformly drawn rating (so P(user) is proportional to the history's
    length); a share of them by vector, carrying the first
    ``exclude_ids`` of that user's history."""
    indptr, indices = hist
    users = np.searchsorted(indptr, rng.integers(0, len(indices), n),
                            side="right") - 1
    by_vector = rng.random(n) < mix["vector_share"]
    noise = 0.01 * rng.standard_normal((n, U.shape[1]), dtype=np.float32)
    payloads = []
    for j, u in enumerate(users):
        if by_vector[j]:
            lo = indptr[u]
            payloads.append((U[u] + noise[j], indices[lo:min(
                indptr[u + 1], lo + mix["exclude_ids"])]))
        else:
            payloads.append(int(u))
    return payloads, users


def excluded_of(payload, user, hist):
    """The ids request ``payload`` of ``user`` was to be answered
    without."""
    indptr, indices = hist
    return (payload[1] if isinstance(payload, tuple)
            else indices[indptr[user]:indptr[user + 1]])


def start_engine(config, mix, seed):
    """(asker over the engine started and warm, U, V, the histories,
    seconds by phase)."""
    stamps = [time.perf_counter()]

    def lap():
        stamps.append(time.perf_counter())
        return stamps[-1] - stamps[-2]

    from tpu_als.serving.engine import ServingEngine

    def lap_peak(name):
        phases[name] = lap()
        phases[name[:-2] + "_peak_gb"] = 1e-9 * memory_peak_bytes()

    if "user_seen" not in inspect.signature(
            ServingEngine.publish).parameters:
        raise BenchmarkError(
            "this program's ServingEngine.publish takes no user_seen: it "
            "cannot recommend only what a user has not rated")
    phases = {"import_s": lap()}
    indptr, indices, stars = histories.seeded_histories(config, seed)
    phases["histories_s"] = lap()
    _, V = serve.seeded_factors(1, config["num_items"],
                                config["als"]["rank"], seed)
    lap_peak("item_factors_s")
    U = histories.planted_user_factors(indptr, indices, stars, V)
    lap_peak("user_factors_s")
    rule = mix.get("rule", True)
    engine = ServingEngine(k=config["serving"]["k"])
    engine.publish(U, V, user_seen=(indptr, indices) if rule else None)
    lap_peak("publish_s")
    engine.warmup()
    engine.start()
    lap_peak("warmup_s")
    hist = (indptr, indices)
    asker = Asker(engine, rule)
    rng = datagen.rng_for(seed, 4)
    for n in mix["warm_batches"]:
        tickets = [asker.submit(p)
                   for p in make_requests(rng, U, hist, mix, n)[0]]
        for t in tickets:
            t.result(timeout=120.0)
    phases["warm_batches_s"] = lap()
    return asker, U, V, hist, phases


def open_stream(asker, U, hist, mix, rng, seconds, k, clock=None):
    """``serve.open_stream`` with this runner's requests: ``(loop, marks,
    users)``."""
    rate, warm_s = mix["rate_per_s"], mix["warmup_seconds"]
    head_due = datagen.poisson_arrivals(rng, rate, warm_s)
    head_due[:mix["head_burst"]] = 0.0
    due = np.concatenate(
        [head_due, warm_s + datagen.poisson_arrivals(rng, rate, seconds)])
    marks = {}

    def at_head():
        if clock is not None:
            marks["compile"] = clock.now()

    payloads, users = make_requests(rng, U, hist, mix, len(due))
    return serve.OpenLoop(asker, payloads, due, mix["answer_timeout_s"], k,
                          head=len(head_due), at_head=at_head), marks, users


def ask_longest(asker, hist, mix, k):
    """One request by id for each of the ``check_longest`` users with the
    longest histories (ties to the lower id), after the window:
    ``(users, scores, ids, unanswered)``."""
    lengths = np.diff(hist[0])
    users = np.argsort(-lengths, kind="stable")[:mix["check_longest"]]
    scores = np.full((len(users), k), -np.inf)
    ids = np.full((len(users), k), -1, np.int64)
    unanswered = 0
    tickets = [asker.submit(int(u)) for u in users]
    for j, t in enumerate(tickets):
        try:
            s, i = t.result(timeout=mix["answer_timeout_s"])
            scores[j, :len(s)], ids[j, :len(i)] = s, i
        except Exception:   # noqa: BLE001 — counted, and compared as empty
            unanswered += 1
    return users, scores, ids, unanswered


def seen_in_answers(loop, users, hist):
    """Over EVERY answered request of the stream, head and window:
    ``(served ids that were to be excluded, by-id requests that were
    served at least one, by-id requests answered)``."""
    pairs = with_seen = by_id = 0
    for j in loop.answered(head_too=True):
        real = loop.scores[j] > NO_ANSWER_BELOW
        hit = int(np.isin(loop.ids[j][real], excluded_of(
            loop.payloads[j], users[j], hist)).sum())
        pairs += hit
        if not isinstance(loop.payloads[j], tuple):
            by_id += 1
            with_seen += hit > 0
    return pairs, int(with_seen), by_id


def sampled(loop, users, mix, seed):
    """Request numbers of a seeded sample of the window's answered
    requests, no user twice."""
    order = datagen.rng_for(seed, 3).permutation(loop.answered())
    _, first = np.unique(users[order], return_index=True)
    return order[np.sort(first)[:mix["check_requests"]]]


def compare(name, scores, ids, Q, V, excluded, k, lim, exact):
    """The checks of one set of answers against the float64 top-k of the
    ids left; names end in ``name``."""
    ref_s, ref_i = exact
    real = scores > NO_ANSWER_BELOW
    own = ref.own_scores(Q, V, ids)
    largest = np.abs(np.where(np.isfinite(ref_s), ref_s, 0)).max(axis=1)
    err = np.where(real, np.abs(scores - own), 0).max(axis=1) / largest
    masked = np.where(real, scores, -np.inf)
    rises = np.nan_to_num(np.maximum(np.diff(masked, axis=1), 0)).max(axis=1)
    return [
        at_most("seen_returned" + name,
                ref.seen_returned(ids, excluded, real), lim["seen_returned"]),
        at_most("score_rel_err" + name, float(np.nanmax(err)),
                lim["score_rel_err"]),
        at_most("scores_ascending_by" + name, float((rises / largest).max()),
                lim["score_rel_err"]),
        at_least("recall_at_k" + name,
                 ref.recall(np.where(real, ids, -1), ref_i),
                 lim["recall_at_k"]),
        at_least("ids_in_catalog" + name,
                 float(((ids >= 0) & (ids < len(V)))[real].all()), 1.0),
    ]


def answer_checks(loop, users, longest, U, V, hist, config, mix, seed):
    if not len(loop.answered()):
        return [at_least("answered_requests", 0, 1)], {}
    k, lim = config["serving"]["k"], config["correct"]
    sample = sampled(loop, users, mix, seed)
    Q = np.stack([p[0] if isinstance(p, tuple) else U[p]
                  for p in (loop.payloads[j] for j in sample)])
    excluded = [excluded_of(loop.payloads[j], users[j], hist)
                for j in sample]
    l_users, l_scores, l_ids, unanswered = longest
    l_excluded = [excluded_of(int(u), u, hist) for u in l_users]
    # one pass over the catalog for both sets of queries
    ex_s, ex_i = ref.exact_topk(np.concatenate([Q, U[l_users]]), V, k,
                                excluded + l_excluded)
    n = len(sample)
    checks = compare("", loop.scores[sample].astype(np.float64),
                     loop.ids[sample], Q, V, excluded, k, lim,
                     (ex_s[:n], ex_i[:n]))
    # 32 queries swing three times as far as 256: a limit of their own
    checks += compare("_longest", l_scores, l_ids, U[l_users], V,
                      l_excluded, k,
                      dict(lim, recall_at_k=lim["recall_at_k_longest"]),
                      (ex_s[n:], ex_i[n:]))
    checks.append(at_most("longest_unanswered", unanswered, 0))
    pairs, with_seen, by_id = seen_in_answers(loop, users, hist)
    checks.append(at_most("seen_returned_all_answers", pairs,
                          lim["seen_returned"]))
    return checks, {"sample": sample, "Q": Q, "excluded": excluded,
                    "longest_Q": U[l_users], "longest_excluded": l_excluded,
                    "by_id_with_seen_share": with_seen / max(by_id, 1)}


def run(cell):
    import jax

    from tpu_als import obs

    cfg, mix = cell.config, cell.traffic
    k = cfg["serving"]["k"]
    t_start = time.perf_counter()
    asker, U, V, hist, phases = start_engine(cfg, mix, cell.seed)
    cell.say("setup", process_to_runner_s=t_start - cell.t_process, **phases)
    rng = datagen.rng_for(cell.seed, 2)
    sent = {}

    def uploaded():
        return obs.counter_value("serving.exclusion_upload_bytes") or 0

    try:
        loop, marks, users = open_stream(asker, U, hist, mix, rng,
                                         cell.seconds, k, clock=cell.clock)
        at_head = loop.at_head

        def window_opens():
            at_head()
            sent["head"] = uploaded()

        loop.at_head = window_opens
        cell.say("ready", requests_s=time.perf_counter() - t_start
                 - sum(v for p, v in phases.items() if p.endswith("_s")),
                 head=loop.head)
        loop.run()
        sent["end"] = uploaded()
        in_window = cell.clock.since(marks["compile"])
        setup_s = loop.t0 + mix["warmup_seconds"] - cell.t_process
        longest = ask_longest(asker, hist, mix, k)
        trace_dir, traced = None, None
        if cell.trace:
            from benchmark.trace import profiler_options

            traced, _, t_users = open_stream(asker, U, hist, mix, rng,
                                             mix["trace_seconds"], k)
            trace_dir = cell.scratch("trace")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profiler_options())
            try:
                traced.run()
            finally:
                jax.profiler.stop_trace()
    finally:
        asker.stop()

    lat, late, queue = loop.latency_ms(), loop.late_ms(), loop.queue_ms()
    failed = loop.n - len(loop.answered())
    lengths = np.diff(hist[0])[users[loop.head:]]
    cell.say("window", setup_s=setup_s, offered_per_s=mix["rate_per_s"],
             requests=loop.n, answered=len(lat), failed=failed, errors=sorted(
                 collections.Counter(e for j, e in loop.errors.items()
                                     if j >= loop.head).items()),
             drain_s=loop.t_end - loop.t_last_submit,
             batches=loop.batches(), batch_sizes=loop.batch_sizes(),
             compile_in_window=in_window,
             exclusion_upload_bytes=sent["end"] - sent["head"],
             history_ids={q: float(np.percentile(lengths, q))
                          for q in (10, 50, 90, 99, 100)},
             gc=loop.gc_clock.summary(), slowest=loop.slowest(),
             latency_ms={q: float(np.percentile(lat, q)) if len(lat) else None
                         for q in (50, 90, 95, 99, 99.9, 100)},
             late_ms={q: float(np.percentile(late, q)) for q in (50, 99, 100)})
    t0 = time.perf_counter()
    checks, found = answer_checks(loop, users, longest, U, V, hist, cfg, mix,
                                  cell.seed)
    cell.say("reference", seconds=time.perf_counter() - t0,
             requests=mix["check_requests"], longest=mix["check_longest"],
             by_id_with_seen_share=found.get("by_id_with_seen_share"))
    checks.append(at_most("compilations_in_window",
                          in_window["compilations"], 0))
    metrics = {"setup_s": setup_s}
    if len(lat):
        for q in (50, 90, 95):
            metrics[f"serve_p{q}_ms"] = float(np.percentile(lat, q))
    if traced is not None:      # nothing of the traced window is timed,
        failed += traced.n - len(traced.answered())   # but a failure counts
    p90 = obs.histogram_quantile("serving.excluded_ids", 0.9,
                                 source="history")
    return Outcome(
        metrics=metrics, attempted=loop.n + (traced.n if traced else 0),
        failed=failed, checks=checks,
        counters={"queue_ms": queue, "late_ms": late, "latency_ms": lat,
                  # the trace holds the traced stream's head too
                  "batches": traced.batches(head_too=True) if traced
                  else None,
                  "excluded_ids_p90": None if np.isnan(p90) else p90,
                  "exclusion_upload_bytes": sent["end"] - sent["head"],
                  "window_batches": loop.batches(),
                  "score_columns": cfg["num_items"],
                  "rank": cfg["als"]["rank"],
                  # what the traced stream's batches were to exclude
                  "excluded_ids_per_batch": None if not traced else sum(
                      len(excluded_of(p, u, hist))
                      for p, u in zip(traced.payloads, t_users))
                  / max(traced.batches(head_too=True), 1)},
        trace_dir=trace_dir,
        artifacts={"loop": loop, "users": users, "U": U, "V": V,
                   "hist": hist, "longest": longest, **found})
