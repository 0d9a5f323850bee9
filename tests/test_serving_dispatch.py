"""One runtime call a batch on the engine thread (PR 41): the staged
batch rides the pinned program's call as the host array it is, and no
``jax.device_put`` is left on a batch's path — for every program
``ServingEngine._dispatch`` can choose: the plain int8 program, the one
with a delta segment, the one that excludes histories, the exact
fallback, and on four CPU devices the mesh's int8 and exact programs
(which since PR 44 take the batch placed by ONE transfer, to the mesh's
first device, ``ServingEngine._place_one``: a second runtime call, and
still no ``jax.device_put``).

One engine a program runs one scenario (``flown``) and four tests read
it: (i) the same answers, bit for bit, as the same pinned executable
called on a ``device_put`` copy of the same staged array; (ii) not one
``device_put`` while a started engine serves 50 batches; (iii) no
compilation across them, nor on the jit fall-back after a dropped pin;
(iv) two
batches of one bucket in flight at once are staged in two arrays and
both answered right.

Every dispatch of a warmed engine rides its pin (PR 46): for an engine of
each benchmark cell's kind (since PR 47 also the one whose catalog moves
under histories that grow), warmed as its cell warms it, one batch for
every (bucket, path, history pad) it pinned goes through that pinned
executable — called once, still pinned afterwards, nothing compiled, the
launch span saying so and naming the executable's own module.  A pin that
misses is invisible otherwise: ``_run_pinned`` drops it and calls the
``jit`` function, whose compile costs milliseconds here and seconds on
the chip."""

from __future__ import annotations

import re
import threading
import time

import jax
import numpy as np
import pytest

from tests.conftest import CompileCount, GatedResponses
from tpu_als import ALSModel, FoldInServer, IdMap, LiveUpdater, make_mesh
from tpu_als.resilience import faults
from tpu_als.serving import engine as engine_module
from tpu_als.serving.engine import ServingEngine

S, N_USERS, N_ITEMS, RANK, K, BUCKET = 4, 203, 2003, 16, 5, 8
BATCHES = 50
# program: the key warmup() pins it under
PROGRAMS = {
    "int8": (BUCKET, "int8"),
    "int8_delta": (BUCKET, "int8_delta"),
    "int8_seen": (BUCKET, "int8", 64),
    "exact": (BUCKET, "exact"),
    "mesh_int8": (BUCKET, "int8"),
    "mesh_exact": (BUCKET, "exact"),
}


class Calls:
    """In a pinned executable's place: counts its calls, keeps their
    arguments, and raises in its stead while ``broken``."""

    def __init__(self, compiled):
        self.compiled, self.args, self.broken = compiled, [], False

    def __call__(self, *args):
        if self.broken:
            raise TypeError("the pin no longer fits (says the test)")
        self.args.append(args)
        return self.compiled(*args)


def build(program):
    rng = np.random.default_rng(41)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    V = (rng.standard_normal((N_ITEMS, RANK)) / 4).astype(np.float32)
    eng = ServingEngine(k=K, buckets=(BUCKET,), shortlist_k=32,
                        mesh=(make_mesh(S) if program.startswith("mesh")
                              else None))
    kw = {}
    if program.endswith("exact"):
        kw["quantize"] = False          # no index: the fallback serves
    if program == "int8_seen":
        lengths = rng.integers(0, 40, N_USERS)
        kw["user_seen"] = (
            np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64),
            np.concatenate([np.sort(rng.choice(N_ITEMS, n, replace=False))
                            for n in lengths]).astype(np.int32))
    eng.publish(U, V, **kw)
    eng.warmup()
    if program == "int8_delta":
        eng.warmup_live(max_rows=8)
    return eng, rng


def requests(rng, n):
    """By id, and every third by vector."""
    return [rng.standard_normal(RANK).astype(np.float32) if j % 3 == 2
            else int(rng.integers(0, N_USERS)) for j in range(n)]


def serve_now(eng, payloads):
    """``payloads`` as ONE batch on the caller's thread."""
    tickets = [eng.submit(p) for p in payloads]
    batch = eng.batcher.next_batch(timeout=0, coalesce=False)
    assert len(batch) == len(payloads)
    eng.serve_batch(batch)
    return [t.result(timeout=0) for t in tickets]


def on_a_placed_copy(eng, pin, args, st):
    """The pinned executable on the same arguments but the staged array
    ``st``, which goes up first by a ``device_put`` of a copy, as every
    batch's did until PR 41 — on a mesh a copy of the whole ``[S * B,
    width]`` array the program takes since PR 44, ``st`` and its ``S -
    1`` blocks of zeros, a placement a shard: the packed response."""
    if eng.mesh is None:
        (at,) = [i for i, a in enumerate(args) if a is st]
        placed = jax.device_put(st.copy())
    else:
        whole = np.zeros((S * len(st), st.shape[1]), st.dtype)
        whole[:len(st)] = st
        (at,) = [i for i, a in enumerate(args)
                 if (a.shape, a.dtype) == (whole.shape, whole.dtype)]
        placed = jax.device_put(whole, eng._by_rows)
    return np.asarray(pin.compiled(*args[:at], placed, *args[at + 1:]))


def packed(answers):
    """``[(scores, ids)]`` as the rows of the packed response."""
    return np.stack([np.concatenate([s.view(np.int32), i])
                     for s, i in answers])


@pytest.fixture(scope="module", params=list(PROGRAMS))
def flown(request):
    """What one engine of ``program`` did, step by step (the keys of the
    returned dict), with ``jax.device_put`` and the compiler watched."""
    program = request.param
    key = PROGRAMS[program]
    eng, rng = build(program)
    compiles = CompileCount()
    pin = eng._pinned[key] = Calls(eng._pinned[key])
    staged, stage = [], eng._staged

    def keep_staged(*args, **kw):
        staged.append(stage(*args, **kw))
        return staged[-1]

    eng._staged = keep_staged
    out = {"program": program, "eng": eng}
    # (i) one batch on the caller's thread
    out["answers"] = packed(serve_now(eng, requests(rng, 5)))
    out["placed"] = on_a_placed_copy(eng, pin, pin.args[-1],
                                     staged[-1])[:5]
    # (iii) the pin dropped: the ordinary jit call takes the host array
    n0, pin.broken = compiles.n, True
    fell = serve_now(eng, requests(rng, 5))
    out["pin_dropped"] = key not in eng._pinned
    out["fallback_compiles"] = compiles.n - n0
    again = requests(rng, 5)
    fell_again = serve_now(eng, again)
    out["fallback_compiles_again"] = compiles.n - n0 - out[
        "fallback_compiles"]
    pin.broken, eng._pinned[key] = False, pin
    out["fallback"] = packed(fell_again), packed(serve_now(eng, again))
    assert len(fell) == 5
    # (ii), (iii) a started engine, BATCHES requests one after another
    puts, real_put = [], jax.device_put
    n0, calls0, seq0 = compiles.n, len(pin.args), eng._batch_seq
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "device_put", lambda *a, **kw: (
            puts.append(threading.current_thread().name),
            real_put(*a, **kw))[1])
        gated = GatedResponses(eng)
        gated.open()
        with eng:
            for p in requests(rng, BATCHES):
                eng.submit(p).result(timeout=20.0)
            out["batches"] = eng._batch_seq - seq0
            out["pinned_calls"] = len(pin.args) - calls0
            out["compiles"] = compiles.n - n0
            out["device_puts"] = list(puts)
            # (iv) two of one bucket in flight: the second staged and
            # dispatched while the first's readback is held
            gated._open = False
            first = len(gated.gates)
            a = eng.submit(7)
            gated.wait_dispatched(first + 1)
            assert gated.gates[first].entered.wait(10.0)
            b = eng.submit(11)
            gated.wait_dispatched(first + 2)
            out["both_in_flight"] = not (a.done() or b.done())
            out["staged"] = staged[-2:]
            gated.open()
            out["pair"] = packed([a.result(timeout=20.0)]), packed(
                [b.result(timeout=20.0)])
        out["device_puts_in_all"] = list(puts)
    out["pair_placed"] = [on_a_placed_copy(eng, pin, args, st)[:1]
                          for args, st in zip(pin.args[-2:], staged[-2:])]
    return out


def test_the_batch_rides_the_call_bit_for_bit(flown):
    """(i) the program and its operands are the parent's: the answers of
    a batch served through the engine are those of the same pinned
    executable on a ``device_put`` copy of the same staged array."""
    eng, key = flown["eng"], PROGRAMS[flown["program"]]
    assert eng._pinned[key].compiled is not None
    assert flown["answers"].shape == (5, 2 * K)
    assert np.array_equal(flown["answers"], flown["placed"])
    rec = eng.batch_flight.records()[-1]
    assert rec["upload_how"] == ("call" if eng.mesh is None
                                 else "put_one") and rec["upload"] > 0


def test_no_device_put_while_a_started_engine_serves(flown):
    """(ii) not from the engine thread, nor from any other."""
    assert flown["batches"] == BATCHES == flown["pinned_calls"]
    assert "tpu-als-serving" not in flown["device_puts_in_all"]
    assert flown["device_puts"] == []


def test_nothing_compiles_on_the_pin_nor_on_the_jit_fall_back(flown):
    """(iii) the staged batch hits the pinned executable; after a
    dropped pin the ordinary jit call takes it too, from the cache entry
    ``warmup()`` left — with a mesh as well since PR 44: the batch
    arrives placed as ``warmup()``'s prototype was (a host argument
    carried no sharding, and the call compiled once more) — and the
    answers are the pinned program's."""
    assert flown["compiles"] == 0
    assert flown["pin_dropped"]
    assert flown["fallback_compiles"] == 0
    assert flown["fallback_compiles_again"] == 0
    by_jit, by_pin = flown["fallback"]
    assert np.array_equal(by_jit, by_pin)


def test_two_batches_in_flight_are_staged_apart_and_both_right(flown):
    """(iv) ``_staged``'s contract: a NEW array every batch — the runtime
    may read the host's buffer after the call has returned."""
    assert flown["both_in_flight"]
    first, second = flown["staged"]
    assert first is not second and not np.shares_memory(first, second)
    assert first.shape == second.shape and first.base is None
    # the first's ids were not overwritten by the second's staging
    assert (first[0, RANK], second[0, RANK]) == (7, 11)
    for got, want in zip(flown["pair"], flown["pair_placed"]):
        assert np.array_equal(got, want)
    assert not np.array_equal(*flown["pair"])


def test_the_fallback_on_a_mesh_uploads_the_last_id_once():
    """The exact fallback's clamp id goes to the mesh once a catalog
    size, not once a batch (the int8 path's ``_last_id`` pattern)."""
    eng, _ = build("mesh_exact")
    handle = eng._last_item(eng._model.n_items)
    assert handle is eng._last_item(eng._model.n_items)
    assert int(handle) == N_ITEMS - 1
    assert handle is not eng._last_item(N_ITEMS + 1)
    assert int(eng._last_item(N_ITEMS + 1)) == N_ITEMS


# -- every dispatch of a warmed engine rides its pin (PR 46) ------------------

PIN_BUCKETS = (8, 32)
# kind of engine -> the history pads it pins (None: it excludes nothing)
# and the name its int8 program is pinned under
KINDS = {
    "plain": (None, "int8"),            # serve-steady, serve-foldin
    "segment": (None, "int8_delta"),    # serve-foldin-items
    "histories": ((64, 512), "int8"),   # serve-unseen
    "grown": ((64, 128), "int8"),       # serve-foldin-unseen
    "mesh": (None, "int8"),             # serve-steady-mesh
    # PR 47, no parent: the catalog moves under histories that grow
    "segment_grown": ((64, 128), "int8_delta"),     # serve-foldin-all
}
# resident history lengths: the as-published ladder 64 / 512 from the
# longest of ``histories``; ``grown``'s longest, 64, with its room (72)
# asks for one rung more, 128, which only an append reaches
LENGTHS = {"histories": (0, 3, 40, 64, 65, 300), "grown": (0, 3, 40, 63, 64),
           "segment_grown": (0, 3, 40, 63, 64)}


def pin_key(bucket, path, pad):
    return (bucket, path) if pad is None else (bucket, path, pad)


def pins_of(kind):
    """``[(bucket, path, history pad or None)]`` of what ``kind``'s
    warm-ups pin: per bucket the int8 program (at every history pad) and
    the exact fallback (at the longest alone)."""
    pads, int8 = KINDS[kind]
    return [(B, path, pad) for B in PIN_BUCKETS for path, pad in (
        [(int8, None), ("exact", None)] if pads is None else
        [(int8, p) for p in pads] + [("exact", pads[-1])])]


class Spans:
    """In ``TraceAnnotation``'s place in the engine: every span's name
    with the metadata it was given, at birth or later."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **stats):
        self.seen.append((name, stats))
        return _Span(stats)

    @staticmethod
    def is_enabled():
        return False                # no profiler records: no CPU stamps


class _Span:
    def __init__(self, stats):
        self.stats = stats

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        self.stats.update(stats)


def warmed(kind):
    """``(engine, {history length: a user of it})`` of ``kind``, published
    and warmed as the benchmark cell of that kind does it."""
    rng = np.random.default_rng(46)
    V = (rng.standard_normal((N_ITEMS, RANK)) / 4).astype(np.float32)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    eng = ServingEngine(k=K, buckets=PIN_BUCKETS, shortlist_k=32,
                        mesh=make_mesh(S) if kind == "mesh" else None)
    if kind not in LENGTHS:
        eng.publish(U, V)
        eng.warmup()
        if kind == "segment":
            eng.warmup_live(max_rows=8)
            # a publish that moves the catalog: a row re-folded, one new
            V2 = np.concatenate([V, V[:1]])
            V2[5] *= 0.5
            assert eng.publish_update(U, V2, touched_items=[5])[1] == "delta"
        return eng, {}
    lengths = np.resize(LENGTHS[kind], N_USERS)
    items = [np.sort(rng.choice(N_ITEMS, n, replace=False)) for n in lengths]
    indptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    indices = np.concatenate(items).astype(np.int32)
    eng.publish(U, V, user_seen=(indptr, indices))
    users = {int(n): j for j, n in enumerate(LENGTHS[kind])}
    if kind == "histories":
        eng.warmup()
        return eng, users
    model = ALSModel(
        RANK, IdMap(ids=np.arange(N_USERS)), IdMap(ids=np.arange(N_ITEMS)),
        U.copy(), V.copy(),
        {"userCol": "u", "itemCol": "i", "ratingCol": "r", "regParam": 0.1,
         "implicitPrefs": False, "alpha": 1.0, "nonnegative": False})
    srv = FoldInServer(model, base_history=(
        indptr, indices, np.ones(len(indices), np.float32)))
    both = kind == "segment_grown"
    srv.prewarm(rows=(8,), sides=("user", "item") if both else ("user",))
    upd = LiveUpdater(eng, srv, max_batch=8, max_wait_ms=2.0,
                      fold_items=both).start()
    try:
        # appends under the warmed programs: the user at the top resident
        # rung outgrows it (and its run's room: the run moves), one at 63
        # crosses 64, one the model never saw gets a first run; where the
        # catalog moves too, the first of them are of items it does not
        # hold yet (a catalog publish each, the id naming a slot)
        fresh = iter(range(N_ITEMS, N_ITEMS + 3 * both))
        for user in [users[64]] * 10 + [users[63]] * 2 + [N_USERS + 3]:
            have = set(items[user].tolist()) if user < N_USERS else set()
            item = next(fresh, None) if user < N_USERS else None
            if item is None:
                item = next(i for i in range(N_ITEMS) if i not in have)
            if user < N_USERS:
                items[user] = np.append(items[user], item)
            seq0 = eng.published_seq
            upd.submit(user, item, 4.0)
            deadline = time.perf_counter() + 30.0
            while eng.published_seq == seq0:
                assert time.perf_counter() < deadline, "no publish"
                time.sleep(0.002)
    finally:
        upd.stop(drain_timeout_s=30.0)
    # ten and two appended: both ride the rung above the resident ones
    users[74], users[65] = users.pop(64), users.pop(63)
    if both:
        index = eng.published_index
        assert index.n_items == N_ITEMS + 3 and index.delta_count >= 3
    return eng, users


@pytest.fixture(scope="module")
def warm_engines():
    """Engines by kind, each built when first asked for."""
    built = {}

    def get(kind):
        if kind not in built:
            built[kind] = warmed(kind)
        return built[kind]

    return get


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_warm_ups_pin_what_they_pinned_at_the_parent(warm_engines, kind):
    eng, _ = warm_engines(kind)
    assert set(eng._pinned) == {pin_key(*pin) for pin in pins_of(kind)}


@pytest.mark.parametrize("kind,bucket,path,pad", [
    (kind, *pin) for kind in KINDS for pin in pins_of(kind)])
def test_a_warmed_engines_dispatch_rides_its_pin(warm_engines, monkeypatch,
                                                 kind, bucket, path, pad):
    eng, users = warm_engines(kind)
    key = pin_key(bucket, path, pad)
    rows = 5 if bucket == 8 else 20
    if pad is None:
        payloads = requests(np.random.default_rng(bucket), rows)
    else:
        # by id for users whose histories the pad holds, the longest of
        # them one the pad below would not; the exact fallback rides the
        # top pad whatever its batch holds
        fit = sorted(n for n in users if n <= pad)
        if path == "exact":
            fit = fit[:2]
        else:
            assert fit[-1] > max([p for p in KINDS[kind][0] if p < pad],
                                 default=-1), (pad, sorted(users))
        payloads = [users[fit[-1]]] + [users[fit[j % len(fit)]]
                                       for j in range(rows - 1)]
    spans = Spans()
    monkeypatch.setattr(engine_module, "TraceAnnotation", spans)
    pin = Calls(eng._pinned[key])
    monkeypatch.setitem(eng._pinned, key, pin)
    compiles = CompileCount()
    if path == "exact":
        faults.install("serving.score=corrupt@nth=1")
    try:
        answers = serve_now(eng, payloads)
    finally:
        faults.clear()
    assert len(answers) == rows
    assert len(pin.args) == 1, "the batch did not ride the pin under its key"
    assert eng._pinned[key] is pin, "the pin was dropped"
    assert compiles.n == 0
    (launch,) = [stats for name, stats in spans.seen
                 if name == "serve.batch.dispatch.launch"]
    assert launch["pinned"] == 1
    # the span names the executable's own module: what a device trace
    # calls its runs, and never the other path's
    module = re.match(r"HloModule (\S+?),", pin.compiled.as_text()).group(1)
    assert launch["program"] == module
    pads, int8 = KINDS[kind]
    other = pin_key(bucket, int8 if path == "exact" else "exact",
                    pads and pads[-1])
    assert module != re.match(
        r"HloModule (\S+?),", eng._pinned[other].as_text()).group(1)
