"""A start walks its histories by run and by part (PR 58): the CSR check
of ``publish(user_seen=)`` and the plan of the grown layout against the
forms they replaced, which stand here as the plain references.

``ServingEngine._checked_seen`` walks the ids in parts of ``CHECK_PART``:
with the part patched down to 4 and 7 ids (tier-1 histories then cross
parts) it accepts and refuses, message for message, what
:func:`checked_as_before` does.  ``ServingEngine._lay_out`` builds ``src``
and ``dst`` from one ``repeat`` of a per-run offset a side: element for
element :func:`planned_as_before`'s, with its runs, its pads and its
account of the room, for a table as published, with spare rows, without a
user, and for one laid out before whose runs have moved."""

import numpy as np
import pytest

from tpu_als import obs
from tpu_als.core.ratings import growth_room
from tpu_als.ops.topk import NOT_AN_ID
from tpu_als.serving import engine as engine_mod
from tpu_als.serving.engine import ServingEngine, history_pads

N_ITEMS = 2000


# -- the check ----------------------------------------------------------------

def checked_as_before(user_seen, n_users, n_items):
    """``_checked_seen`` as it stood until PR 58: an ``int64`` copy of the
    ids, its ``diff``, the row starts excused by a fancy-index write."""
    indptr, indices = (np.asarray(a) for a in user_seen)
    if indptr.shape != (n_users + 1,) or indptr[0] != 0 \
            or indptr[-1] != len(indices) \
            or len(indices) >= NOT_AN_ID:
        raise ValueError(
            f"user_seen: indptr of shape {indptr.shape} ending at "
            f"{indptr[-1] if len(indptr) else None} for {n_users} "
            f"users and {len(indices)} ids")
    lengths = np.diff(indptr)
    ok = bool((lengths >= 0).all())
    if ok and len(indices):
        rises = np.diff(indices.astype(np.int64)) > 0
        starts = indptr[1:-1]
        rises[starts[(starts > 0) & (starts < len(indices))] - 1] = True
        ok = bool(indices.min() >= 0 and indices.max() < n_items
                  and rises.all())
    if not ok:
        raise ValueError(
            "user_seen: every row holds catalog ids in "
            f"[0, {n_items}), ascending, none twice")
    return indptr, indices, lengths


def rows_of(lengths, dtype=np.int32):
    """CSR of rows ``lengths`` long, each ascending from a LOW id, so that
    every row boundary is a fall (allowed)."""
    indptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    indices = np.concatenate(
        [3 * np.arange(n) + 1 + j % 2 for j, n in enumerate(lengths)]
        + [np.empty(0, np.int64)]).astype(dtype)
    return indptr, indices


def one_row(part):
    """One row of three parts and a half, and ten users without a
    rating."""
    return rows_of([3 * part + part // 2] + [0] * 10)


def boundaries(part, dtype=np.int32):
    """Rows that end on a part's last id, on its first and one past it,
    with empty rows at the start, in the middle and at the end."""
    return rows_of([0, 0, part, 1, 0, part - 1, 2, 0, 0, part + 1, 0],
                   dtype)


def planted(make, at, value):
    """``make``'s histories with the id ``at(part, indices)`` replaced:
    ``value(id before it, itself)``."""
    def case(part):
        indptr, indices = make(part)
        p = at(part, indices)
        indices[p] = value(int(indices[p - 1]), int(indices[p]))
        return indptr, indices
    return case


def fall(before, itself):
    return before - 1


def twice(before, itself):
    return before


def with_indptr(make, edit):
    def case(part):
        indptr, indices = make(part)
        return edit(indptr.copy()), indices
    return case


def shorter(indptr):
    return indptr[:-1]


def not_to_the_end(indptr):
    indptr[-1] -= 1
    return indptr


def not_from_zero(indptr):
    indptr[0] = 1
    return indptr


def a_negative_length(indptr):
    # the rows before and after still add up: only the walk would see it
    indptr[3], indptr[4] = indptr[4], indptr[3]
    return indptr


def as_lists(part):
    """What a caller without numpy hands over: no user has a history, and
    the empty list of ids arrives as ``float64``."""
    return [0] * 12, []


def whole_floats(part):
    indptr, indices = boundaries(part)
    return indptr, indices.astype(np.float64)


def a_fraction(part):
    indptr, indices = whole_floats(part)
    indices[1] -= 0.5       # in range, still ascending: no catalog id
    return indptr, indices


def past_int32(part):
    """A ``uint32`` id that an ``int32`` view would read as negative."""
    indptr, indices = boundaries(part, np.uint32)
    indices[-1] = 3_000_000_000
    return indptr, indices


TAKEN = {
    "no_ids": lambda part: rows_of([0] * 11),
    "no_ids_as_lists": as_lists,
    "one_row": one_row,
    "empty_rows_and_falls_across_boundaries": boundaries,
    "int64_ids": lambda part: boundaries(part, np.int64),
    "uint32_ids": lambda part: boundaries(part, np.uint32),
    "uint8_ids": lambda part: boundaries(part, np.uint8),
    "whole_floats": whole_floats,
}
REFUSED = {
    # for its rows
    "fall_at_a_parts_first": planted(one_row, lambda P, ids: P, fall),
    "fall_at_a_parts_last": planted(one_row, lambda P, ids: 2 * P - 1, fall),
    "fall_behind_the_overlap": planted(one_row, lambda P, ids: P + 1, fall),
    "fall_at_the_second_id": planted(one_row, lambda P, ids: 1, fall),
    "fall_at_the_last_id": planted(one_row, lambda P, ids: len(ids) - 1,
                                   fall),
    "twice_at_a_parts_first": planted(one_row, lambda P, ids: 2 * P, twice),
    "twice_inside_a_part": planted(one_row, lambda P, ids: P + 2, twice),
    "twice_in_a_short_row": planted(boundaries, lambda P, ids: 2 * P + 1,
                                    twice),
    "minus_one_first": planted(one_row, lambda P, ids: 0, lambda b, i: -1),
    "minus_one_at_a_parts_first": planted(
        boundaries, lambda P, ids: P, lambda b, i: -1),
    "n_items_at_a_parts_last": planted(
        one_row, lambda P, ids: 3 * P - 1, lambda b, i: N_ITEMS),
    "n_items_at_the_end": planted(
        boundaries, lambda P, ids: len(ids) - 1, lambda b, i: N_ITEMS),
    "a_negative_length": with_indptr(boundaries, a_negative_length),
    "a_fraction": a_fraction,
    "past_int32": past_int32,
    # for its indptr
    "indptr_a_row_short": with_indptr(boundaries, shorter),
    "indptr_not_to_the_end": with_indptr(boundaries, not_to_the_end),
    "indptr_not_from_zero": with_indptr(boundaries, not_from_zero),
}
CASES = TAKEN | REFUSED
# the form before cast 3.5 down to 3 and took it
STRICTER = {"a_fraction"}


@pytest.mark.parametrize("part", [4, 7, engine_mod.CHECK_PART])
@pytest.mark.parametrize("name", list(CASES))
def test_the_check_by_parts_against_the_check_as_it_was(monkeypatch, name,
                                                        part):
    monkeypatch.setattr(engine_mod, "CHECK_PART", part)
    user_seen = CASES[name](min(part, 7))
    n_users = 11
    try:
        want = checked_as_before(user_seen, n_users, N_ITEMS)
    except ValueError as e:
        want = str(e)
    if name in STRICTER:
        assert not isinstance(want, str)
        want = ("user_seen: every row holds catalog ids in "
                f"[0, {N_ITEMS}), ascending, none twice")
    assert isinstance(want, str) == (name in REFUSED)
    if name.startswith("indptr"):
        assert "indptr of shape" in want
    if isinstance(want, str):
        with pytest.raises(ValueError) as refused:
            ServingEngine._checked_seen(user_seen, n_users, N_ITEMS)
        assert str(refused.value) == want
        return
    indptr, indices, lengths = ServingEngine._checked_seen(
        user_seen, n_users, N_ITEMS)
    np.testing.assert_array_equal(indptr, want[0])
    np.testing.assert_array_equal(indices, want[1])
    np.testing.assert_array_equal(lengths, want[2])
    assert lengths.dtype == want[2].dtype
    if want[1].dtype.kind in "iu":
        # the caller's arrays themselves: nothing an id wide was copied
        assert indices is np.asarray(user_seen[1])


def test_the_phases_say_how_many_ids_they_walked():
    reg = obs.reset()
    eng = ServingEngine(k=5, buckets=(8,))
    indptr, indices = boundaries(7)
    seen = eng._place_seen((indptr, indices), 11, N_ITEMS)
    eng._lay_out(seen, 16)
    spans = {e["name"]: e for e in reg._events if e["type"] == "span"}
    check = spans["start.publish.histories.check"]
    assert (check["ids"], check["parts"]) == (len(indices), 1)
    assert spans["start.warmup_histories.plan"]["ids"] == len(indices)


# -- the plan -----------------------------------------------------------------

def planned_as_before(seen, rows, more=0):
    """``_lay_out``'s plan as it stood until PR 58: every id's user and
    its place within the run, two gathers."""
    n = len(seen.lengths)
    lengths = np.zeros(rows, np.int32)
    lengths[:n] = seen.lengths
    cap = lengths + growth_room(lengths)
    cap[n:] = 0
    start = np.zeros(rows, np.int64)
    np.cumsum(cap[:-1], out=start[1:])
    held = int(start[-1] + cap[-1])
    size = held + max(1 << 16, held >> 3) + int(more)
    pads = history_pads(lengths.max(initial=0), grows=True)
    old = (np.asarray(seen.runs)[:-1] if seen.room is None
           else seen.room.start[:n])
    user = np.repeat(np.arange(n), seen.lengths)
    within = (np.arange(len(user))
              - np.repeat(np.cumsum(seen.lengths) - seen.lengths,
                          seen.lengths))
    src, dst = ((a[user] + within).astype(np.int32) for a in (old, start))
    return dict(src=src, dst=dst, start=start, cap=cap, held=held, size=size,
                pads=pads, lengths=lengths)


def laid_out_against_the_plan_before(eng, seen, rows, monkeypatch, more=0):
    want = planned_as_before(seen, rows, more)
    sent = {}

    def spread(indices, src, dst, *, size):
        sent.update(src=np.asarray(src), dst=np.asarray(dst), size=size)
        return spread_runs(indices, src, dst, size=size)

    spread_runs = engine_mod._spread_runs
    monkeypatch.setattr(engine_mod, "_spread_runs", spread)
    got = eng._lay_out(seen, rows, more)
    for side in ("src", "dst"):
        assert sent[side].dtype == np.int32
        np.testing.assert_array_equal(sent[side], want[side])
    assert sent["size"] == want["size"] + want["pads"][-1]
    start, count = (np.asarray(a) for a in got.runs)
    assert start.dtype == count.dtype == np.int32
    np.testing.assert_array_equal(start, want["start"])
    np.testing.assert_array_equal(count, want["lengths"])
    np.testing.assert_array_equal(got.lengths, want["lengths"])
    assert got.pads == want["pads"]
    room = got.room
    assert room.start.dtype == want["start"].dtype
    np.testing.assert_array_equal(room.start, want["start"])
    np.testing.assert_array_equal(room.cap, want["cap"])
    assert (room.free, room.size) == (want["held"], want["size"])
    # and the ids lie where the plan says
    table = np.asarray(got.indices)
    held = np.full(len(table), NOT_AN_ID, np.int32)
    held[want["dst"]] = np.asarray(seen.indices)[want["src"]]
    np.testing.assert_array_equal(table, held)
    return got


LENGTHS = {
    "mixed": [3, 0, 0, 70, 1, 9, 0, 64, 513, 2, 0],
    "without_a_rating": [0] * 9,
    "one_user": [5],
    "no_user": [],
}


# (a table of no rows has no plan, nor had one)
@pytest.mark.parametrize("lengths,spare", [
    (name, spare) for name in LENGTHS for spare in (0, 1, 37)
    if LENGTHS[name] or spare])
def test_the_plan_by_run_of_a_table_as_published(monkeypatch, lengths,
                                                 spare):
    lengths = LENGTHS[lengths]
    eng = ServingEngine(k=5, buckets=(8,))
    seen = eng._place_seen(rows_of(lengths), len(lengths), N_ITEMS)
    assert seen.room is None
    laid_out_against_the_plan_before(eng, seen, len(lengths) + spare,
                                     monkeypatch, more=spare)


def test_the_plan_by_run_of_a_table_whose_runs_have_moved(monkeypatch):
    """Histories laid out to grow, appended to until runs have moved to
    the free room (a run's start then lies anywhere), and laid out again:
    the second plan reads ``room.start``."""
    reg = obs.reset()
    rng = np.random.default_rng(58)
    lengths = LENGTHS["mixed"]
    n = len(lengths)
    U = rng.standard_normal((n, 8)).astype(np.float32)
    V = rng.standard_normal((N_ITEMS, 8)).astype(np.float32)
    eng = ServingEngine(k=5, buckets=(8,))
    eng.publish(U, V, user_seen=rows_of(lengths))
    have = {u: 3 * lengths[u] + 3 for u in range(n)}    # above a row's ids
    for step in range(40):
        # users 0 and 5 outgrow their room twice, the users between once
        users = [0, 5] if step % 2 else [0, 1, 4, 5]
        items = [have[u] for u in users]
        for u in users:
            have[u] += 1
        eng.publish_update(U, V, touched_users=users,
                           seen_appended=(users, items))
    assert reg.counter_value("live.history_relocations") >= 4
    seen = eng._model.seen
    assert seen.room is not None
    assert (np.diff(seen.room.start[:n]) < 0).any()     # runs anywhere
    rows = int(eng._model.U.shape[0])
    got = laid_out_against_the_plan_before(eng, seen, rows, monkeypatch,
                                           more=11)
    # every user's history, in the order it grew
    table, start = np.asarray(got.indices), got.room.start
    for u in range(n):
        want = np.concatenate([rows_of(lengths)[1][
            sum(lengths[:u]):sum(lengths[:u + 1])],
            np.arange(3 * lengths[u] + 3, have[u])])
        np.testing.assert_array_equal(
            table[start[u]:start[u] + got.lengths[u]], want)
