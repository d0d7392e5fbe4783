"""Tick frame: the batched live replication plane (ISSUE 7).

Three layers of coverage:

1. Randomized differential suite (>= 10k cases): the batched
   tick-frame commit decision must be IDENTICAL to
   quorum_scalar.leader_commit_index for every generated row —
   joint-consensus old/new voter sets, learners, NO_OFFSET sentinels
   and term-start gating included. quorum_scalar is the oracle; the
   frame is the hot path.
2. TickFrame mechanics: enqueue coalescing, loop-soon flush,
   heartbeat-fold merging, callback routing, freed-row masking.
3. The grow-prewarm regression (satellite): after a capacity grow on
   the device backend, the next tick must NOT pay a fresh XLA
   trace/compile — _grow prewarms the new shape on the control plane.
"""

import asyncio
import os

import numpy as np
import pytest

from redpanda_tpu.models.consensus_state import SELF_SLOT, GroupState
from redpanda_tpu.raft import quorum_scalar as qs
from redpanda_tpu.raft.shard_state import NO_OFFSET, ShardGroupArrays
from redpanda_tpu.raft.tick_frame import TickFrame
from test_devplane import _run_armed


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _fill_random(arrays, rows, rng, joint_prob=0.25, learner_prob=0.3):
    """Randomize quorum-relevant lanes for `rows`. Every row keeps
    SELF as a current voter (a leader is always in its own config);
    other slots mix voters, joint old-config voters, learners
    (tracked but non-voting) and NO_OFFSET sentinels."""
    g = len(rows)
    r = arrays.replica_slots
    match = rng.integers(-1, 1000, (g, r)).astype(np.int64)
    flushed = match - rng.integers(0, 50, (g, r)).astype(np.int64)
    np.maximum(flushed, NO_OFFSET, out=flushed)
    # sprinkle NO_OFFSET sentinels (never-acked slots)
    sent = rng.random((g, r)) < 0.15
    match[sent] = NO_OFFSET
    flushed[sent] = NO_OFFSET
    voter = rng.random((g, r)) < 0.6
    voter[:, SELF_SLOT] = True
    # learners: value-bearing slots with no voter flags happen
    # naturally where voter is False (prob ~learner_prob after joint)
    old = np.zeros((g, r), bool)
    joint = rng.random(g) < joint_prob
    old[joint] = rng.random((int(joint.sum()), r)) < 0.5
    is_leader = rng.random(g) < 0.85
    commit = rng.integers(-1, 500, g).astype(np.int64)
    term_start = rng.integers(0, 600, g).astype(np.int64)
    arrays.match_index[rows] = match
    arrays.flushed_index[rows] = flushed
    arrays.is_voter[rows] = voter
    arrays.is_voter_old[rows] = old
    arrays.is_leader[rows] = is_leader
    arrays.commit_index[rows] = commit
    arrays.term_start[rows] = term_start
    arrays.last_visible[rows] = commit
    arrays.voter_epoch += 1
    arrays.touch()


def _oracle_commits(arrays, rows):
    """Expected post-frame commit per row via quorum_scalar — the
    same replica construction as scalar_commit_update."""
    out = np.empty(len(rows), np.int64)
    for k, row in enumerate(rows):
        if not arrays.is_leader[row]:
            out[k] = arrays.commit_index[row]
            continue
        replicas = [
            qs.ReplicaState(
                match_index=int(arrays.match_index[row, s]),
                flushed_index=int(arrays.flushed_index[row, s]),
                is_voter=bool(arrays.is_voter[row, s]),
                is_voter_old=bool(arrays.is_voter_old[row, s]),
            )
            for s in range(arrays.replica_slots)
            if arrays.is_voter[row, s] or arrays.is_voter_old[row, s]
        ]
        out[k] = qs.leader_commit_index(
            replicas,
            leader_flushed=int(arrays.flushed_index[row, SELF_SLOT]),
            commit_index=int(arrays.commit_index[row]),
            term_start=int(arrays.term_start[row]),
        )
    return out


class TestDifferential:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_frame_commit_matches_scalar_oracle_10k(self, seed):
        """>= 10k randomized rows (5 seeds x 2048): frame_tick's
        commit decision == quorum_scalar.leader_commit_index, and the
        advanced-row set matches exactly."""
        g = 2048
        arrays = ShardGroupArrays(capacity=g)
        rows = np.array([arrays.alloc_row() for _ in range(g)], np.int64)
        rng = np.random.default_rng(seed)
        _fill_random(arrays, rows, rng)
        before = arrays.commit_index[rows].copy()
        expected = _oracle_commits(arrays, rows)
        # quorum_dirty is set by alloc/reset; clear it and use the
        # tick frame's force path, the live enqueue route
        arrays.quorum_dirty[:] = False
        advanced = arrays.frame_tick(
            np.empty(0, np.int64),
            np.empty(0, np.int64),
            np.empty(0, np.int64),
            np.empty(0, np.int64),
            np.empty(0, np.int64),
            force_rows=rows,
        )
        np.testing.assert_array_equal(arrays.commit_index[rows], expected)
        exp_adv = set(rows[expected > before].tolist())
        assert set(int(r) for r in advanced) == exp_adv

    def test_reply_schedule_differential(self):
        """Streamed replies through the enqueue route (cells folded
        inline, quorum batched): every flush lands on the oracle's
        answer, including stale-seq replies that must not move it."""
        g, rounds = 64, 40
        arrays = ShardGroupArrays(capacity=g)
        rows = np.array([arrays.alloc_row() for _ in range(g)], np.int64)
        rng = np.random.default_rng(7)
        _fill_random(arrays, rows, rng, joint_prob=0.3)
        arrays.is_leader[rows] = True  # keep replies meaningful
        arrays.quorum_dirty[:] = False
        arrays.frame_tick(*([np.empty(0, np.int64)] * 5), force_rows=rows)
        frame = TickFrame(arrays)
        for _ in range(rounds):
            for _ in range(rng.integers(1, 64)):
                row = int(rows[rng.integers(0, g)])
                slot = int(rng.integers(0, arrays.replica_slots))
                dirty = int(rng.integers(-1, 1200))
                flushed = max(dirty - int(rng.integers(0, 30)), -1)
                # stale ~25% of the time: seq at-or-below the lane
                stale = rng.random() < 0.25
                last = int(arrays.last_seq[row, slot])
                seq = last if stale else last + 1
                # mirror process_append_reply: inline cell fold behind
                # the seq guard, then enqueue
                if seq <= last:
                    continue
                arrays.last_seq[row, slot] = seq
                arrays.match_index[row, slot] = max(
                    int(arrays.match_index[row, slot]), dirty
                )
                arrays.flushed_index[row, slot] = max(
                    int(arrays.flushed_index[row, slot]), flushed
                )
                arrays.touch()
                frame.enqueue_reply(row, slot, dirty, flushed, seq)
            frame.flush()
            np.testing.assert_array_equal(
                arrays.commit_index[rows], _oracle_commits(arrays, rows)
            )

    @pytest.mark.parametrize("n_forced", [1, 8, 9, 40, 96])
    def test_host_device_frame_identical(self, monkeypatch, n_forced):
        """Backend parity for frame_tick: byte-identical advanced set,
        commit_index, last_visible and health lanes host vs device, at
        every bucket from 8 up to the capacity's."""
        g = 96
        results = {}
        for backend in ("host", "device"):
            monkeypatch.setenv("RP_QUORUM_BACKEND", backend)
            arrays = ShardGroupArrays(capacity=g)
            rows = np.array([arrays.alloc_row() for _ in range(g)], np.int64)
            rng = np.random.default_rng(11)
            _fill_random(arrays, rows, rng)
            arrays.quorum_dirty[:] = False
            advanced = arrays.frame_tick(
                *([np.empty(0, np.int64)] * 5), force_rows=rows[:n_forced]
            )
            results[backend] = (
                np.sort(np.asarray(advanced)).tobytes(),
                arrays.commit_index[rows].tobytes(),
                arrays.last_visible[rows].tobytes(),
                *(getattr(arrays, lane).tobytes() for lane in _HEALTH_LANES),
            )
        assert results["host"] == results["device"]


_EMPTY = np.empty(0, np.int64)
# what the device fold reads back beside the lanes (host_tick computes
# them on the host): the same values, row for row
_HEALTH_LANES = ("health_max_lag", "health_under", "health_leaderless")


class _Trio:
    """Three ShardGroupArrays driven through one history: `host` folds
    with host_tick, `dev` with the device backend and its resident
    state, `fresh` with the device backend and the resident state
    dropped before every fold (a whole upload each time: what the
    device backend did before it kept a state)."""

    def __init__(self, monkeypatch, capacity):
        self.mp = monkeypatch
        self.names = ("host", "dev", "fresh")
        self.arrays = {}
        for name in self.names:
            self._backend(name)
            self.arrays[name] = ShardGroupArrays(capacity=capacity)

    def _backend(self, name):
        self.mp.setenv("RP_QUORUM_BACKEND", "host" if name == "host" else "device")

    def each(self, fn):
        """Apply one host-side write to all three (under each one's
        own backend: alloc_row can grow, and _grow prewarms)."""
        out = None
        for name in self.names:
            self._backend(name)
            out = fn(self.arrays[name])
        return out

    def fold(self, window, force):
        adv = {}
        for name in self.names:
            self._backend(name)
            a = self.arrays[name]
            if name == "host":
                adv[name] = a.host_tick(*window, force_rows=force)
            else:
                if name == "fresh":
                    a._resident = None
                adv[name] = a.device_tick(*window, force_rows=force)
        return {k: np.sort(np.asarray(v)) for k, v in adv.items()}

    def assert_same(self, adv, against, step):
        a, b = self.arrays["dev"], self.arrays[against]
        np.testing.assert_array_equal(
            adv["dev"], adv[against], err_msg=f"advanced rows, step {step}"
        )
        for lane in GroupState._fields + _HEALTH_LANES:
            np.testing.assert_array_equal(
                getattr(a, lane), getattr(b, lane),
                err_msg=f"{lane} against {against}, step {step}",
            )


def _random_group(a, row, rng):
    """Make `row` a group with SELF and a random set of other voters,
    sometimes in a joint configuration."""
    r = a.replica_slots
    voters = rng.random(r) < 0.6
    voters[SELF_SLOT] = True
    a.is_voter[row] = voters
    a.is_voter_old[row] = (rng.random(r) < 0.5) & (rng.random() < 0.3)
    a.voter_epoch += 1
    a.touch()


def _host_write(trio, row, rng):
    """One of the writes the broker makes to a row between folds, the
    same bytes on all three. None marks the row quorum_dirty: the
    caller passes it as a forced row at a later fold, as the tick
    frame does for a row whose lanes the broker wrote itself."""
    kind = int(rng.integers(0, 7))
    draw = rng.integers(0, 1 << 30)

    def write(a):
        g = np.random.default_rng(draw)
        if kind == 0:  # term change
            a.term[row] += 1
            a.term_start[row] = int(a.match_index[row, SELF_SLOT]) + 1
        elif kind == 1:  # become or lose leader
            a.is_leader[row] = not a.is_leader[row]
        elif kind == 2:  # voter or joint-configuration change
            _random_group(a, row, g)
        elif kind == 3:  # reset_row, then a group again
            a.reset_row(row)
            a.row_active[row] = True
            _random_group(a, row, g)
            a.is_leader[row] = True
        elif kind == 4:  # free and re-allocate (the free list hands it back)
            a.free_row(row)
            assert a.alloc_row() == row
            _random_group(a, row, g)
            a.is_leader[row] = bool(g.random() < 0.8)
        else:  # SELF-slot append, then flush
            a.match_index[row, SELF_SLOT] += int(g.integers(1, 20))
            if kind == 6:
                a.flushed_index[row, SELF_SLOT] = a.match_index[row, SELF_SLOT]
        a.quorum_dirty[row] = False
        a.touch()

    trio.each(write)


class TestResidentState:
    """The device backend keeps its GroupState on the device and
    exchanges only the rows a fold touches (ISSUE 26). Whatever the
    host wrote meanwhile to rows a fold does not touch, every fold has
    to leave the mirrors and the advanced rows of a whole upload."""

    @pytest.mark.parametrize("cap", [16, 64])
    @pytest.mark.parametrize("seed", [3, 17, 2026])
    @pytest.mark.parametrize("by_the_rules", [True, False],
                             ids=["host_and_fresh", "fresh_wild"])
    def test_folds_between_host_writes(self, monkeypatch, seed, by_the_rules, cap):
        """A seeded history of folds and host-side writes, with a
        capacity doubling in the middle, from 16 rows and from 64.
        `host_and_fresh`: a written
        row gets no reply before a fold is forced to recompute it (the
        broker's own rule, and what makes host_tick's incremental
        sweep comparable), and all three agree after every fold.
        `fresh_wild`: replies land on written rows at once; the
        resident state still agrees with a whole upload."""
        rng = np.random.default_rng(seed)
        steps = 36
        trio = _Trio(monkeypatch, cap)
        rows = [trio.each(lambda a: a.alloc_row()) for _ in range(cap)]
        for row in rows:
            draw = rng.integers(0, 1 << 30)

            def setup(a, row=row, draw=draw):
                _fill_random(a, np.array([row]), np.random.default_rng(draw))
                a.last_seq[row] = 0

            trio.each(setup)
        trio.fold((_EMPTY,) * 5, None)  # the dirty rows, and the seed
        waiting: list[int] = []  # written, not yet forced
        for step in range(steps):
            if step == steps // 2:
                # one row past the capacity: every lane doubles, the resident state goes
                rows.append(trio.each(lambda a: a.alloc_row()))
                assert trio.arrays["dev"].capacity == 2 * cap
                assert trio.arrays["dev"]._resident is not None  # _grow's prewarm
                draw = rng.integers(0, 1 << 30)

                def fill_new(a, draw=draw):
                    _fill_random(a, np.array(rows[-1:]), np.random.default_rng(draw))
                    a.quorum_dirty[rows[-1]] = False

                trio.each(fill_new)
                waiting.append(rows[-1])
            kind = step % 6
            n = 0 if kind == 0 else int(rng.integers(1, 40))
            pool = [r for r in rows if not (by_the_rules and r in waiting)]
            g_rows = rng.choice(pool, n).astype(np.int64)
            g_slots = rng.integers(1, 8, n).astype(np.int64)
            if n > 3:  # duplicate reply pairs in one window
                g_rows[-2:] = g_rows[:2]
                g_slots[-2:] = g_slots[:2]
            last = trio.arrays["dev"].last_seq[g_rows, g_slots]
            g_seqs = last + rng.integers(-1, 3, n)  # stale ones among them
            g_dirty = rng.integers(-1, 1500, n).astype(np.int64)
            g_flushed = np.maximum(g_dirty - rng.integers(0, 40, n), -1)
            force = None
            if kind in (0, 3) and waiting:
                k = int(rng.integers(1, len(waiting) + 1))
                force, waiting = np.array(waiting[:k], np.int64), waiting[k:]
            if kind == 5:  # nothing at all: an all-padding program
                g_rows = g_slots = g_dirty = g_flushed = g_seqs = _EMPTY
            touched = set(g_rows.tolist()) | set(
                [] if force is None else force.tolist())
            for row in rng.choice(rows, int(rng.integers(0, 5)), replace=False):
                if int(row) not in touched:
                    _host_write(trio, int(row), rng)
                    if int(row) not in waiting:
                        waiting.append(int(row))
            adv = trio.fold((g_rows, g_slots, g_dirty, g_flushed, g_seqs), force)
            trio.assert_same(adv, "fresh", step)
            for lane in ("_folded_self_m", "_folded_self_f", "quorum_dirty"):
                np.testing.assert_array_equal(
                    getattr(trio.arrays["dev"], lane),
                    getattr(trio.arrays["fresh"], lane), err_msg=lane)
            if by_the_rules:
                trio.assert_same(adv, "host", step)


_ARMED_PRELUDE = """\
import numpy as np
from redpanda_tpu.observability import devplane
from redpanda_tpu.raft.shard_state import ShardGroupArrays
from redpanda_tpu.utils import compileguard

assert devplane.enabled()
moved = []  # every (direction, bytes) the program counts
count = devplane.count_transfer
devplane.count_transfer = lambda n, d: (moved.append((d, n)), count(n, d))[1]
EMPTY = np.empty(0, np.int64)


def group(a, n):
    rows = np.array([a.alloc_row() for _ in range(n)], np.int64)
    a.is_leader[rows] = True
    a.is_voter[rows, :3] = True
    a.voter_epoch += 1
    return rows


def replies(rows, seq, slots=(1, 2)):
    r = np.repeat(rows, len(slots))
    s = np.tile(np.array(slots, np.int64), len(rows))
    off = np.full(len(r), seq, np.int64)
    return r, s, off, off, off
"""

_FOLD_COST = """\
per_fold = {}
for cap in (64, 2048):
    a = ShardGroupArrays(capacity=cap)
    rows = group(a, 40)
    a.device_tick(EMPTY, EMPTY, EMPTY, EMPTY, EMPTY)  # 40 dirty rows, the seed
    devplane.reset()
    del moved[:]
    shapes = []
    for seq, n in enumerate((1, 3, 4, 5, 20, 40), start=1):
        a.match_index[rows[:n], 0] = seq
        a.flushed_index[rows[:n], 0] = seq
        adv = a.device_tick(*replies(rows[:n], seq))
        assert sorted(adv) == list(rows[:n]), (cap, n, adv)
        # one upload and one readback a fold, whatever the capacity
        assert [d for d, _ in moved] == ["h2d", "d2h"], moved
        shapes.append(tuple(b for _, b in moved))
        del moved[:]
    per_fold[cap] = shapes
    st = devplane.status()
    assert st["state_seeds"] == 0, st["state_seeds"]
    assert st["host"]["tick.upload"]["count"] == 6, st["host"]
    assert st["host"]["tick.readback"]["count"] == 6, st["host"]
# the bytes follow the bucket and the slots, not the lanes' capacity
assert per_fold[64] == per_fold[2048], per_fold
slots = a.replica_slots
for n, (up, down) in zip((1, 3, 4, 5, 20, 40), per_fold[64]):
    bucket = max(8, 1 << (2 * n - 1).bit_length())
    assert up == bucket * (13 + 5 * slots) * 8, (n, up)
    assert down == bucket * (5 + 3 * slots) * 8, (n, down)
print("FOLD-COST-OK")
"""

_SEED_COUNT = """\
a = ShardGroupArrays(capacity=16)
rows = group(a, 16)
devplane.reset()
for seq in range(1, 41):
    pick = rows[seq % 5 :: 5]
    a.match_index[pick, 0] = seq
    a.flushed_index[pick, 0] = seq
    a.term[rows[(seq + 1) % 5 :: 5]] += 1  # host writes beside the fold
    adv = a.device_tick(*replies(pick, seq))
    assert sorted(adv) == list(pick), (seq, adv)
assert devplane.status()["state_seeds"] == 1
seeds = [s[7]["seed"] for s in devplane.status()["spans"] if s[0] == "tick.upload"]
assert seeds == [1] + [0] * 39, seeds
extra = a.alloc_row()  # 17th row: _grow, and its prewarm seeds again
assert a.capacity == 32
assert devplane.status()["state_seeds"] == 2
a.match_index[rows, 0] = 50
a.flushed_index[rows, 0] = 50
adv = a.device_tick(*replies(rows, 50))
assert sorted(adv) == list(rows), adv
assert devplane.status()["state_seeds"] == 2
print("SEED-COUNT-OK")
"""

_PREWARM = """\
# (capacity, max_replies, programs): a program a power of two from 8
# up to the larger of the capacity and the largest window's bucket
for cap, most, programs in ((32, 100, 5), (64, 10, 4)):
    a = ShardGroupArrays(capacity=cap)
    rows = group(a, cap)
    compileguard.reset()
    devplane.reset()
    a.prewarm(max_replies=most)
    warmed = devplane.status()["compiles"]["quorum.heartbeat_tick"]
    assert warmed["warmup"] >= programs and warmed["steady"] == 0, warmed
    compileguard.steady()
    devplane.reset()
    folds = [(0, 0), (1, 0), (8, 0), (9, 3), (most // 3, 0), (most // 2, cap),
             (most, 0), (0, cap), (2, cap // 2 + 1)]
    for seq, (n_replies, n_dirty) in enumerate(folds, start=1):
        r = np.resize(rows, n_replies)
        s = np.resize(np.arange(1, 8, dtype=np.int64), n_replies)
        off = np.full(n_replies, seq, np.int64)
        a.mark_quorum_dirty(rows[:n_dirty])
        a.device_tick(r, s, off, off, off, force_rows=rows[:1])
    assert compileguard.reports() == [], compileguard.reports()
    st = devplane.status()
    assert st["compiles"] == {}, st["compiles"]  # none of either phase
    assert st["state_seeds"] == 0
print("PREWARM-OK")
"""


class TestResidentCost:
    """What a fold moves, counted by the armed devplane (an
    import-time latch, so each case is a process of its own)."""

    @pytest.mark.parametrize(
        "body, token, sample",
        [(_FOLD_COST, "FOLD-COST-OK", "16"),
         (_SEED_COUNT, "SEED-COUNT-OK", "1"),
         (_PREWARM, "PREWARM-OK", "16")],
        ids=["bytes_follow_the_bucket", "one_seed_then_one_a_grow",
             "prewarm_covers_every_bucket"],
    )
    def test_armed(self, tmp_path, body, token, sample):
        out = _run_armed(
            tmp_path, _ARMED_PRELUDE + body,
            {"RP_DEVPLANE_SAMPLE": sample, "RP_COMPILEGUARD": "1",
             "RP_QUORUM_BACKEND": "device"},
        )
        assert out.returncode == 0, out.stderr[-4000:]
        assert token in out.stdout


class TestTickFrame:
    def test_enqueue_defers_then_flush_advances_and_calls_back(self):
        arrays = ShardGroupArrays(capacity=8)
        row = arrays.alloc_row()
        arrays.is_leader[row] = True
        arrays.is_voter[row, 0] = True
        arrays.is_voter[row, 1] = True
        arrays.is_voter[row, 2] = True
        arrays.match_index[row, SELF_SLOT] = 10
        arrays.flushed_index[row, SELF_SLOT] = 10
        arrays.voter_epoch += 1
        arrays.quorum_dirty[:] = False
        fired = []
        frame = TickFrame(arrays)
        frame.register(row, lambda: fired.append(row))
        # reply from slot 1 (cells folded inline, as the consensus
        # ingestion site does), quorum deferred to the frame
        arrays.last_seq[row, 1] = 1
        arrays.match_index[row, 1] = 10
        arrays.flushed_index[row, 1] = 10
        frame.enqueue_reply(row, 1, 10, 10, 1)
        assert arrays.commit_index[row] == NO_OFFSET  # deferred
        assert frame.pending
        advanced = frame.flush()
        assert arrays.commit_index[row] == 10
        assert list(advanced) == [row]
        assert fired == [row]
        assert frame.pending == 0

    def test_scheduled_flush_runs_on_loop_soon(self):
        async def main():
            arrays = ShardGroupArrays(capacity=8)
            row = arrays.alloc_row()
            arrays.is_leader[row] = True
            arrays.is_voter[row, 0] = True
            arrays.match_index[row, SELF_SLOT] = 5
            arrays.flushed_index[row, SELF_SLOT] = 5
            arrays.voter_epoch += 1
            arrays.quorum_dirty[:] = False
            frame = TickFrame(arrays)
            frame.note_self(row)
            assert arrays.commit_index[row] == NO_OFFSET
            await asyncio.sleep(0)  # the call_soon flush runs
            assert arrays.commit_index[row] == 5
            assert frame.flushes == 1

        run(main())

    def test_fold_now_merges_pending_with_tick_batch(self):
        arrays = ShardGroupArrays(capacity=8)
        r1, r2 = arrays.alloc_row(), arrays.alloc_row()
        for row in (r1, r2):
            arrays.is_leader[row] = True
            arrays.is_voter[row, 0] = True
            arrays.is_voter[row, 1] = True
            arrays.match_index[row, SELF_SLOT] = 7
            arrays.flushed_index[row, SELF_SLOT] = 7
        arrays.voter_epoch += 1
        arrays.quorum_dirty[:] = False
        frame = TickFrame(arrays)
        # pending: reply for r1 via the enqueue route
        arrays.last_seq[r1, 1] = 3
        arrays.match_index[r1, 1] = 7
        arrays.flushed_index[r1, 1] = 7
        frame.enqueue_reply(r1, 1, 7, 7, 3)
        # heartbeat tick batch: reply for r2 (not pre-folded — the
        # heartbeat fold path hands raw vectors)
        advanced = frame.fold_now(
            np.array([r2], np.int64),
            np.array([1], np.int64),
            np.array([7], np.int64),
            np.array([7], np.int64),
            np.array([1], np.int64),
        )
        assert sorted(int(r) for r in advanced) == sorted([r1, r2])
        assert arrays.commit_index[r1] == 7
        assert arrays.commit_index[r2] == 7
        assert frame.flushes == 1  # one fused call covered both

    def test_freed_row_pair_is_masked(self):
        arrays = ShardGroupArrays(capacity=8)
        row = arrays.alloc_row()
        arrays.is_leader[row] = True
        arrays.is_voter[row, 0] = True
        arrays.is_voter[row, 1] = True
        arrays.voter_epoch += 1
        frame = TickFrame(arrays)
        frame.register(row, lambda: None)
        frame.enqueue_reply(row, 1, 50, 50, 9)
        # group removed before the flush: the stale pair must not
        # pollute the recycled row's lanes
        frame.deregister(row)
        arrays.free_row(row)
        row2 = arrays.alloc_row()
        assert row2 == row  # recycled
        arrays.quorum_dirty[:] = False
        frame.flush()
        assert arrays.match_index[row2, 1] == NO_OFFSET
        assert arrays.last_seq[row2, 1] == 0

    def test_column_growth_past_initial_capacity(self):
        arrays = ShardGroupArrays(capacity=8)
        row = arrays.alloc_row()
        arrays.is_leader[row] = True
        arrays.is_voter[row, 0] = True
        arrays.is_voter[row, 1] = True
        arrays.match_index[row, SELF_SLOT] = 500
        arrays.flushed_index[row, SELF_SLOT] = 500
        arrays.voter_epoch += 1
        arrays.quorum_dirty[:] = False
        frame = TickFrame(arrays)
        for seq in range(1, 200):  # > the 64-entry initial columns
            arrays.last_seq[row, 1] = seq
            arrays.match_index[row, 1] = seq
            arrays.flushed_index[row, 1] = seq
            frame.enqueue_reply(row, 1, seq, seq, seq)
        frame.flush()
        assert arrays.commit_index[row] == 199
        assert frame.replies_folded == 199

    def test_close_drops_pending(self):
        arrays = ShardGroupArrays(capacity=8)
        row = arrays.alloc_row()
        frame = TickFrame(arrays)
        frame.register(row, lambda: None)
        frame.note_self(row)
        frame.close()
        assert frame.pending == 0
        assert frame.flush() is not None  # no-op, no raise


def _random_rows(arrays, rng):
    """Every row of `arrays` randomized around its own commit index,
    in a range small enough that ties and the rule's edges are common:
    voters and learners, SELF a voter or not, joint configurations,
    followers ahead of and behind the leader's flush, never-acked
    slots, `term_start` on both sides of the commit, and now and then
    a visible offset behind it."""
    g, r = arrays.capacity, arrays.replica_slots
    rows = np.array([arrays.alloc_row() for _ in range(g)], np.int64)
    commit = rng.integers(-1, 40, g).astype(np.int64)
    match = commit[:, None] + rng.integers(-4, 6, (g, r))
    match[:, SELF_SLOT] = commit + rng.integers(0, 8, g)
    flushed = match - rng.integers(0, 4, (g, r))
    never = rng.random((g, r)) < 0.1
    match[never] = NO_OFFSET
    flushed[never] = NO_OFFSET
    arrays.match_index[rows] = np.maximum(match, NO_OFFSET)
    arrays.flushed_index[rows] = np.maximum(flushed, NO_OFFSET)
    voters = rng.random((g, r)) < 0.7
    voters[:, SELF_SLOT] = rng.random(g) < 0.9
    arrays.is_voter[rows] = voters
    joint = rng.random(g) < 0.2
    arrays.is_voter_old[rows] = joint[:, None] & (rng.random((g, r)) < 0.5)
    arrays.is_leader[rows] = rng.random(g) < 0.9
    arrays.commit_index[rows] = commit
    arrays.term_start[rows] = commit + rng.integers(-3, 6, g)
    behind = rng.random(g) < 0.05
    arrays.last_visible[rows] = np.where(
        behind, commit - 1, commit + rng.integers(0, 4, g)
    )
    arrays.voter_epoch += 1
    return rows


def _lead(arrays, voters, at=10):
    """One leader row, its first `voters` slots voting, every lane and
    the commit at `at`, nothing dirty: a group at rest."""
    row = arrays.alloc_row()
    arrays.is_leader[row] = True
    arrays.is_voter[row, :voters] = True
    arrays.match_index[row, :voters] = at
    arrays.flushed_index[row, :voters] = at
    arrays.commit_index[row] = at
    arrays.last_visible[row] = at
    arrays.voter_epoch += 1
    arrays.quorum_dirty[:] = False
    return row


def _self_move(arrays, row, to):
    arrays.match_index[row, SELF_SLOT] = to
    arrays.flushed_index[row, SELF_SLOT] = to


def _reply(arrays, frame, row, slot, to, seq):
    """One append reply as consensus.process_append_reply ingests it:
    the cells inline, the quorum math to the frame."""
    arrays.last_seq[row, slot] = seq
    arrays.match_index[row, slot] = to
    arrays.flushed_index[row, slot] = to
    frame.enqueue_reply(row, slot, to, to, seq)


class TestSelfMovePredicate:
    """`self_move_can_advance` against the oracle: False is allowed
    only where the scalar rule changes nothing."""

    @pytest.mark.parametrize("seed", range(8))
    def test_false_means_the_scalar_rule_changes_nothing(self, seed):
        rng = np.random.default_rng(2800 + seed)
        said_no = moved_on_yes = 0
        for slots in range(1, 9):
            arrays = ShardGroupArrays(capacity=192, replica_slots=slots)
            for row in _random_rows(arrays, rng):
                lanes = (arrays.match_index, arrays.flushed_index)
                at_rest = [lane[row].copy() for lane in lanes]
                commit = int(arrays.commit_index[row])
                visible = int(arrays.last_visible[row])
                can = arrays.self_move_can_advance(row)
                # the SELF slot as the round left it, then wherever
                # else a flush could have put it: the answer reads the
                # other slots alone, so it has to hold for all of them
                m0, f0 = (int(lane[row, SELF_SLOT]) for lane in lanes)
                for m, f in ((m0, f0), (m0 + 50, m0 + 50), (m0 + 50, f0),
                             (NO_OFFSET, NO_OFFSET)):
                    arrays.match_index[row, SELF_SLOT] = m
                    arrays.flushed_index[row, SELF_SLOT] = f
                    assert can == arrays.self_move_can_advance(row)
                    arrays.scalar_commit_update(row)
                    after = (int(arrays.commit_index[row]),
                             int(arrays.last_visible[row]))
                    if not can:
                        assert after == (commit, visible), (
                            f"seed {seed}, {slots} slots, row {row}: deferred, "
                            f"and the rule moved {(commit, visible)} to {after}"
                        )
                    elif after != (commit, visible):
                        moved_on_yes += 1
                    arrays.commit_index[row] = commit
                    arrays.last_visible[row] = visible
                for lane, was in zip(lanes, at_rest):
                    lane[row] = was
                said_no += not can
        # neither answer is the trivial one
        assert said_no > 100 and moved_on_yes > 100, (said_no, moved_on_yes)

    CASES = {
        # name: (voting slots, what differs from a group at rest, answer)
        "rf3_followers_at_the_commit": (3, {}, False),
        "rf3_one_follower_ahead": (3, {"match": {1: 20}, "flushed": {1: 20}}, True),
        "rf3_one_follower_ahead_unflushed": (3, {"match": {1: 20}}, True),
        "rf5_one_follower_ahead": (5, {"match": {1: 20}, "flushed": {1: 20}}, False),
        "rf5_two_followers_ahead": (
            5, {"match": {1: 20, 3: 20}, "flushed": {1: 20, 3: 20}}, True),
        "rf1": (1, {}, True),
        "rf2_lone_follower_at_the_commit": (2, {}, False),
        "rf3_learner_ahead": (3, {"match": {5: 20}, "flushed": {5: 20}}, False),
        "rf3_joint": (3, {"old": {4: True}}, True),
        "rf3_not_leader": (3, {"leader": False}, True),
        "rf3_visible_behind_commit": (3, {"visible": 9}, True),
        "no_voters": (0, {}, True),
    }

    @pytest.mark.parametrize("name", list(CASES))
    def test_named_rows(self, name):
        voters, change, answer = self.CASES[name]
        arrays = ShardGroupArrays(capacity=8)
        row = _lead(arrays, voters)
        for slot, v in change.get("match", {}).items():
            arrays.match_index[row, slot] = v
        for slot, v in change.get("flushed", {}).items():
            arrays.flushed_index[row, slot] = v
        for slot, v in change.get("old", {}).items():
            arrays.is_voter_old[row, slot] = v
        arrays.is_leader[row] = change.get("leader", True)
        arrays.last_visible[row] = change.get("visible", 10)
        _self_move(arrays, row, 30)
        assert arrays.self_move_can_advance(row) is answer


@pytest.fixture(params=["host", "device"])
def backend(request, monkeypatch):
    monkeypatch.setenv("RP_QUORUM_BACKEND", request.param)
    return request.param


class TestSelfMoveFoldsOnce:
    """The leader's own flush schedules a fold only where the fold
    could advance the row (ISSUE 28), on the host fold and on the
    device program alike."""

    def test_rf3_self_move_rides_the_first_reply_s_fold(self, backend):
        async def main():
            arrays = ShardGroupArrays(capacity=8)
            row = _lead(arrays, 3)
            fired = []
            frame = TickFrame(arrays)
            frame.register(row, lambda: fired.append(int(arrays.commit_index[row])))
            _self_move(arrays, row, 20)
            frame.note_self(row)
            for _ in range(3):
                await asyncio.sleep(0)
            assert (frame.flushes, frame.self_deferred) == (0, 1)
            assert frame.pending == 1 and arrays.commit_index[row] == 10
            _reply(arrays, frame, row, 1, 20, 1)
            await asyncio.sleep(0)
            assert (frame.flushes, frame.self_deferred) == (1, 1)
            assert arrays.commit_index[row] == 20 and arrays.last_visible[row] == 20
            assert fired == [20]
            # the second follower's reply folds and moves nothing
            _reply(arrays, frame, row, 2, 20, 1)
            await asyncio.sleep(0)
            assert fired == [20] and frame.pending == 0

        run(main())

    def test_flush_clamp_release_folds_at_once(self, backend):
        """Both followers flushed the round before the leader's own
        `fsync` returned: the leader's flush was what held the commit,
        so its SELF move is the one that releases it."""
        async def main():
            arrays = ShardGroupArrays(capacity=8)
            row = _lead(arrays, 3)
            fired = []
            frame = TickFrame(arrays)
            frame.register(row, lambda: fired.append(int(arrays.commit_index[row])))
            arrays.match_index[row, SELF_SLOT] = 20  # appended, not flushed
            _reply(arrays, frame, row, 1, 20, 1)
            _reply(arrays, frame, row, 2, 20, 1)
            await asyncio.sleep(0)
            assert frame.flushes == 1 and arrays.commit_index[row] == 10
            assert arrays.last_visible[row] == 20
            _self_move(arrays, row, 20)
            frame.note_self(row)
            await asyncio.sleep(0)
            assert (frame.flushes, frame.self_deferred) == (2, 0)
            assert arrays.commit_index[row] == 20 and fired == [20]

        run(main())

    def test_self_move_rides_a_fold_already_scheduled(self, backend):
        async def main():
            arrays = ShardGroupArrays(capacity=8)
            row, other = _lead(arrays, 3), _lead(arrays, 3)
            frame = TickFrame(arrays)
            _self_move(arrays, other, 20)
            _reply(arrays, frame, other, 1, 20, 1)  # schedules the fold
            _self_move(arrays, row, 20)
            frame.note_self(row)
            assert frame.self_deferred == 0  # not asked: it rides
            await asyncio.sleep(0)
            assert frame.flushes == 1 and frame.pending == 0
            assert arrays.commit_index[other] == 20
            assert arrays.commit_index[row] == 10

        run(main())

    @pytest.mark.parametrize("beat", ["drain", "replies"])
    def test_deferred_row_is_recomputed_by_the_next_heartbeat(self, backend, beat):
        """The second line: a deferred row stays forced, so the
        heartbeat's fold recomputes it from the mirrors even where no
        reply ever named it. Made visible by a lane written behind the
        frame's back, which no writer in the tree does."""
        arrays = ShardGroupArrays(capacity=8)
        row, other = _lead(arrays, 3), _lead(arrays, 3)
        fired = []
        frame = TickFrame(arrays)
        frame.register(row, lambda: fired.append(row))
        _self_move(arrays, row, 20)
        frame.note_self(row)
        assert (frame.flushes, frame.self_deferred, frame.pending) == (0, 1, 1)
        arrays.match_index[row, 2] = 20
        arrays.flushed_index[row, 2] = 20
        if beat == "drain":
            # heartbeat_manager.tick with no reply of its own to fold
            assert frame.pending
            frame.flush()
        else:
            one = np.array([1], np.int64)
            frame.fold_now(np.array([other]), one, one * 10, one * 10, one)
        assert frame.flushes == 1 and frame.pending == 0
        assert arrays.commit_index[row] == 20 and fired == [row]


class TestGrowPrewarm:
    def test_grow_does_not_leave_compile_for_next_tick(self, monkeypatch):
        """Satellite: after _grow on the device backend, the next
        device_tick at the new capacity must reuse a compiled program
        (no fresh trace) — _grow prewarms off the hot path."""
        monkeypatch.setenv("RP_QUORUM_BACKEND", "device")
        from redpanda_tpu.ops.quorum import heartbeat_tick_jit

        cache_size = getattr(heartbeat_tick_jit, "_cache_size", None)
        if cache_size is None:
            pytest.skip("jax jit cache introspection unavailable")
        arrays = ShardGroupArrays(capacity=16)
        rows = [arrays.alloc_row() for _ in range(16)]
        arrays.prewarm()
        for row in rows:
            arrays.is_leader[row] = True
            arrays.is_voter[row, 0] = True
            arrays.is_voter[row, 1] = True
            arrays.match_index[row, SELF_SLOT] = 3
            arrays.flushed_index[row, SELF_SLOT] = 3
        arrays.voter_epoch += 1
        arrays.quorum_dirty[:] = False
        # a real tick at the warmed capacity (compiles the 8-bucket
        # shape if prewarm didn't already)
        arrays.device_tick(
            np.array([rows[0]], np.int64),
            np.array([1], np.int64),
            np.array([3], np.int64),
            np.array([3], np.int64),
            np.array([1], np.int64),
        )
        grow_row = arrays.alloc_row()  # 17th: triggers _grow(32)
        assert arrays.capacity == 32
        warmed = cache_size()
        arrays.quorum_dirty[:] = False
        # the next tick at the grown shape must hit the cache
        arrays.device_tick(
            np.array([rows[1]], np.int64),
            np.array([1], np.int64),
            np.array([3], np.int64),
            np.array([3], np.int64),
            np.array([2], np.int64),
        )
        assert cache_size() == warmed, (
            "device_tick after _grow traced a fresh program — the "
            "grow prewarm regressed (mid-traffic compile stall)"
        )
        arrays.free_row(grow_row)


class TestLiveIntegration:
    def test_single_node_quorum_resolves_through_frame(self, tmp_path):
        """GroupManager wiring end-to-end: acks=-1 replicate resolves
        via the tick frame (deferred quorum), not the scalar path."""
        from redpanda_tpu.raft.group_manager import GroupManager

        async def main():
            async def no_send(dst, method_id, payload, timeout):
                raise RuntimeError("single node: no peers")

            gm = GroupManager(
                node_id=1,
                data_dir=str(tmp_path / "n1"),
                send=no_send,
                election_timeout_s=0.1,
                heartbeat_interval_s=0.02,
            )
            await gm.start()
            c = await gm.create_group(1, [1])
            deadline = asyncio.get_event_loop().time() + 5.0
            while c.role.name != "LEADER":
                if asyncio.get_event_loop().time() > deadline:
                    raise TimeoutError("no leader")
                await asyncio.sleep(0.02)
            from redpanda_tpu.models.record import (
                RecordBatchBuilder,
                RecordBatchType,
            )

            b = RecordBatchBuilder(batch_type=RecordBatchType.raft_data)
            b.add(value=b"v", key=b"k")
            base, last = await c.replicate(b, acks=-1)
            assert c.commit_index >= last
            assert gm.tick_frame.flushes > 0
            await gm.stop()

        run(main())


class TestAppendAggregatorFrameCap:
    """A mass catch-up herd must drain as bounded frames, not one
    jumbo APPEND_ENTRIES_BATCH whose service time exceeds the RPC
    timeout (the lockstep livelock the frame cap exists to prevent)."""

    def test_herd_drains_in_capped_frames(self):
        from redpanda_tpu.raft import append_aggregator as agg_mod
        from redpanda_tpu.raft import types as rt
        from redpanda_tpu.raft.append_aggregator import AppendAggregator

        calls = []

        async def raw_send(peer, method_id, payload, timeout):
            # suspend like a real transport so concurrent dispatches
            # pile into the aggregator queue instead of each winning
            # the uncontended fast path
            await asyncio.sleep(0)
            if method_id == rt.APPEND_ENTRIES_BATCH:
                subs = rt.decode_multi(payload)
                calls.append(len(subs))
                return rt.encode_multi([b"r:" + p for p in subs])
            calls.append(1)
            return b"r:" + payload

        async def main():
            agg = AppendAggregator(raw_send)
            n = int(agg_mod._FRAME_CAP * 2.5) + 7
            sends = [
                agg.send(1, rt.APPEND_ENTRIES, b"p%d" % i, 5.0)
                for i in range(n)
            ]
            replies = await asyncio.gather(*sends)
            # every waiter got ITS OWN reply, in order
            assert replies == [b"r:p%d" % i for i in range(n)]
            # no wire frame carried more than the cap
            assert max(calls) <= agg_mod._FRAME_CAP
            # and the queue really was multiplexed, not sent 1:1
            assert len(calls) < n
            assert sum(calls) == n

        run(main())

    def test_failure_isolated_to_one_frame(self):
        from redpanda_tpu.raft import append_aggregator as agg_mod
        from redpanda_tpu.raft import types as rt
        from redpanda_tpu.raft.append_aggregator import AppendAggregator

        boom = {"armed": 0}

        async def raw_send(peer, method_id, payload, timeout):
            await asyncio.sleep(0)
            if method_id == rt.APPEND_ENTRIES_BATCH:
                boom["armed"] += 1
                if boom["armed"] == 1:
                    raise ConnectionError("first frame dies")
                subs = rt.decode_multi(payload)
                return rt.encode_multi([b"r:" + p for p in subs])
            return b"r:" + payload

        async def main():
            agg = AppendAggregator(raw_send)
            n = agg_mod._FRAME_CAP + 50
            sends = [
                agg.send(1, rt.APPEND_ENTRIES, b"p%d" % i, 5.0)
                for i in range(n)
            ]
            results = await asyncio.gather(*sends, return_exceptions=True)
            failed = [r for r in results if isinstance(r, Exception)]
            ok = [r for r in results if not isinstance(r, Exception)]
            # ONE frame's waiters failed; the rest of the herd still
            # completed on later frames (no all-or-nothing collapse)
            assert failed and ok
            assert len(failed) <= agg_mod._FRAME_CAP

        run(main())
