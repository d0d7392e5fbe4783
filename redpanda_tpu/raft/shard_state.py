"""Per-shard struct-of-arrays raft state + batched sweep driver.

The host-side mirror of models.consensus_state.GroupState: every
per-group scalar the quorum/commit math needs is a row in contiguous
numpy arrays. `Consensus` objects own a row; the heartbeat manager
steps ALL rows with one jitted device call per tick
(ops.quorum.heartbeat_tick_jit) — the reference's per-group loops
(heartbeat_manager.cc:203, consensus.cc:2704) collapsed into one
program (SURVEY.md §3.3, the north-star sweep).

Rows are recycled through a free list; freed rows are neutralized
(is_leader=False, voter masks cleared) so they are no-ops in the sweep.
"""

from __future__ import annotations

import os
import time

import numpy as np

from ..models.consensus_state import (
    DEFAULT_REPLICA_SLOTS,
    SELF_SLOT,
    GroupState,
)
from ..observability import devplane, trace
from ..ops.health import health_reduce_np
from ..utils import compileguard
from . import quorum_scalar as qs

I64_MIN = np.int64(np.iinfo(np.int64).min)
I64_MAX = np.int64(np.iinfo(np.int64).max)
NO_OFFSET = np.int64(-1)

# RP_SAME_DEBUG=1: SAME-frame serves verify a lane fingerprint against
# the armed snapshot — catches write sites that missed touch() at the
# first masked serve (tests flip this module attribute directly)
SAME_DEBUG = os.environ.get("RP_SAME_DEBUG", "0") == "1"

# term-boundary mirror ring per group: the last TB_SLOTS (start_offset,
# term) pairs of the log, so the heartbeat build can answer
# term_at(prev) for every group with one gather instead of per-group
# log walks (heartbeat_manager.cc:203's get_term calls, VERDICT r1 #6)
TB_SLOTS = 8
_EMPTY_ROWS = np.empty(0, np.int64)


def term_at_batch_cached(arrays, cache, rows, prevs):
    """(terms, known, cache') — tb_epoch-guarded incremental cache
    around arrays.term_at_batch. The leader heartbeat build and the
    follower batch check both ask for the same prev vector tick after
    tick; only rows whose prev moved (or any term-boundary change,
    via tb_epoch) recompute. Callers thread `cache'` back in."""
    if (
        cache is not None
        and cache[0] == arrays.tb_epoch
        and len(cache[1]) == len(prevs)
    ):
        _, cprevs, cterms, cknown = cache
        changed = prevs != cprevs
        if changed.any():
            idx = np.flatnonzero(changed)
            t2, k2 = arrays.term_at_batch(rows[idx], prevs[idx])
            cterms = cterms.copy()
            cknown = cknown.copy()
            cterms[idx] = t2
            cknown[idx] = k2
        terms, known = cterms, cknown
    else:
        terms, known = arrays.term_at_batch(rows, prevs)
    return terms, known, (arrays.tb_epoch, prevs.copy(), terms, known)


class ShardGroupArrays:
    def __init__(self, capacity: int = 64, replica_slots: int = DEFAULT_REPLICA_SLOTS):
        self.replica_slots = replica_slots
        self._cap = capacity
        # stored descending so pop() hands rows out ASCENDING: plans
        # built over sequentially created groups then cover dense row
        # ranges, unlocking the slice fast paths in the heartbeat
        # tick/service (row_slice) — fancy gathers over 50k rows cost
        # 4-10x a strided slice
        self._free: list[int] = list(range(capacity - 1, -1, -1))
        self._alloc_count = 0
        g, r = capacity, replica_slots
        self.term = np.zeros(g, np.int64)
        self.is_leader = np.zeros(g, bool)
        self.commit_index = np.full(g, NO_OFFSET, np.int64)
        self.term_start = np.zeros(g, np.int64)
        self.last_visible = np.full(g, NO_OFFSET, np.int64)
        # column-major (order='F'): the heartbeat tick reads/writes
        # whole per-slot COLUMNS (match_index[:, slot]); with C order
        # each such pass strides 8*r bytes and walks the full 3 MB row
        # space at 50k groups — F order makes columns contiguous and
        # the tick's column ops memcpy-fast. Row access (per-group
        # scalar paths) is unaffected semantically.
        self.match_index = np.full((g, r), NO_OFFSET, np.int64, order="F")
        self.flushed_index = np.full((g, r), NO_OFFSET, np.int64, order="F")
        self.is_voter = np.zeros((g, r), bool)
        self.is_voter_old = np.zeros((g, r), bool)
        self.last_seq = np.zeros((g, r), np.int64, order="F")
        # host-only: next request seq per (group, peer slot)
        self.next_seq = np.zeros((g, r), np.int64, order="F")
        # host-only: term-boundary ring (ascending starts; unused slots
        # hold I64_MAX so they never match a <= comparison)
        self.tb_start = np.full((g, TB_SLOTS), I64_MAX, np.int64)
        self.tb_term = np.full((g, TB_SLOTS), -1, np.int64)
        self.tb_count = np.zeros(g, np.int32)
        # host-only follower-side mirrors so the node-batched heartbeat
        # handler answers every group with vector ops (service.py):
        self.last_hb = np.zeros(g, np.float64)  # loop-time of last beat
        self.log_start = np.zeros(g, np.int64)  # log start offset
        self.snap_index = np.full(g, NO_OFFSET, np.int64)
        self.leader_id = np.full(g, -1, np.int64)  # known leader node
        # role mirror (True only for Role.FOLLOWER — candidates must
        # drop to the scalar heartbeat path to step down correctly)
        self.is_follower = np.zeros(g, bool)
        # voter-count cache for the host quorum fold: voter sets change
        # only on (re)configuration, so per-tick mask sums are wasted —
        # bump voter_epoch at every is_voter/is_voter_old write site
        self.voter_epoch = 0
        self._voter_cache: tuple | None = None
        # incremental-sweep change tracking (host_tick): rows whose
        # configuration changed since the last sweep, and the SELF-slot
        # values the sweep last folded (detects local append/fsync
        # progress between ticks — the flush-clamp release)
        # (set through mark_quorum_dirty, which raises _dirty_pending
        # too: the device fold scans the lane only while that is up)
        self.quorum_dirty = np.zeros(g, bool)
        self._dirty_pending = False
        self._folded_self_m = np.full(g, I64_MIN, np.int64)
        self._folded_self_f = np.full(g, I64_MIN, np.int64)
        # coarse mutation epoch over the lanes that feed heartbeat
        # frames and replies (match/flushed/commit/term/role/log_start/
        # snap_index): the quiesced SAME-frame heartbeat path is armed
        # against a snapshot of this counter and de-arms on ANY bump —
        # writers call touch() (write sites) so a steady 50k-group tick
        # can skip every per-row gather/compare. Coarse by design:
        # a false bump costs one full frame, a missed bump is bounded
        # by the manager's forced-full cadence.
        self.mut_epoch = 0
        # node-level suppression count (sum of hb_suppress): lets the
        # tick skip the 50k-row suppress gather when nothing is active
        self.hb_suppress_total = 0
        # SAME-frame liveness coverage: node id whose armed quiesced
        # heartbeat batch covers this row (-1 = none). Written once per
        # arming (scatter amortized over the quiesced window) so the
        # election sweeper credits node-level SAME stamps ONLY to rows
        # the sender's armed batch actually covers — crediting by
        # leader_id alone would let a leader that still SAMEs *other*
        # groups suppress elections for a group it no longer leads.
        self.same_cover_node = np.full(g, -1, np.int64)
        # node-level liveness stamps from HEARTBEAT_SAME frames,
        # merged with per-row last_hb by BOTH the election sweeper and
        # Consensus._last_heartbeat (prevote/vote denial must see
        # quiesced leaders as live, or an isolated node could talk a
        # SAME-quiesced cluster into an election)
        self.node_hb: dict[int, float] = {}
        # term-boundary mirror version: callers caching term_at_batch
        # answers (heartbeat build/check paths) invalidate on change
        self.tb_epoch = 0
        # election scheduling lanes: ONE node-level sweeper scans these
        # instead of one asyncio timer task per group — 3k timer-heap
        # entries cost ~6% of the core at 1k partitions x 3 brokers
        # (r4 sampling profile: events.__lt__ + sleep cancel + role
        # checks). Deadline semantics match the old per-group loop:
        # fire when now-last_hb > timeout*(1+jitter), rate-limited to
        # one attempt per timeout, jitter re-rolled per attempt.
        self.el_timeout = np.full(g, 3600.0, np.float64)
        self.el_jitter = np.zeros(g, np.float64)
        self.last_el = np.zeros(g, np.float64)
        # health lanes (ops.health): refreshed for changed rows by the
        # per-tick fold (host and device alike, from the mirrors);
        # `health_refresh` recomputes all rows on demand. row_active
        # distinguishes allocated rows from free-list residents so a
        # recycled row never reads as a leaderless partition.
        self.row_active = np.zeros(g, bool)
        self.health_max_lag = np.zeros(g, np.int64)
        self.health_under = np.zeros(g, bool)
        self.health_leaderless = np.zeros(g, bool)
        # count of live append/catch-up fibers per follower slot — the
        # heartbeat manager suppresses beats to slots a fiber is
        # actively driving (consensus::suppress_heartbeats /
        # heartbeat_manager.cc needs_heartbeat). A counter, not a
        # timestamp: suppression lifts the moment the fiber exits, so
        # the tick's recovery-fallback role is preserved exactly.
        self.hb_suppress = np.zeros((g, r), np.int32, order="F")
        # mesh backend (RP_QUORUM_BACKEND=mesh): lazily constructed
        # MeshFrame (parallel/mesh_frame), per-chip changed-row
        # counters, and the fleet totals from the last full frame's
        # one cross-chip fold
        self._mesh_frame = None
        self._chip_changed: "np.ndarray | None" = None
        self._mesh_totals: dict | None = None
        self._last_fold_us = 0.0
        # full changed-row set of the last incremental sweep (the
        # advanced-rows return is a subset); per-chip attribution and
        # the mesh tick read it
        self._last_changed = _EMPTY_ROWS
        self._reserving = False
        # device backend: the GroupState that stays on the device
        # between folds (device_tick); None until a fold seeds it
        self._resident: "GroupState | None" = None
        # the fold's packed layout (_fold_layout) and its upload
        # buffers, one a bucket, written again by every fold
        self._layout: tuple | None = None
        self._fold_bufs: dict[int, np.ndarray] = {}

    def touch(self) -> None:
        """Invalidate armed SAME-frame heartbeat state (see mut_epoch)."""
        self.mut_epoch += 1

    def mark_quorum_dirty(self, rows) -> None:
        """Ask the next fold to recompute `rows` (a row or an index
        array) although no offset of theirs moves: a configuration
        change, a row reset or moved. The one way to set the lane."""
        self.quorum_dirty[rows] = True
        self._dirty_pending = True

    # SAME-frame lanes whose writers MUST call touch(); the debug
    # fingerprint (RP_SAME_DEBUG=1) checksums exactly these, so a
    # write site that forgets the bump is caught at the next SAME
    # serve instead of being masked until the forced-full cadence.
    SAME_LANES = (
        "term",
        "is_leader",
        "is_follower",
        "match_index",
        "flushed_index",
        "commit_index",
        "log_start",
        "snap_index",
    )

    def same_fingerprint(self) -> int:
        """CRC over every SAME-relevant lane + the term-boundary epoch.
        Debug-mode invariant: while mut_epoch is unchanged, this value
        must not change — a divergence means some write site missed
        touch() (correctness-by-convention made checkable)."""
        import zlib

        acc = zlib.crc32(str(self.tb_epoch).encode())
        for name in self.SAME_LANES:
            acc = zlib.crc32(
                np.ascontiguousarray(getattr(self, name)).tobytes(), acc
            )
        return acc

    # -- row lifecycle ------------------------------------------------
    def alloc_row(self) -> int:
        if not self._free:
            self._grow()
        row = self._free.pop()
        self._alloc_count += 1
        self.row_active[row] = True
        return row

    def free_row(self, row: int) -> None:
        self.reset_row(row)
        self._free.append(row)
        self._alloc_count -= 1

    def reset_row(self, row: int) -> None:
        self.term[row] = 0
        self.is_leader[row] = False
        self.commit_index[row] = NO_OFFSET
        self.term_start[row] = 0
        self.last_visible[row] = NO_OFFSET
        self.match_index[row] = NO_OFFSET
        self.flushed_index[row] = NO_OFFSET
        self.is_voter[row] = False
        self.is_voter_old[row] = False
        self.last_seq[row] = 0
        self.next_seq[row] = 0
        self.tb_start[row] = I64_MAX
        self.tb_term[row] = -1
        self.tb_count[row] = 0
        self.tb_epoch += 1
        self.last_hb[row] = 0.0
        self.log_start[row] = 0
        self.snap_index[row] = NO_OFFSET
        self.leader_id[row] = -1
        self.is_follower[row] = False
        self.voter_epoch += 1
        self.mark_quorum_dirty(row)
        self._folded_self_m[row] = I64_MIN
        self._folded_self_f[row] = I64_MIN
        self.hb_suppress[row] = 0
        self.el_timeout[row] = 3600.0
        self.el_jitter[row] = 0.0
        self.last_el[row] = 0.0
        self.same_cover_node[row] = -1
        self.row_active[row] = False
        self.health_max_lag[row] = 0
        self.health_under[row] = False
        self.health_leaderless[row] = False
        self.touch()

    # every per-row lane, in one place: _grow resizes them all and
    # migrate_row (cross-chip lane moves) copies them all — adding a
    # lane without listing it here breaks both the same way
    ROW_LANES = (
        "term",
        "is_leader",
        "commit_index",
        "term_start",
        "last_visible",
        "match_index",
        "flushed_index",
        "is_voter",
        "is_voter_old",
        "last_seq",
        "next_seq",
        "tb_start",
        "tb_term",
        "tb_count",
        "last_hb",
        "log_start",
        "snap_index",
        "is_follower",
        "leader_id",
        "quorum_dirty",
        "_folded_self_m",
        "_folded_self_f",
        "hb_suppress",
        "el_timeout",
        "el_jitter",
        "last_el",
        "same_cover_node",
        "row_active",
        "health_max_lag",
        "health_under",
        "health_leaderless",
    )

    def _grow(self) -> None:
        old = self._cap
        new = old * 2
        for name in self.ROW_LANES:
            arr = getattr(self, name)
            shape = (new,) + arr.shape[1:]
            order = (
                "F"
                if arr.ndim == 2
                and arr.flags.f_contiguous
                and not arr.flags.c_contiguous
                else "C"
            )
            grown = np.zeros(shape, arr.dtype, order=order)
            grown[:old] = arr
            if arr.dtype == np.int64 and name in (
                "commit_index",
                "last_visible",
                "match_index",
                "flushed_index",
                "snap_index",
            ):
                grown[old:] = NO_OFFSET
            elif name == "same_cover_node":
                grown[old:] = -1
            elif name == "tb_start":
                grown[old:] = I64_MAX
            elif name in ("tb_term", "leader_id"):
                grown[old:] = -1
            elif name in ("_folded_self_m", "_folded_self_f"):
                grown[old:] = I64_MIN
            elif name == "el_timeout":
                grown[old:] = 3600.0
            setattr(self, name, grown)
        self._free.extend(range(new - 1, old - 1, -1))
        self._cap = new
        self.voter_epoch += 1  # cached voter counts have the old shape
        self._resident = None  # and so has the device's copy of the lanes
        # mid-traffic compile stall fix: _grow runs on the control
        # plane (row allocation), so compiling the device sweep at the
        # new capacity HERE keeps the next live tick at its
        # steady-state cost — without this, the first device_tick
        # after a doubling paid a fresh XLA trace at the new [G, R]
        # shape while heartbeats starved. Host backend compiles
        # nothing, so this is free in the default configuration.
        if not self._reserving and self._backend() in ("device", "mesh"):
            self.prewarm()

    def reserve(self, capacity: int) -> None:
        """Pre-size the row space (control plane, ahead of traffic).
        Mesh deployments MUST pre-size: chip blocks are derived from
        the current capacity (chip_of_rows), so a mid-flight grow
        would remap every (chip, lane) address the placement table
        holds. One prewarm at the final capacity instead of one per
        doubling."""
        if capacity <= self._cap:
            return
        self._reserving = True
        try:
            while self._cap < capacity:
                self._grow()
        finally:
            self._reserving = False
        if self._backend() in ("device", "mesh"):
            self.prewarm()

    @property
    def capacity(self) -> int:
        return self._cap

    # -- term-boundary mirror -----------------------------------------
    def tb_set(self, row: int, bounds: list[tuple[int, int]]) -> None:
        """Replace the row's ring with the LAST TB_SLOTS boundaries of
        `bounds` (ascending (start_offset, term) pairs)."""
        tail = bounds[-TB_SLOTS:]
        n = len(tail)
        self.tb_start[row] = I64_MAX
        self.tb_term[row] = -1
        for i, (start, term) in enumerate(tail):
            self.tb_start[row, i] = start
            self.tb_term[row, i] = term
        self.tb_count[row] = n
        self.tb_epoch += 1

    def tb_note_append(self, row: int, base_offset: int, term: int) -> None:
        """O(1) per-append maintenance: push a boundary when the log
        enters a new term."""
        n = int(self.tb_count[row])
        if n and term <= self.tb_term[row, n - 1]:
            return
        if n == TB_SLOTS:
            self.tb_start[row, :-1] = self.tb_start[row, 1:]
            self.tb_term[row, :-1] = self.tb_term[row, 1:]
            n -= 1
        self.tb_start[row, n] = base_offset
        self.tb_term[row, n] = term
        self.tb_count[row] = n + 1
        self.tb_epoch += 1

    def term_at_batch(
        self, rows: np.ndarray, offsets: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """(terms, known) for entry offsets across many groups in one
        gather. known=False where the ring no longer covers the offset
        (older than the retained boundaries) — callers fall back to the
        per-group log walk for those rare laggards. Offsets < 0 answer
        term -1 (the empty-log sentinel), known=True."""
        starts = self.tb_start[rows]  # [M, K]
        idx = np.count_nonzero(starts <= offsets[:, None], axis=1) - 1
        known = idx >= 0
        terms = self.tb_term[rows, np.clip(idx, 0, None)]
        neg = offsets < 0
        terms = np.where(neg, -1, terms)
        known = known | neg
        return terms, known

    # -- scalar fast path (per-replicate quorum, reference semantics) -
    def scalar_commit_update(self, row: int) -> bool:
        """Recompute commit/visible for one group with the scalar
        backend (quorum_scalar); returns True if commit advanced.
        Bit-identical to the batched kernel (differential-tested)."""
        if not self.is_leader[row]:
            return False
        replicas = []
        for slot in range(self.replica_slots):
            if self.is_voter[row, slot] or self.is_voter_old[row, slot]:
                replicas.append(
                    qs.ReplicaState(
                        match_index=int(self.match_index[row, slot]),
                        flushed_index=int(self.flushed_index[row, slot]),
                        is_voter=bool(self.is_voter[row, slot]),
                        is_voter_old=bool(self.is_voter_old[row, slot]),
                    )
                )
        new_commit = qs.leader_commit_index(
            replicas,
            leader_flushed=int(self.flushed_index[row, SELF_SLOT]),
            commit_index=int(self.commit_index[row]),
            term_start=int(self.term_start[row]),
        )
        advanced = new_commit > self.commit_index[row]
        if advanced:
            self.touch()
        self.commit_index[row] = new_commit
        dirty = qs.leader_majority_dirty(
            replicas, leader_dirty=int(self.match_index[row, SELF_SLOT])
        )
        self.last_visible[row] = max(
            self.last_visible[row], new_commit, dirty if replicas else I64_MIN
        )
        return bool(advanced)

    def self_move_can_advance(self, row: int) -> bool:
        """Could a fold, run now for a SELF-slot move alone, change
        `commit_index` or `last_visible` of `row`? Reads the row's
        mirrors as they stand, any SELF value: False only where the
        scalar rule (quorum_scalar.leader_commit_index,
        leader_majority_dirty) provably changes nothing.

        With one voter set of n voters the quorum value is held by at
        least m = n // 2 + 1 of them, SELF among them at most once, so
        the commit can pass `commit_index` only if m - 1 OTHER voters
        already hold a committed match (min of match and flushed) above
        it, and the majority dirty offset can pass `last_visible` only
        if m - 1 others hold a match above that. `last_visible` also
        follows the commit up, so a row whose visible offset is behind
        its commit is not reasoned about either. Everything else the
        rule reads (the leader's flush clamp, `term_start`) can only
        hold an advance back, never make one, and is left out: True
        errs to a fold. A joint configuration and a row that does not
        lead get True, the answer before this predicate existed."""
        if not self.is_leader[row] or self.is_voter_old[row].any():
            return True
        commit = self.commit_index[row]
        visible = self.last_visible[row]
        if visible < commit:
            return True
        voters = self.is_voter[row]
        others = voters.copy()
        others[SELF_SLOT] = False
        need = int(np.count_nonzero(voters)) // 2  # m - 1
        match = self.match_index[row]
        committed = np.minimum(match, self.flushed_index[row])
        return bool(
            np.count_nonzero(others & (committed > commit)) >= need
            or np.count_nonzero(others & (match > visible)) >= need
        )

    # -- batched device sweep ----------------------------------------
    def to_device_state(self) -> GroupState:
        import jax.numpy as jnp

        lanes = GroupState(
            term=self.term,
            is_leader=self.is_leader,
            commit_index=self.commit_index,
            term_start=self.term_start,
            last_visible=self.last_visible,
            match_index=self.match_index,
            flushed_index=self.flushed_index,
            is_voter=self.is_voter,
            is_voter_old=self.is_voter_old,
            last_seq=self.last_seq,
        )
        if devplane.ENABLED:
            devplane.count_transfer(sum(a.nbytes for a in lanes), "h2d")
        return GroupState(*(jnp.asarray(a) for a in lanes))

    # The host fold is the DEFAULT everywhere; RP_QUORUM_BACKEND=device
    # opts in. The device fold keeps a GroupState resident on the
    # device and exchanges with it only the rows a fold touches, one
    # packed upload and one packed readback a fold (device_tick); the
    # whole lanes go up (to_device_state) only to seed that state, and
    # again after _grow / reserve, prewarm or a change of backend, when
    # its shape may no longer be the mirrors'. The numpy mirrors stay
    # the truth for every host reader and writer. Where the device
    # fold pays against the host's is tools/measure_quorum_crossover.py's
    # to take; the math is differentially tested identical either way,
    # and steady-state ticks skip the fold entirely (incremental sweep).

    def _backend(self) -> str:
        import os

        forced = os.environ.get("RP_QUORUM_BACKEND")
        if forced in ("host", "device", "mesh"):
            return forced
        return "host"

    # -- mesh backend: (chip, lane) addressing ------------------------
    # Reply windows at or past this size run the real sharded mesh
    # program; smaller windows take the incremental chip-local host
    # sweep (identical math, differentially pinned) so a steady tick
    # never pays a device dispatch. RP_MESH_FULL=1 forces the mesh
    # program on every frame (the parity suites and the bench's
    # fold_us measurement).
    MESH_FULL_THRESHOLD = 4096

    @property
    def mesh_frame(self):
        mf = self._mesh_frame
        if mf is None:
            from ..parallel.mesh_frame import MeshFrame

            mf = self._mesh_frame = MeshFrame()
        return mf

    def chip_count(self) -> int:
        """Devices in the live mesh (1 off the mesh backend)."""
        if self._backend() != "mesh":
            return 1
        return self.mesh_frame.n_devices

    def chip_block(self) -> int:
        """Rows per chip under the CURRENT capacity — NamedSharding's
        even contiguous block over the (padded) row axis. The chip of
        a row is derived, not stored: chip = row // chip_block()."""
        n = self.chip_count()
        return -(-self._cap // n) if n > 1 else self._cap

    def chip_of_rows(self, rows) -> np.ndarray:
        """Vectorized row → chip resolution (the derived half of the
        (chip, lane) address the placement table records)."""
        rows = np.asarray(rows, np.int64)
        n = self.chip_count()
        if n <= 1:
            return np.zeros(len(rows), np.int64)
        return rows // self.chip_block()

    def chip_of(self, row: int) -> int:
        """Scalar row → chip (control-plane convenience: leader hints,
        move replies, admin attribution)."""
        n = self.chip_count()
        return int(row) // self.chip_block() if n > 1 else 0

    def alloc_row_on_chip(self, chip: int) -> int:
        """Allocate a row inside one chip's block (the lane-adopt step
        of a cross-chip migration). Unlike alloc_row this NEVER grows:
        growing would remap every existing (chip, lane) address (see
        reserve), so an exhausted block is a hard error the mover
        surfaces as a rollback."""
        n = self.chip_count()
        if chip < 0 or chip >= n:
            raise ValueError(f"no such chip {chip} (mesh has {n})")
        block = self.chip_block()
        lo, hi = chip * block, min((chip + 1) * block, self._cap)
        # _free is stored descending, so the smallest free rows — the
        # density-preserving choice — sit at the END; scan from there
        for i in range(len(self._free) - 1, -1, -1):
            row = self._free[i]
            if lo <= row < hi:
                del self._free[i]
                self._alloc_count += 1
                self.row_active[row] = True
                return row
        raise RuntimeError(
            f"chip {chip} lane block [{lo}, {hi}) exhausted "
            f"(reserve() a larger capacity before moving lanes in)"
        )

    def migrate_row(self, src: int, dst: int) -> None:
        """Copy every per-row lane src → dst (the evacuate/adopt core
        of a cross-chip lane move; control plane — the caller froze the
        group). The src row is NOT freed here: until the caller commits
        the swap, src stays canonical and dst is a disposable copy, so
        rollback is free_row(dst) with nothing lost."""
        for name in self.ROW_LANES:
            arr = getattr(self, name)
            arr[dst] = arr[src]
        # force a quorum recompute at dst and refresh every epoch a
        # row rewrite can invalidate (same set reset_row bumps)
        self.mark_quorum_dirty(dst)
        self._folded_self_m[dst] = I64_MIN
        self._folded_self_f[dst] = I64_MIN
        self.tb_epoch += 1
        self.voter_epoch += 1
        self.touch()

    def _note_chip_changed(self, rows: np.ndarray) -> None:
        if not len(rows):
            return
        n = self.chip_count()
        cc = self._chip_changed
        if cc is None or len(cc) != n:
            cc = self._chip_changed = np.zeros(n, np.int64)
        cc += np.bincount(self.chip_of_rows(rows), minlength=n)

    def mesh_totals(self) -> dict | None:
        """Fleet view from the last full mesh frame's single cross-chip
        fold (None before the first full frame)."""
        return self._mesh_totals

    def lane_attribution(self) -> list[dict]:
        """Per-chip lane attribution for the bench/admin JSON: active
        groups, cumulative changed rows, and the last full-fold wall µs
        (one SPMD program — each chip runs the same frame, so the wall
        time is per-frame, reported on every chip row)."""
        n = self.chip_count()
        active_rows = np.flatnonzero(self.row_active)
        groups = (
            np.bincount(self.chip_of_rows(active_rows), minlength=n)
            if len(active_rows)
            else np.zeros(n, np.int64)
        )
        cc = self._chip_changed
        if cc is None or len(cc) != n:
            cc = np.zeros(n, np.int64)
        return [
            {
                "chip": c,
                "groups": int(groups[c]),
                "changed_rows": int(cc[c]),
                "fold_us": round(self._last_fold_us, 1),
            }
            for c in range(n)
        ]

    def _mesh_tick(
        self,
        group_rows: np.ndarray,
        replica_slots: np.ndarray,
        last_dirty: np.ndarray,
        last_flushed: np.ndarray,
        seqs: np.ndarray,
        force_rows: "np.ndarray | None" = None,
    ) -> np.ndarray:
        """Mesh-backend tick: small windows run the incremental host
        sweep — chip-local BY CONSTRUCTION, since every changed row
        lives in exactly one chip block and the fold never mixes rows —
        while big/forced windows run the real sharded mesh program
        (one device dispatch, one cross-chip totals fold). Under
        RP_DEVPLANE=1 the whole tick runs inside devplane.tick_scope:
        any device dispatch or transfer outside the full frame's
        frame_scope is counted as an RPL018 runtime breach."""
        import os

        full = (
            os.environ.get("RP_MESH_FULL", "0") == "1"
            or len(group_rows) >= self.MESH_FULL_THRESHOLD
        )
        with devplane.tick_scope():
            if not full:
                advanced = self.host_tick(
                    group_rows,
                    replica_slots,
                    last_dirty,
                    last_flushed,
                    seqs,
                    force_rows=force_rows,
                )
                self._note_chip_changed(self._last_changed)
                return advanced
            return self._mesh_full_frame(
                group_rows,
                replica_slots,
                last_dirty,
                last_flushed,
                seqs,
                force_rows=force_rows,
            )

    def _mesh_full_frame(
        self,
        group_rows: np.ndarray,
        replica_slots: np.ndarray,
        last_dirty: np.ndarray,
        last_flushed: np.ndarray,
        seqs: np.ndarray,
        force_rows: "np.ndarray | None" = None,
    ) -> np.ndarray:
        """The real sharded program: place the lanes over the mesh, run
        fold + commit + health chip-local with ONE cross-chip totals
        fold, write back. Same touched-row discipline as the device
        backend, so all three backends advance IDENTICAL row sets."""
        import time

        m = len(group_rows)
        bucket = 8
        while bucket < m:
            bucket *= 2
        g_rows = np.zeros(bucket, np.int64)
        g_slots = np.zeros(bucket, np.int64)
        g_dirty = np.full(bucket, I64_MIN, np.int64)
        g_flushed = np.full(bucket, I64_MIN, np.int64)
        g_seqs = np.full(bucket, I64_MIN, np.int64)
        if m:
            g_rows[:m] = group_rows
            g_slots[:m] = replica_slots
            g_dirty[:m] = last_dirty
            g_flushed[:m] = last_flushed
            g_seqs[:m] = seqs
        dirty_rows = np.flatnonzero(self.quorum_dirty)
        parts = [np.asarray(group_rows, np.int64), dirty_rows]
        if force_rows is not None and len(force_rows):
            parts.append(np.asarray(force_rows, np.int64))
        touched = (
            np.unique(np.concatenate(parts))
            if any(len(p) for p in parts)
            else _EMPTY_ROWS
        )
        before = self.commit_index[touched].copy()
        t0 = time.perf_counter()
        new, health, totals = self.mesh_frame.run(
            self, g_rows, g_slots, g_dirty, g_flushed, g_seqs
        )
        self._last_fold_us = (time.perf_counter() - t0) * 1e6
        self.commit_index[touched] = new["commit_index"][touched]
        self.last_visible[touched] = new["last_visible"][touched]
        self.match_index = new["match_index"]
        self.flushed_index = new["flushed_index"]
        self.last_seq = new["last_seq"]
        self.health_max_lag = health["max_lag"]
        self.health_under = health["under_replicated"]
        self.health_leaderless = health["leaderless"]
        self.touch()
        self._folded_self_m[touched] = self.match_index[touched, SELF_SLOT]
        self._folded_self_f[touched] = self.flushed_index[touched, SELF_SLOT]
        self.quorum_dirty[:] = False
        self._mesh_totals = totals
        self._last_changed = touched
        self._note_chip_changed(touched)
        return touched[self.commit_index[touched] > before]

    @staticmethod
    def _masked_quorum_np(
        values: np.ndarray, mask: np.ndarray, n: np.ndarray
    ) -> np.ndarray:
        """numpy mirror of ops.quorum._masked_quorum_value; `n` is the
        per-row voter count (cached across ticks via voter_epoch).
        (np.sort beats a host Batcher network mirror at 8 lanes —
        measured; the network only wins on the device, ops.quorum.)"""
        g, r = values.shape
        filled = np.where(mask, values, I64_MIN)
        ordered = np.sort(filled, axis=-1)
        idx = np.clip(r - n + (n - 1) // 2, 0, r - 1)
        val = np.take_along_axis(ordered, idx[:, None], axis=-1)[:, 0]
        return np.where(n > 0, val, I64_MIN)

    @staticmethod
    def _masked_quorum_np2(
        a: np.ndarray, b: np.ndarray, mask: np.ndarray, n: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """_masked_quorum_np over TWO value planes sharing one voter
        mask/count — the sweep's committed (commit quorum) and match
        (dirty/visibility quorum) lanes. One stacked sort instead of
        two: the sort is the incremental sweep's largest single cost
        at mesh scale."""
        g, r = a.shape
        filled = np.where(mask, np.stack((a, b)), I64_MIN)
        ordered = np.sort(filled, axis=-1)
        idx = np.clip(r - n + (n - 1) // 2, 0, r - 1)
        val = np.take_along_axis(
            ordered,
            np.broadcast_to(idx[None, :, None], (2, g, 1)),
            axis=-1,
        )[:, :, 0]
        out = np.where(n > 0, val, I64_MIN)
        return out[0], out[1]

    def _voter_counts(self) -> tuple[np.ndarray, "np.ndarray | None", bool]:
        """(n_voters, n_voters_old | None, any_joint), recomputed only
        when a configuration changed since the last call."""
        cache = self._voter_cache
        if cache is None or cache[0] != self.voter_epoch:
            n_cur = self.is_voter.sum(axis=-1, dtype=np.int64)
            any_joint = bool(self.is_voter_old.any())
            n_old = (
                self.is_voter_old.sum(axis=-1, dtype=np.int64)
                if any_joint
                else None
            )
            cache = (self.voter_epoch, n_cur, n_old, any_joint)
            self._voter_cache = cache
        return cache[1], cache[2], cache[3]

    # -- partition health (ops.health) --------------------------------
    # Incremental in-fold refresh is bounded: beyond this touched-row
    # count the fancy-indexed gather costs milliseconds (18 ms at 100k
    # rows) while every lane reader calls health_refresh() anyway, so
    # a giant fold defers to the on-read authoritative recompute.
    HEALTH_INCR_CAP = 2048

    def _health_np_rows(
        self,
        rows: np.ndarray,
        *,
        match: "np.ndarray | None" = None,
        commit: "np.ndarray | None" = None,
        voters: "np.ndarray | None" = None,
        voters_old: "np.ndarray | None" = None,
        leaders: "np.ndarray | None" = None,
    ) -> None:
        """Refresh the health lanes for a row subset with the numpy
        mirror of the device reduction — hooked onto the sweep's
        changed-row set, so steady-state ticks pay nothing and hot rows
        never read stale. Oversized sets (full-frame folds) skip: the
        read path's health_refresh() is always authoritative. Callers
        that already gathered a lane pass it through the keywords (the
        sweep's lanes are post-write, exactly what the reduction
        reads) — the row gathers dominate the incremental path."""
        if not len(rows) or len(rows) > self.HEALTH_INCR_CAP:
            return
        h = health_reduce_np(
            self.match_index[rows] if match is None else match,
            self.commit_index[rows] if commit is None else commit,
            self.is_voter[rows] if voters is None else voters,
            self.is_voter_old[rows] if voters_old is None else voters_old,
            self.is_leader[rows] if leaders is None else leaders,
            self.leader_id[rows] >= 0,
            self.row_active[rows],
        )
        self.health_max_lag[rows] = h["max_lag"]
        self.health_under[rows] = h["under_replicated"]
        self.health_leaderless[rows] = h["leaderless"]

    def health_refresh(self) -> None:
        """Authoritative all-rows health recompute via the selected
        backend (RP_QUORUM_BACKEND, same seam as the quorum fold).
        Endpoints call this before reading the lanes, so the reported
        view is never staler than the request — and leader_id changes
        (which don't dirty the quorum sweep) are always reflected."""
        backend = self._backend()
        if backend == "mesh":
            # read path, not the per-tick sweep: the health-only mesh
            # program (no reply fold, no commit movement) refreshes
            # the lanes and the fleet totals in one dispatch
            health, totals = self.mesh_frame.run_health(self)
            self.health_max_lag = health["max_lag"]
            self.health_under = health["under_replicated"]
            self.health_leaderless = health["leaderless"]
            self._mesh_totals = dict(
                self._mesh_totals or {}, **totals
            )
            return
        if backend == "device":
            import jax.numpy as jnp

            from ..ops.health import health_reduce_jit

            h = health_reduce_jit(
                jnp.asarray(self.match_index),
                jnp.asarray(self.commit_index),
                jnp.asarray(self.is_voter),
                jnp.asarray(self.is_voter_old),
                jnp.asarray(self.is_leader),
                jnp.asarray(self.leader_id >= 0),
                jnp.asarray(self.row_active),
            )
            # control-plane read path, not the per-tick sweep
            self.health_max_lag = np.array(h["max_lag"])  # rplint: disable=RPL002
            self.health_under = np.array(h["under_replicated"])  # rplint: disable=RPL002
            self.health_leaderless = np.array(h["leaderless"])  # rplint: disable=RPL002
            return
        h = health_reduce_np(
            self.match_index,
            self.commit_index,
            self.is_voter,
            self.is_voter_old,
            self.is_leader,
            self.leader_id >= 0,
            self.row_active,
        )
        self.health_max_lag[:] = h["max_lag"]
        self.health_under[:] = h["under_replicated"]
        self.health_leaderless[:] = h["leaderless"]

    def health_totals(self) -> dict:
        """Aggregate view over the (already refreshed) health lanes."""
        return {
            "max_follower_lag": int(self.health_max_lag.max(initial=0)),
            "under_replicated": int(np.count_nonzero(self.health_under)),
            "leaderless": int(np.count_nonzero(self.health_leaderless)),
            "active": int(np.count_nonzero(self.row_active)),
        }

    def host_tick(
        self,
        group_rows: np.ndarray,
        replica_slots: np.ndarray,
        last_dirty: np.ndarray,
        last_flushed: np.ndarray,
        seqs: np.ndarray,
        force_rows: "np.ndarray | None" = None,
    ) -> np.ndarray:
        """Vectorized host fold + INCREMENTAL commit step.

        Same math as the device sweep (ops.quorum.heartbeat_tick), but
        the quorum/median pass runs only over rows whose quorum inputs
        changed since the last tick:

          - fold pairs whose match/flushed actually increased,
          - rows whose SELF slot moved since last folded (local append
            or fsync completing between ticks — the flush-clamp release),
          - rows flagged `quorum_dirty` (configuration changes),
          - `force_rows`: rows whose quorum inputs were already folded
            into the lanes by the caller (the tick frame's pending-reply
            enqueue path pre-applies cell updates inline for the
            catch-up fiber's progress checks, so the movement detection
            above cannot see them — the frame passes those rows here,
            and the rows of the leader's own flushes with them).

        Soundness: every OTHER mutation path (per-replicate replies,
        catch-up, become-leader) calls scalar_commit_update itself or
        enqueues into the tick frame (which forces its rows through
        here), so a row skipped has had no quorum-input change since
        the value this sweep last used, but for a SELF-slot move that
        self_move_can_advance proved changes nothing (it stays in the
        frame's force set and rides the next fold). Steady-state
        ticks — the common case at 50k groups — touch no rows and cost
        O(replies) gathers only, which is what makes a 50k-group live
        tick fit inside one 50 ms heartbeat interval on a single host
        core.
        """
        from ..models.consensus_state import SELF_SLOT

        changed_rows: list[np.ndarray] = []
        if force_rows is not None and len(force_rows):
            changed_rows.append(np.asarray(force_rows, np.int64))
        if len(group_rows):
            fresh = seqs > self.last_seq[group_rows, replica_slots]
            r, s = group_rows[fresh], replica_slots[fresh]
            pre_m = self.match_index[r, s]
            pre_f = self.flushed_index[r, s]
            # one reply per lane per window is the overwhelming steady
            # shape: unique (row, slot) pairs fold with plain
            # gather/scatter maxima. Duplicate pairs (catch-up bursts
            # re-acking a lane inside one window) take np.maximum.at,
            # whose unbuffered element loop costs ~10x the vector pair.
            key = r * self.replica_slots + s
            if len(key) == 0 or len(np.unique(key)) == len(key):
                new_m = np.maximum(pre_m, last_dirty[fresh])
                new_f = np.maximum(pre_f, last_flushed[fresh])
                self.match_index[r, s] = new_m
                self.flushed_index[r, s] = new_f
                self.last_seq[r, s] = seqs[fresh]  # fresh => strictly up
                moved = (new_m > pre_m) | (new_f > pre_f)
            else:
                np.maximum.at(self.match_index, (r, s), last_dirty[fresh])
                np.maximum.at(self.flushed_index, (r, s), last_flushed[fresh])
                np.maximum.at(self.last_seq, (r, s), seqs[fresh])
                moved = (self.match_index[r, s] > pre_m) | (
                    self.flushed_index[r, s] > pre_f
                )
            if moved.any():
                changed_rows.append(r[moved])
            # self-slot movement since the last fold over these rows
            self_m = self.match_index[group_rows, SELF_SLOT]
            self_f = self.flushed_index[group_rows, SELF_SLOT]
            self_moved = (self_m != self._folded_self_m[group_rows]) | (
                self_f != self._folded_self_f[group_rows]
            )
            if self_moved.any():
                changed_rows.append(group_rows[self_moved])
        if self.quorum_dirty.any():
            changed_rows.append(np.flatnonzero(self.quorum_dirty))
            self.quorum_dirty[:] = False
        if not changed_rows:
            self._last_changed = _EMPTY_ROWS
            return _EMPTY_ROWS
        self.touch()
        rows = np.unique(np.concatenate(changed_rows))
        self._last_changed = rows
        self._folded_self_m[rows] = self.match_index[rows, SELF_SLOT]
        self._folded_self_f[rows] = self.flushed_index[rows, SELF_SLOT]

        # quorum fold over the changed subset only
        match = self.match_index[rows]
        flushed = self.flushed_index[rows]
        voters = self.is_voter[rows]
        before = self.commit_index[rows]
        committed = np.minimum(flushed, match)
        n_cur_all, n_old_all, _ = self._voter_counts()
        n_cur = n_cur_all[rows]
        voters_old = self.is_voter_old[rows]
        # joint consensus is transient (reconfig windows); skip the
        # old-config quorum sorts when no changed row is joint
        any_joint = bool(voters_old.any())
        m_cur, d_cur = self._masked_quorum_np2(committed, match, voters, n_cur)
        if any_joint:
            n_old = n_old_all[rows] if n_old_all is not None else (
                voters_old.sum(axis=-1, dtype=np.int64)
            )
            m_old, d_old = self._masked_quorum_np2(
                committed, match, voters_old, n_old
            )
            majority = np.where(n_old > 0, np.minimum(m_cur, m_old), m_cur)
        else:
            majority = m_cur
        majority = np.minimum(majority, flushed[:, SELF_SLOT])
        leaders = self.is_leader[rows]
        advance = (
            leaders
            & (n_cur > 0)
            & (majority > before)
            & (majority >= self.term_start[rows])
        )
        new_commit = np.where(advance, majority, before)
        if any_joint:
            majority_dirty = np.where(
                n_old > 0, np.minimum(d_cur, d_old), d_cur
            )
        else:
            majority_dirty = d_cur
        majority_dirty = np.minimum(majority_dirty, match[:, SELF_SLOT])
        last_vis = self.last_visible[rows]
        self.last_visible[rows] = np.where(
            leaders & (n_cur > 0),
            np.maximum(last_vis, np.maximum(new_commit, majority_dirty)),
            last_vis,
        )
        self.commit_index[rows] = new_commit
        # health refresh reuses the lanes this sweep already gathered —
        # the changed-row gathers are the steady tick's dominant cost
        # at mesh scale (1M rows: random-row gathers are cache-miss
        # bound), so never pay them twice in one fold
        self._health_np_rows(
            rows,
            match=match,
            commit=new_commit,
            voters=voters,
            voters_old=voters_old,
            leaders=leaders,
        )
        return rows[new_commit > before]

    def device_tick(
        self,
        group_rows: np.ndarray,
        replica_slots: np.ndarray,
        last_dirty: np.ndarray,
        last_flushed: np.ndarray,
        seqs: np.ndarray,
        force_rows: "np.ndarray | None" = None,
    ) -> np.ndarray:
        """Fold a reply batch + advance every group's commit in ONE
        call. The HOST fold is the default at every size (see
        _backend); RP_QUORUM_BACKEND=device routes to the compiled
        device program. Returns rows whose
        commit advanced. `force_rows` always recompute (see
        host_tick): the tick frame's rows of replies whose cells the
        caller pre-applied, and its rows whose SELF slot the leader's
        own flush moved, those that asked for this fold and those
        that only ride it (TickFrame.note_self).

        The device fold keeps a GroupState resident on the device and
        moves only `touched` (the reply rows, the quorum_dirty rows,
        the forced rows) in one upload and one readback
        (_fold_on_device). That is sound without tracking who writes
        a lane, because the kernel's result is consumed on `touched`
        alone: commit and visible are written back there, and the fold
        changes match / flushed / last_seq at reply pairs, whose rows
        are in it. Every lane of a touched row is scattered fresh from
        the mirrors inside the same dispatch, and no row's result
        reads another row, so what the resident state holds anywhere
        else — rows a host writer changed since, freed rows, whatever
        the full-width commit step made of stale ones — is never
        read. Only its shape has to be the mirrors': it is dropped,
        and seeded again by the next fold, by _grow / reserve, prewarm
        and a change of backend, and by nothing else.

        Rows and reply window share one power-of-two bucket, so XLA
        compiles one program a power of two up to the capacity (or the
        largest window, prewarm's `max_replies`), not one per count;
        padding replies carry seq = i64 min, which the fold's
        reply-reordering guard drops (ops.quorum.fold_replies), and
        padding rows an index past the lanes, which the scatter
        drops."""
        backend = self._backend()
        if backend != "device":
            self._resident = None
        if backend == "host":
            return self.host_tick(
                group_rows,
                replica_slots,
                last_dirty,
                last_flushed,
                seqs,
                force_rows=force_rows,
            )
        if backend == "mesh":
            return self._mesh_tick(
                group_rows,
                replica_slots,
                last_dirty,
                last_flushed,
                seqs,
                force_rows=force_rows,
            )
        # steady-state skip (mirrors host_tick's incremental sweep): if
        # no reply can move match/flushed, no SELF slot moved, no row
        # is forced, and no config changed, fold only the seq guard
        # host-side and skip the device round-trip entirely
        forced = force_rows is not None and len(force_rows) > 0
        dirty = self._dirty_pending
        if dirty and not self.quorum_dirty.any():
            # every dirty row was cleared by another fold (host_tick,
            # the mesh's) or by hand
            dirty = self._dirty_pending = False
        if len(group_rows) and not forced and not dirty:
            fresh = seqs > self.last_seq[group_rows, replica_slots]
            may_move = (
                last_dirty[fresh]
                > self.match_index[group_rows[fresh], replica_slots[fresh]]
            ) | (
                last_flushed[fresh]
                > self.flushed_index[group_rows[fresh], replica_slots[fresh]]
            )
            self_moved = (
                self.match_index[group_rows, SELF_SLOT]
                != self._folded_self_m[group_rows]
            ) | (
                self.flushed_index[group_rows, SELF_SLOT]
                != self._folded_self_f[group_rows]
            )
            if not may_move.any() and not self_moved.any():
                np.maximum.at(
                    self.last_seq,
                    (group_rows[fresh], replica_slots[fresh]),
                    seqs[fresh],
                )
                return _EMPTY_ROWS
        # commit/visible writeback is restricted to the reply rows plus
        # config-dirtied rows plus forced rows, exactly the set
        # host_tick recomputes — the two backends must advance
        # IDENTICAL row sets (the differential tests pin this)
        parts = [group_rows] if len(group_rows) else []
        if dirty:
            parts.append(np.flatnonzero(self.quorum_dirty))
        if forced:
            parts.append(np.asarray(force_rows, np.int64))
        if not parts:
            touched = _EMPTY_ROWS
        elif len(parts) == 1 and len(parts[0]) == 1:
            touched = parts[0]  # the write fold's one row
        else:
            touched = np.unique(np.concatenate(parts))
        before = self.commit_index[touched]
        m = max(len(group_rows), len(touched))
        bucket = 8
        while bucket < m:
            bucket *= 2
        self._fold_on_device(
            touched,
            (group_rows, replica_slots, last_dirty, last_flushed, seqs),
            bucket,
        )
        # commit/match/flushed are SAME lanes: invalidate armed frames
        # (host_tick bumps the epoch for the same reason)
        self.touch()
        self._folded_self_m[touched] = self.match_index[touched, SELF_SLOT]
        self._folded_self_f[touched] = self.flushed_index[touched, SELF_SLOT]
        if dirty:
            self.quorum_dirty[:] = False
            self._dirty_pending = False
        return touched[self.commit_index[touched] > before]

    def _fold_on_device(
        self, touched: np.ndarray, window: tuple, bucket: int
    ) -> None:
        """One fold's exchange with the resident device state
        (ops.quorum.resident_tick has the two layouts): every lane of
        the `touched` rows and the reply `window`'s five columns go up
        in one packed buffer of `bucket` rows, handed to the jit as
        32-bit words so that the upload crosses inside the dispatch;
        the five lanes a fold changes and the rows' health lanes come
        back at the same rows in one buffer of words, and are written
        into the mirrors in place (so the device fold leaves no health
        refresh to the host: `_health_np_rows` is host_tick's).
        The whole lanes go up first only where no resident state is
        left (`seed`)."""
        from ..ops.quorum import heartbeat_tick_jit

        t_up = time.monotonic_ns()
        # donated to the call: ours again only when it has returned
        state, self._resident = self._resident, None
        seed = state is None
        if seed:
            state = self.to_device_state()
            devplane.count_state_seed()
        words = self._pack_fold(touched, window, bucket)
        devplane.count_transfer(words.nbytes, "h2d")
        trace.record(
            "tick.upload", "run", t_up, time.monotonic_ns(),
            seed=int(seed), rows=len(touched), replies=len(window[0]),
            bucket=bucket,
        )
        self._resident, back = heartbeat_tick_jit(state, words)
        t_back = time.monotonic_ns()
        # the fold's one readback, after its one kernel
        out = np.asarray(back)  # rplint: disable=RPL002
        self._unpack_fold(touched, out)
        trace.record("tick.readback", "run", t_back, time.monotonic_ns())
        devplane.count_transfer(out.nbytes, "d2h")

    def _fold_layout(self) -> tuple:
        """(upload width, ((lane, first column, width), ...) of the
        upload, readback width, the same of the readback) in int64
        columns: the packed layouts of ops.quorum.resident_tick at this
        many replica slots. The upload's lanes end two columns before
        the window, where the leader-known and allocated flags go."""
        layout = self._layout
        if layout is None:
            from ..ops.quorum import TICK_HEALTH_LANES, TICK_READBACK_LANES

            r = self.replica_slots

            def columns(names, col):
                out = []
                for name in names:
                    width = 1 if getattr(self, name).ndim == 1 else r
                    out.append((name, col, width))
                    col += width
                return tuple(out), col

            up, width = columns(GroupState._fields, 1)
            back, back_width = columns(TICK_READBACK_LANES + TICK_HEALTH_LANES, 0)
            layout = self._layout = (width + 7, up, back_width, back)
        return layout

    def _pack_fold(
        self, touched: np.ndarray, window: tuple, bucket: int
    ) -> np.ndarray:
        """The fold's upload as uint32 words: the int64 rows of
        ops.quorum.resident_tick, written into a buffer kept for the
        bucket (the jit has copied it to the device by the time the
        fold's readback returns, so the next fold may write it again)."""
        width, up, _, _ = self._fold_layout()
        packed = self._fold_bufs.get(bucket)
        if packed is None:
            packed = self._fold_bufs[bucket] = np.empty((bucket, width), np.int64)
        t = len(touched)
        packed[:t, 0] = touched
        packed[t:, 0] = self._cap
        for name, col, w in up:
            packed[:t, col : col + w] = getattr(self, name)[touched].reshape(t, w)
        col = width - 7
        packed[:t, col] = self.leader_id[touched] >= 0
        packed[:t, col + 1] = self.row_active[touched]
        col += 2
        m = len(window[0])
        if m:
            for i, column in enumerate(window):
                packed[:m, col + i] = column
        packed[m:, col : col + 2] = 0
        packed[m:, col + 2 :] = I64_MIN
        return packed.view(np.uint32)

    def _unpack_fold(self, touched: np.ndarray, out: np.ndarray) -> None:
        """Write the readback's words (uint32 [B · 2(5 + 3R)], flat)
        into the mirrors at the `touched` rows, one assignment a lane:
        the five a fold changes and the three health lanes."""
        _, _, width, lanes = self._fold_layout()
        t = len(touched)
        back = out.view(np.int64).reshape(-1, width)
        for name, col, w in lanes:
            lane = getattr(self, name)
            lane[touched] = back[:t, col : col + w].reshape((t,) + lane.shape[1:])

    def frame_tick(  # rplint: hot
        self,
        group_rows: np.ndarray,
        replica_slots: np.ndarray,
        last_dirty: np.ndarray,
        last_flushed: np.ndarray,
        seqs: np.ndarray,
        force_rows: "np.ndarray | None" = None,
    ) -> np.ndarray:
        """One tick frame: fold the window's pending reply columns and
        advance commits, the whole live replication plane per tick as
        one call. Returns the advanced rows. device_tick picks the
        fold by backend; this is the name the tick-discipline rules
        (RPL011, RPL018) hold the frame's callers to."""
        return self.device_tick(
            group_rows,
            replica_slots,
            last_dirty,
            last_flushed,
            seqs,
            force_rows=force_rows,
        )

    def prewarm(self, max_replies: int = 0) -> None:
        """Compile the sweep kernels up front so the first live tick
        doesn't stall the event loop on XLA compilation (which would
        starve heartbeats and trigger spurious elections). Re-invoked
        by _grow so a capacity doubling never hands the next tick a
        fresh trace at the new [G, R] shape (the mid-traffic compile
        stall).

        Device backend: the tick program at every bucket a fold can
        land in, a power of two from 8 up to the capacity (a fold can
        touch every row) or, where larger, up to a window of
        `max_replies` replies. Each bucket is its own XLA program —
        four to ten seconds apiece cold on a TPU v5e (PERF.md) — so a
        deployment that knows its partition count warms them here,
        ahead of traffic. The resident device state is seeded again on
        the way."""
        empty = np.array([], np.int64)
        backend = self._backend()
        # a fold here has nobody to tell which rows' commit advanced
        # (Consensus._notify_commit runs off the tick frame's and the
        # heartbeat manager's folds): rows that wait for a recompute
        # are set aside, and the next live fold moves and reports them
        pending = self.quorum_dirty.copy()
        self.quorum_dirty[:] = False
        # declared-warmup region: compiles here are the point of the
        # call (capacity doubling / backend bring-up), so the compile
        # guard must not count them against the steady window
        try:
            with compileguard.warmup("prewarm at capacity %d" % self._cap):
                if backend == "mesh":
                    # compile the sharded frame + health programs at
                    # the current capacity
                    self._mesh_full_frame(empty, empty, empty, empty, empty)
                    self.health_refresh()
                    return
                self._resident = None
                self.device_tick(empty, empty, empty, empty, empty)
                if backend == "device":
                    bucket = 8
                    while True:
                        # all padding: nothing is scattered or folded
                        # and nothing read back is kept
                        self._fold_on_device(_EMPTY_ROWS, (empty,) * 5, bucket)
                        if bucket >= max(self._cap, max_replies):
                            break
                        bucket *= 2
        finally:
            if pending.any():
                self.mark_quorum_dirty(pending)
