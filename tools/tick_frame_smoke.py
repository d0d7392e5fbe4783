#!/usr/bin/env python
"""Tick-frame smoke: the batched live replication plane at 100k rows.

Two phases, both deterministic (fixed seeds):

  1. scale smoke (default): build a 100k-row ShardGroupArrays by
     direct lane writes (no Consensus/disk — this gates the MATH and
     the fold plumbing, not group setup), push a randomized reply
     schedule through a real TickFrame, and differentially check a
     row sample against quorum_scalar.leader_commit_index after every
     fold. A gross O(groups)-per-fold interpreter regression also
     trips the generous per-fold wall bound.

  2. --parity: replay the IDENTICAL schedule twice — once under
     RP_QUORUM_BACKEND=host (the numpy fallback) and once under
     =device — and require byte-identical commit_index/last_visible
     lanes plus identical advanced-row sets. The fallback leg of
     tools/verify.sh runs this so a device-only semantic drift cannot
     hide behind the host default.

Exit 0 on success; any assertion failure is a gate failure.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def build(n: int, seed: int):
    """n allocated rows with randomized quorum lanes (vectorized
    writes; every row keeps SELF a current voter)."""
    from redpanda_tpu.models.consensus_state import SELF_SLOT
    from redpanda_tpu.raft.shard_state import NO_OFFSET, ShardGroupArrays

    arrays = ShardGroupArrays(capacity=n)
    rows = np.array([arrays.alloc_row() for _ in range(n)], np.int64)
    rng = np.random.default_rng(seed)
    r = arrays.replica_slots
    match = rng.integers(-1, 400, (n, r)).astype(np.int64)
    flushed = np.maximum(match - rng.integers(0, 40, (n, r)), NO_OFFSET)
    sent = rng.random((n, r)) < 0.15
    match[sent] = NO_OFFSET
    flushed[sent] = NO_OFFSET
    voter = rng.random((n, r)) < 0.6
    voter[:, SELF_SLOT] = True
    old = np.zeros((n, r), bool)
    joint = rng.random(n) < 0.25
    old[joint] = rng.random((int(joint.sum()), r)) < 0.5
    arrays.match_index[rows] = match
    arrays.flushed_index[rows] = flushed
    arrays.is_voter[rows] = voter
    arrays.is_voter_old[rows] = old
    arrays.is_leader[rows] = True
    arrays.commit_index[rows] = rng.integers(-1, 200, n)
    arrays.term_start[rows] = rng.integers(0, 300, n)
    arrays.last_visible[rows] = arrays.commit_index[rows]
    arrays.voter_epoch += 1
    arrays.touch()
    arrays.quorum_dirty[:] = False
    # baseline sweep: bring every row's commit to a lane-consistent
    # state (in the live system group registration marks rows dirty
    # and the first tick sweeps them; direct lane writes bypass that)
    empty = np.empty(0, np.int64)
    arrays.frame_tick(empty, empty, empty, empty, empty, force_rows=rows)
    return arrays, rows


def schedule(n: int, rows: np.ndarray, rounds: int, per_round: int, seed: int):
    """Deterministic reply schedule: per round, `per_round` UNIQUE
    rows each get one reply on a random non-SELF slot; round k carries
    seq k+1 (monotone per lane), with round 3 replaying round 2's seq
    (stale — the guard must drop it identically on both backends)."""
    rng = np.random.default_rng(seed)
    out = []
    for k in range(rounds):
        pick = rng.choice(n, size=min(per_round, n), replace=False)
        rr = rows[pick]
        slots = rng.integers(1, 8, len(rr)).astype(np.int64)
        dirty = rng.integers(-1, 1000, len(rr)).astype(np.int64)
        flushed = np.maximum(dirty - rng.integers(0, 25, len(rr)), -1)
        seq = np.full(len(rr), (2 if k == 3 else k) + 1, np.int64)
        out.append((rr, slots, dirty, flushed, seq.astype(np.int64)))
    return out


def oracle_check(arrays, rows, sample: int, seed: int) -> None:
    """Sampled differential: batched commit decisions vs the scalar
    oracle, same replica construction as scalar_commit_update."""
    from redpanda_tpu.models.consensus_state import SELF_SLOT
    from redpanda_tpu.raft import quorum_scalar as qs

    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rows), size=min(sample, len(rows)), replace=False)
    for row in rows[pick]:
        row = int(row)
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
        want = qs.leader_commit_index(
            replicas,
            leader_flushed=int(arrays.flushed_index[row, SELF_SLOT]),
            commit_index=int(arrays.commit_index[row]),
            term_start=int(arrays.term_start[row]),
        )
        got = int(arrays.commit_index[row])
        assert got == want, (
            f"row {row}: batched commit {got} != scalar oracle {want}"
        )


def run_schedule(n: int, seed: int):
    """One full replay: fresh arrays + TickFrame, fold every round.
    The first two folds are compile warmup; from round 2 the compile
    guard (RP_COMPILEGUARD=1) treats any further jit trace as a
    steady-state recompile finding. Returns (arrays, rows,
    advanced_sets, fold_times)."""
    from redpanda_tpu.raft.tick_frame import TickFrame
    from redpanda_tpu.utils import compileguard

    arrays, rows = build(n, seed)
    frame = TickFrame(arrays)
    sched = schedule(n, rows, rounds=8, per_round=max(1, n // 5), seed=seed)
    advanced_sets = []
    times = []
    compileguard.reset()
    for k, (rr, slots, dirty, flushed, seq) in enumerate(sched):
        if k == 2:
            compileguard.steady()
        t0 = time.perf_counter()
        advanced = frame.fold_now(rr, slots, dirty, flushed, seq)
        times.append(time.perf_counter() - t0)
        advanced_sets.append(np.sort(np.asarray(advanced, np.int64)))
    return arrays, rows, advanced_sets, times


#: the lanes a fold writes, compared byte for byte across backends
PARITY_LANES = (
    "commit_index",
    "last_visible",
    "match_index",
    "flushed_index",
    "last_seq",
)


def run_parity(n: int, seed: int, backend: str = "device") -> dict:
    """Replay the identical schedule under RP_QUORUM_BACKEND=host and
    =`backend` ("device" or "mesh") and require byte-identical
    PARITY_LANES plus identical advanced-row sets; then hold the
    `backend` run's lanes to the scalar oracle on a row sample.
    Returns the `backend` run's arrays and counts (chip_smoke reads
    lane attribution and fold times off them)."""
    lanes = {}
    runs = {}
    for b in ("host", backend):
        os.environ["RP_QUORUM_BACKEND"] = b
        arrays, rows, advanced_sets, times = run_schedule(n, seed)
        runs[b] = (arrays, rows, times)
        lanes[b] = {
            name: getattr(arrays, name)[rows].tobytes()
            for name in PARITY_LANES
        }
        lanes[b]["advanced-row sets"] = [a.tobytes() for a in advanced_sets]
    for name, want in lanes["host"].items():
        assert want == lanes[backend][name], (
            f"{name} diverged host vs {backend}"
        )
    arrays, rows, times = runs[backend]
    oracle_check(arrays, rows, sample=2000, seed=seed + 1)
    advanced_sets = lanes[backend]["advanced-row sets"]
    return {
        "arrays": arrays,
        "rows": n,
        "folds": len(advanced_sets),
        "advanced": sum(len(a) // 8 for a in advanced_sets),
        "fold_s": times,
        "host_fold_s": runs["host"][2],
    }


def guard_check() -> str:
    """Fail the smoke on any steady-state recompile report; returns
    the status fragment for the OK line."""
    from redpanda_tpu.utils import compileguard

    if not compileguard.enabled():
        return ""
    reps = compileguard.reports()
    assert not reps, "steady-state recompiles:\n" + "\n".join(
        r.render() for r in reps
    )
    return ", compile-guard clean"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--groups",
        type=int,
        default=int(os.environ.get("RP_SMOKE_GROUPS", "100000")),
    )
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument(
        "--parity",
        action="store_true",
        help="replay the schedule under RP_QUORUM_BACKEND=host and "
        "=device and require byte-identical commit decisions",
    )
    args = ap.parse_args()
    n = args.groups

    if args.parity:
        got = run_parity(n, args.seed)
        print(
            f"tick-frame parity OK: {n} rows, "
            f"{got['folds']} folds byte-identical host vs "
            f"device, 2000-row oracle sample clean{guard_check()}"
        )
        return 0

    arrays, rows, advanced_sets, times = run_schedule(n, args.seed)
    oracle_check(arrays, rows, sample=2000, seed=args.seed + 1)
    worst_ms = max(times) * 1e3
    per_part_ns = (sum(times) / len(times)) / n * 1e9
    n_adv = sum(len(a) for a in advanced_sets)
    print(
        f"tick-frame smoke OK: {n} rows, {len(times)} folds, "
        f"{n_adv} advances, worst fold {worst_ms:.1f} ms, "
        f"{per_part_ns:.0f} ns/partition/fold, 2000-row oracle sample "
        f"clean{guard_check()}"
    )
    # generous interpreter-regression bound: a per-group Python loop
    # at 100k rows costs seconds per fold, vectorized folds cost ~ms
    budget_ms = 2000.0
    assert worst_ms < budget_ms, (
        f"fold took {worst_ms:.0f} ms at {n} rows — per-group "
        "interpreter work crept back into the tick frame"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
