"""Batched quorum / commit-index kernels — the north-star sweep.

One device call advances the consensus decision math for *all* raft
groups on a shard, replacing the reference's per-group scalar loops:

* `quorum_commit_step` — the leader commit rule
  (reference: consensus.cc:2704-2759 do_maybe_update_leader_commit_idx
  + group_configuration.h:407-428 quorum_match): per-replica value is
  min(flushed, match) (types.h:97-99 match_committed_index); the
  majority value is the ascending (n-1)/2-th order statistic over
  voters; joint configs take min over both voter sets
  (group_configuration.h:487-490); result is clamped to the leader's
  own flushed offset and gated on the current-term check
  (commit only entries of the leader's term — Raft §5.4.2).
  Also computes the majority-replicated dirty offset used for
  relaxed-consistency visibility (consensus.cc:3262-3276).

* `follower_commit_step` — the follower-side rule
  (consensus.cc:2760-2777): commit = min(leader_commit, flushed),
  monotone.

* `fold_replies` — scatter a node-batch of append_entries/heartbeat
  replies back into the [G, R] match/flushed tensors with the
  monotone-seq reordering guard (types.h:107-117), replacing the
  per-reply scalar path (consensus.cc:274 update_follower_index).

All kernels are pure jnp on `[G]`/`[G, R]` int64/bool tensors — XLA
fuses the sort + arithmetic into a handful of HBM passes; no Python
per-group work anywhere.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..models.consensus_state import SELF_SLOT, GroupState
from ..observability import devplane
from ..utils import compileguard
from .health import health_reduce

_I64_MIN = jnp.iinfo(jnp.int64).min
_I64_MAX = jnp.iinfo(jnp.int64).max


def _oddeven_merge_pairs(n: int) -> list[tuple[int, int]]:
    """Batcher odd-even mergesort comparator network for n lanes
    (n a power of two; 19 comparators at n=8)."""
    pairs: list[tuple[int, int]] = []

    def merge(lo: int, span: int, r: int) -> None:
        step = r * 2
        if step < span:
            merge(lo, span, step)
            merge(lo + r, span, step)
            for i in range(lo + r, lo + span - r, step):
                pairs.append((i, i + r))
        else:
            pairs.append((lo, lo + r))

    def sort(lo: int, span: int) -> None:
        if span > 1:
            m = span // 2
            sort(lo, m)
            sort(lo + m, m)
            merge(lo, span, 1)

    sort(0, n)
    return pairs


def _lane_sort(x: jax.Array) -> jax.Array:
    """Ascending sort along the (small) replica axis. For power-of-two
    lane counts a fixed min/max comparator network beats XLA's generic
    sort by ~1.6x on the 50k-group sweep — the replica axis is the hot
    inner dimension of the whole quorum fold."""
    r = x.shape[-1]
    if r == 0 or r & (r - 1):  # empty or not a power of two: generic sort
        return jnp.sort(x, axis=-1)
    cols = [x[..., i] for i in range(r)]
    for a, b in _oddeven_merge_pairs(r):
        lo = jnp.minimum(cols[a], cols[b])
        hi = jnp.maximum(cols[a], cols[b])
        cols[a], cols[b] = lo, hi
    return jnp.stack(cols, axis=-1)


def _masked_quorum_value(values: jax.Array, mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row majority order statistic over masked entries.

    values: [G, R] i64; mask: [G, R] bool. Returns ([G] value, [G] n).
    Matches details::quorum_match (group_configuration.h:407-428):
    ascending order statistic at index (n-1)/2. Masked-out slots are
    filled with i64 min so they sort to the front; the real values
    occupy positions [R-n, R), making the target index
    R - n + (n-1)//2. Rows with n == 0 return i64 min.
    """
    g, r = values.shape
    filled = jnp.where(mask, values, _I64_MIN)
    ordered = _lane_sort(filled)
    n = jnp.sum(mask, axis=-1, dtype=jnp.int64)
    idx = jnp.clip(r - n + (n - 1) // 2, 0, r - 1)
    val = jnp.take_along_axis(ordered, idx[:, None], axis=-1)[:, 0]
    return jnp.where(n > 0, val, _I64_MIN), n


def quorum_commit_step(state: GroupState) -> GroupState:
    """Advance commit_index and last_visible for every leader group."""
    # Quorum input per replica: min(flushed, match). For SELF_SLOT the
    # tensors mirror the local log, so this equals the leader's flushed
    # offset — the same value consensus.cc:2712 feeds for `_self`.
    committed = jnp.minimum(state.flushed_index, state.match_index)

    m_cur, n_cur = _masked_quorum_value(committed, state.is_voter)
    m_old, n_old = _masked_quorum_value(committed, state.is_voter_old)
    # joint consensus: min over both quorums when the old set is active
    majority = jnp.where(n_old > 0, jnp.minimum(m_cur, m_old), m_cur)

    # clamp to leader's own flushed offset (consensus.cc:2737-2739)
    leader_flushed = state.flushed_index[:, SELF_SLOT]
    majority = jnp.minimum(majority, leader_flushed)

    # current-term gate: log.get_term(majority) == term  ⇔  majority >=
    # term_start (consensus.cc:2741), plus monotonicity.
    advance = (
        state.is_leader
        & (n_cur > 0)
        & (majority > state.commit_index)
        & (majority >= state.term_start)
    )
    new_commit = jnp.where(advance, majority, state.commit_index)

    # relaxed-consistency visibility: majority over dirty offsets,
    # joint min, no flush clamp (consensus.cc:3262-3276); visible index
    # never exceeds commit-gated rules — mirror
    # maybe_update_last_visible_index by taking max of commit and the
    # majority-dirty value capped at the leader's dirty offset.
    d_cur, dn_cur = _masked_quorum_value(state.match_index, state.is_voter)
    d_old, dn_old = _masked_quorum_value(state.match_index, state.is_voter_old)
    majority_dirty = jnp.where(dn_old > 0, jnp.minimum(d_cur, d_old), d_cur)
    leader_dirty = state.match_index[:, SELF_SLOT]
    majority_dirty = jnp.minimum(majority_dirty, leader_dirty)
    new_visible = jnp.where(
        state.is_leader & (dn_cur > 0),
        jnp.maximum(state.last_visible, jnp.maximum(new_commit, majority_dirty)),
        state.last_visible,
    )
    return state._replace(commit_index=new_commit, last_visible=new_visible)


def follower_commit_step(
    state: GroupState, leader_commit: jax.Array
) -> GroupState:
    """Follower commit rule over all groups at once
    (consensus.cc:2760-2777): if leaderCommit > commit, commit =
    min(leaderCommit, flushed). leader_commit: [G] i64 (i64 min for
    groups with no update this tick)."""
    flushed = state.flushed_index[:, SELF_SLOT]
    proposed = jnp.minimum(leader_commit, flushed)
    new_commit = jnp.where(
        (leader_commit > state.commit_index) & (proposed > state.commit_index),
        proposed,
        state.commit_index,
    )
    visible = jnp.maximum(state.last_visible, new_commit)
    return state._replace(commit_index=new_commit, last_visible=visible)


def fold_replies(
    state: GroupState,
    group_idx: jax.Array,     # [M] i32/i64 group row per reply
    replica_slot: jax.Array,  # [M] slot of the responding peer
    last_dirty: jax.Array,    # [M] i64 follower's last dirty offset
    last_flushed: jax.Array,  # [M] i64 follower's last flushed offset
    seq: jax.Array,           # [M] i64 request sequence number
) -> GroupState:
    """Fold a node-batch of successful append/heartbeat replies into
    match/flushed. Replies with seq <= last_seq[g, r] are dropped
    (reordered responses, types.h:107-117). Duplicate (g, r) pairs in
    one batch resolve via per-target max — safe because updates are
    monotone on the fast path."""
    fresh = seq > state.last_seq[group_idx, replica_slot]
    eff_dirty = jnp.where(fresh, last_dirty, _I64_MIN)
    eff_flushed = jnp.where(fresh, last_flushed, _I64_MIN)
    eff_seq = jnp.where(fresh, seq, _I64_MIN)
    return state._replace(
        match_index=state.match_index.at[group_idx, replica_slot].max(eff_dirty),
        flushed_index=state.flushed_index.at[group_idx, replica_slot].max(eff_flushed),
        last_seq=state.last_seq.at[group_idx, replica_slot].max(eff_seq),
    )


def local_append_update(
    state: GroupState, group_idx: jax.Array, dirty: jax.Array, flushed: jax.Array
) -> GroupState:
    """Reflect local log appends/flushes into SELF_SLOT for a batch of
    groups (the disk_append → leader state hand-off)."""
    self_slot = jnp.full_like(group_idx, SELF_SLOT)
    return state._replace(
        match_index=state.match_index.at[group_idx, self_slot].max(dirty),
        flushed_index=state.flushed_index.at[group_idx, self_slot].max(flushed),
    )


# jitted entry points (donate state buffers: the sweep updates in
# place); every binding registers with the compile guard so steady-
# state recompiles are caught under RP_COMPILEGUARD=1
quorum_commit_step_jit = devplane.instrument(
    compileguard.instrument(
        jax.jit(quorum_commit_step, donate_argnums=0), "quorum.commit_step"
    ),
    "quorum.commit_step",
)
follower_commit_step_jit = devplane.instrument(
    compileguard.instrument(
        jax.jit(follower_commit_step, donate_argnums=0),
        "quorum.follower_commit_step",
    ),
    "quorum.follower_commit_step",
)
fold_replies_jit = devplane.instrument(
    compileguard.instrument(
        jax.jit(fold_replies, donate_argnums=0), "quorum.fold_replies"
    ),
    "quorum.fold_replies",
)
local_append_update_jit = devplane.instrument(
    compileguard.instrument(
        jax.jit(local_append_update, donate_argnums=0),
        "quorum.local_append_update",
    ),
    "quorum.local_append_update",
)


def heartbeat_tick(
    state: GroupState,
    group_idx: jax.Array,
    replica_slot: jax.Array,
    last_dirty: jax.Array,
    last_flushed: jax.Array,
    seq: jax.Array,
) -> GroupState:
    """One fused leader tick: fold a reply batch, then advance commit
    indices for all groups — the complete 50k-partition sweep as a
    single compiled program."""
    state = fold_replies(state, group_idx, replica_slot, last_dirty, last_flushed, seq)
    return quorum_commit_step(state)


# the five lanes a fold can change, in the column order of the packed
# readback (one column a group lane, `replica_slots` a slot lane)
TICK_READBACK_LANES = (
    "commit_index",
    "last_visible",
    "match_index",
    "flushed_index",
    "last_seq",
)
# after them, one column each, the health lanes (ops.health) of the same
# rows as the fold leaves them, under the names the host keeps them by
TICK_HEALTH_LANES = ("health_max_lag", "health_under", "health_leaderless")


def words_to_i64(words: jax.Array) -> jax.Array:
    """uint32 [B, 2N] → int64 [B, N], bit for bit: each int64 is the
    pair of 32-bit words a little-endian host's `view(np.uint32)` of it
    gives, low word first."""
    b = words.shape[0]
    return lax.bitcast_convert_type(words.reshape(b, -1, 2), jnp.int64)


def i64_to_words(lanes: jax.Array) -> jax.Array:
    """int64 [B, N] → uint32 [B · 2N], the inverse of words_to_i64, flat:
    the host reads it back with `view(np.int64).reshape(B, N)`. Flat,
    because the v5e hands a 2-D readback to the host in whatever order
    its layout keeps (a [1024, 58] one arrived with its last axis not
    contiguous, which `view` refuses), and a 1-D one in order."""
    return lax.bitcast_convert_type(lanes, jnp.uint32).reshape(-1)


def resident_tick(
    state: GroupState, words: jax.Array
) -> tuple[GroupState, jax.Array]:
    """The tick against a state that stays on the device: scatter the
    rows this fold touches into it, fold the reply window, step every
    group's commit at full width, gather what the host reads back.

    `words` is the fold's one upload, handed to the jit as the host's
    numpy buffer so that it crosses inside the dispatch: an int64
    [B, 13 + 5R] buffer (bools widened) as uint32 [B, 2(13 + 5R)]
    (`words_to_i64`): column 0 the row index (an index past the last
    group is padding and its row is dropped, not scattered), then every
    GroupState lane of that row in field order (one column a group
    lane, R a slot lane), then whether the row knows its leader and
    whether it is allocated (read by the health reduction alone, not
    kept), then the reply window's five columns (group, slot,
    last_dirty, last_flushed, seq; padding carries seq = i64 min,
    which fold_replies drops). Returns the state, donated and so
    updated in place, and the fold's one readback, int64 [B, 5 + 3R]
    as flat uint32 words (`i64_to_words`): TICK_READBACK_LANES and
    TICK_HEALTH_LANES at the same rows (a padding row reads the last
    group's; the host drops it).

    Only the scattered rows of the result mean anything: every other
    row holds whatever its last fold left (ShardGroupArrays.device_tick
    says why that is enough)."""
    packed = words_to_i64(words)
    b = packed.shape[0]
    rows = packed[:, 0]
    lanes, col = [], 1
    for lane in state:
        width = math.prod(lane.shape[1:])
        fresh = packed[:, col : col + width].reshape((b,) + lane.shape[1:])
        lanes.append(lane.at[rows].set(fresh.astype(lane.dtype), mode="drop"))
        col += width
    leader_known, active = packed[:, col] != 0, packed[:, col + 1] != 0
    state = heartbeat_tick(
        GroupState(*lanes), *(packed[:, col + 2 + i] for i in range(5))
    )
    at = jnp.minimum(rows, state.num_groups - 1)
    back = [getattr(state, name)[at].reshape(b, -1) for name in TICK_READBACK_LANES]
    health = health_reduce(
        back[2],
        back[0][:, 0],
        state.is_voter[at],
        state.is_voter_old[at],
        state.is_leader[at],
        leader_known,
        active,
    )
    back += [health[k].astype(jnp.int64)[:, None] for k in
             ("max_lag", "under_replicated", "leaderless")]
    return state, i64_to_words(jnp.concatenate(back, axis=1))


# the benchmark's device metrics find this program by the name of its
# XLA module (jit_heartbeat_tick): it is the same tick, with the row
# exchange folded into the one dispatch
resident_tick.__name__ = "heartbeat_tick"

heartbeat_tick_jit = devplane.instrument(
    compileguard.instrument(
        jax.jit(resident_tick, donate_argnums=0), "quorum.heartbeat_tick"
    ),
    "quorum.heartbeat_tick",
)
