"""Struct-of-arrays consensus state — the flagship device model.

The key inversion vs the reference (SURVEY.md §7): where Redpanda keeps
one `raft::consensus` object per partition and loops over thousands of
them each heartbeat tick (heartbeat_manager.cc:203,
consensus.cc:2704-2759), we keep all per-group scalar state as
`[groups]`- and `[groups, replica_slots]`-indexed arrays resident in
device HBM, and step every group in one batched kernel call
(ops.quorum). Per-group Python objects survive only for log I/O and
membership bookkeeping (raft.consensus).

Layout convention:
  * `R` replica slots per group (default 8 ≥ any practical replication
    factor). Slot 0 is ALWAYS the local node (self); remaining slots
    hold peers in config order. Empty slots have is_voter=False.
  * match_index[g, r]   — highest appended ("dirty") offset known on
    replica r (reference: follower_index_metadata.last_dirty_log_index,
    raft/types.h:78-117). Slot 0 mirrors the local log's dirty offset.
  * flushed_index[g, r] — highest fsynced offset on replica r
    (last_flushed_log_index). Slot 0 mirrors the local flushed offset;
    the quorum value of a replica is min(match, flushed)
    (match_committed_index, types.h:97-99).
  * is_voter / is_voter_old — current and joint-consensus-old voter
    masks (group_configuration.h:487-490: joint quorum = min of both).
  * term_start[g] — first offset appended in the current term; the
    batched stand-in for `log.get_term(offset) == term` in the commit
    rule (consensus.cc:2738): offset o has current term iff
    o >= term_start.
  * last_seq[g, r] — monotone reply sequence guard against reordered
    append_entries responses (types.h:107-117).

Non-monotone events (truncation, membership change, leadership change,
snapshot install) are host-side slow path: they rewrite rows via
`host_update` instead of flowing through the batched kernel, mirroring
how the reference treats them as rare control-plane transitions.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .fundamental import NO_OFFSET as _NO_OFFSET

DEFAULT_REPLICA_SLOTS = 8
SELF_SLOT = 0

# the one shared "no offset" sentinel (-1), as an int64 for tensor fills
NO_OFFSET = np.int64(_NO_OFFSET)


class GroupState(NamedTuple):
    """Per-shard consensus tensors. A pytree; every field is a jnp array."""

    term: jax.Array          # [G] i64  current term
    is_leader: jax.Array     # [G] bool this node leads the group
    commit_index: jax.Array  # [G] i64
    term_start: jax.Array    # [G] i64  first offset of current term
    last_visible: jax.Array  # [G] i64  relaxed-consistency visible offset
    match_index: jax.Array   # [G, R] i64
    flushed_index: jax.Array  # [G, R] i64
    is_voter: jax.Array      # [G, R] bool
    is_voter_old: jax.Array  # [G, R] bool (all False unless joint config)
    last_seq: jax.Array      # [G, R] i64 reply-reordering guard

    @property
    def num_groups(self) -> int:
        return self.term.shape[0]

    @property
    def replica_slots(self) -> int:
        return self.match_index.shape[1]


def make_group_state(
    num_groups: int, replica_slots: int = DEFAULT_REPLICA_SLOTS
) -> GroupState:
    g, r = num_groups, replica_slots
    return GroupState(
        term=jnp.zeros(g, jnp.int64),
        is_leader=jnp.zeros(g, bool),
        commit_index=jnp.full(g, NO_OFFSET, jnp.int64),
        term_start=jnp.zeros(g, jnp.int64),
        last_visible=jnp.full(g, NO_OFFSET, jnp.int64),
        match_index=jnp.full((g, r), NO_OFFSET, jnp.int64),
        flushed_index=jnp.full((g, r), NO_OFFSET, jnp.int64),
        is_voter=jnp.zeros((g, r), bool),
        is_voter_old=jnp.zeros((g, r), bool),
        last_seq=jnp.zeros((g, r), jnp.int64),
    )


def host_update(state: GroupState, group: int, **fields) -> GroupState:
    """Slow-path row rewrite (membership/leadership/truncation events).

    Host-side, per-group, infrequent — the analog of the reference's
    scalar control-plane mutations around the hot sweep."""
    updates = {}
    for name, value in fields.items():
        arr = getattr(state, name)
        updates[name] = arr.at[group].set(value)
    return state._replace(**updates)
