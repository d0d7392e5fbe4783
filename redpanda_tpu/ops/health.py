"""Batched partition-health reduction — lag / under-replication math
as one vmap'd pass over the quorum lanes.

The reference computes follower lag and under-replication per
partition inside the health monitor's scalar walk
(cluster/health_monitor.cc + partition_probe); here the inputs already
live as `[G]`/`[G, R]` device lanes (models.consensus_state), so the
whole fleet's health rolls up in a single XLA dispatch:

* per-slot follower lag  — leader dirty offset minus the follower's
  last known dirty offset (`match_index[:, SELF_SLOT] - match_index`),
  clamped at zero, masked to tracked (voter ∪ old-voter) slots so
  learners and empty slots never count;
* `max_lag[g]`           — worst tracked follower per leader row;
* `under_replicated[g]`  — any tracked slot's match < commit_index:
  a committed entry some voter still lacks (the reference's
  under-replicated partition predicate);
* `leaderless[g]`        — an active row that neither leads nor knows
  a leader (metadata-cache `leader_of() is None` analog, but from the
  live raft lanes instead of the controller snapshot).

The scalar oracle for differential testing is `raft.health_scalar`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..models.consensus_state import SELF_SLOT
from ..observability import devplane
from ..utils import compileguard


def health_reduce(
    match: jax.Array,         # [G, R] i64 dirty offsets (slot 0 = self)
    commit: jax.Array,        # [G] i64 commit_index
    is_voter: jax.Array,      # [G, R] bool current voter mask
    is_voter_old: jax.Array,  # [G, R] bool joint-consensus old voters
    is_leader: jax.Array,     # [G] bool
    leader_known: jax.Array,  # [G] bool leader_id resolved for the row
    active: jax.Array,        # [G] bool row is allocated (not freed)
) -> dict[str, jax.Array]:
    """One pass over the quorum lanes -> per-row health vectors."""
    tracked = is_voter | is_voter_old
    self_dirty = match[:, SELF_SLOT]
    lag = jnp.where(tracked, jnp.maximum(self_dirty[:, None] - match, 0), 0)
    lead = is_leader & active
    max_lag = jnp.where(lead, jnp.max(lag, axis=-1), 0)
    under = lead & jnp.any(tracked & (match < commit[:, None]), axis=-1)
    leaderless = active & ~is_leader & ~leader_known
    return {
        "max_lag": max_lag,
        "under_replicated": under,
        "leaderless": leaderless,
    }


def health_reduce_np(
    match: np.ndarray,
    commit: np.ndarray,
    is_voter: np.ndarray,
    is_voter_old: np.ndarray,
    is_leader: np.ndarray,
    leader_known: np.ndarray,
    active: np.ndarray,
) -> dict[str, np.ndarray]:
    """Numpy mirror of `health_reduce` for the host backend — identical
    math, identical dtypes, so host/device stay byte-equal."""
    tracked = is_voter | is_voter_old
    self_dirty = match[:, SELF_SLOT]
    lag = np.where(
        tracked, np.maximum(self_dirty[:, None] - match, 0), np.int64(0)
    )
    lead = is_leader & active
    max_lag = np.where(lead, lag.max(axis=-1), np.int64(0))
    under = lead & (tracked & (match < commit[:, None])).any(axis=-1)
    leaderless = active & ~is_leader & ~leader_known
    return {
        "max_lag": max_lag.astype(np.int64, copy=False),
        "under_replicated": under,
        "leaderless": leaderless,
    }


health_reduce_jit = devplane.instrument(
    compileguard.instrument(jax.jit(health_reduce), "health.reduce"),
    "health.reduce",
)
