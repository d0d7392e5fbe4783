"""Multi-device replicated cluster step — heartbeats over ICI.

Models an N-node cluster as an N-device mesh: device d leads the
groups in its shard block and follows the groups of devices d-1, d-2
(ring placement, replication factor 3). One `cluster_tick` is the
complete heartbeat round the reference runs over TCP
(heartbeat_manager.cc:373 → service.h:66 → consensus append → reply →
commit-index fold), executed as a single shard_map program:

  1. leaders reflect their local appends (SELF_SLOT),
  2. heartbeat payloads (term/commit/last_dirty) ride ICI to the
     follower devices via lax.ppermute (ring hops +1, +2),
  3. followers advance their follower-side log mirrors and commit
     indices (follower_commit_step rule), reply with
     (last_dirty, last_flushed) over the reverse hops,
  4. leaders fold replies into [G, R] slots positionally (slot r ↔
     ring hop r — no scatter needed) and run the batched quorum sweep.

A final psum over per-device committed counts stands in for the
cluster-level health/metrics aggregation (health_monitor analog).

On one host this exercises the virtual CPU mesh; on a real slice the
same program rides ICI. Cross-host (DCN) replication uses the host RPC
path instead (redpanda_tpu.rpc), mirroring the reference's
TCP backend; see SURVEY.md §5.8.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.consensus_state import GroupState, make_group_state
from ..observability import devplane
from ..ops.quorum import quorum_commit_step
from ..utils import compileguard
from .mesh import SHARD_AXIS

RF = 3  # replication factor modeled by the ring placement


class ClusterState(NamedTuple):
    """Per-device leader state + follower-side mirrors.

    Every array's axis 0 is the global group axis, sharded over the
    mesh. fol_* hold this device's *follower* role for the groups led
    by ring neighbors: fol_dirty[g, j] is the mirrored dirty offset for
    hop j+1's groups aligned to the neighbor's block positions."""

    leader: GroupState
    fol_dirty: jax.Array    # [G, RF-1] i64
    fol_flushed: jax.Array  # [G, RF-1] i64
    fol_commit: jax.Array   # [G, RF-1] i64
    fol_term: jax.Array     # [G, RF-1] i64 highest APPEND-path term seen
    # highest term this mirror VOTED in (voted_for bookkeeping). Kept
    # SEPARATE from fol_term: in raft, granting a vote adopts the term
    # for election purposes but does NOT truncate the log — truncation
    # happens when the new-term leader's APPEND conflicts. Folding
    # votes into fol_term consumed that term-bump signal and left
    # divergent suffixes untruncated after a voted election (caught by
    # the model-vs-broker differential, tests/test_ici_differential.py).
    voted_term: jax.Array   # [G, RF-1] i64
    # leader-side first retained log offset (snapshot boundary + 1):
    # retention advances it up to commit+1; a follower whose mirror
    # fell below it cannot be served appends and must install the
    # snapshot (recovery_stm.cc install_snapshot fallback over ICI)
    log_start: jax.Array    # [G] i64


def make_cluster_state(num_groups: int, replica_slots: int = 8) -> ClusterState:
    leader = make_group_state(num_groups, replica_slots)
    # every group: 3 voters in slots 0..2 (self + 2 ring followers)
    voters = jnp.zeros((num_groups, replica_slots), bool).at[:, :RF].set(True)
    leader = leader._replace(is_leader=jnp.ones(num_groups, bool), is_voter=voters)
    shape = (num_groups, RF - 1)
    neg = jnp.full(shape, -1, jnp.int64)
    return ClusterState(
        leader,
        neg,
        neg,
        neg,
        jnp.zeros(shape, jnp.int64),
        jnp.zeros(shape, jnp.int64),
        jnp.zeros(num_groups, jnp.int64),
    )


def cluster_tick(
    state: ClusterState, new_dirty: jax.Array
) -> tuple[ClusterState, jax.Array, jax.Array]:
    """One heartbeat round. new_dirty: [G] i64 — offsets appended to
    each leader's local log this tick. Returns (state, total_committed,
    total_installs): cluster-wide counts (psum'd) of groups whose
    commit advanced and of stranded followers that installed the
    leader's snapshot boundary this round."""
    axis = SHARD_AXIS
    n = jax.lax.axis_size(axis)
    leader = state.leader

    # 1. local append: self slot tracks the leader log (flush immediate
    # in this modeled step; the host runtime splits dirty/flushed).
    match = leader.match_index.at[:, 0].max(new_dirty)
    flushed = leader.flushed_index.at[:, 0].max(new_dirty)
    leader = leader._replace(match_index=match, flushed_index=flushed)
    old_commit = leader.commit_index

    # a deposed leader (is_leader False after an election) must not
    # heartbeat: its divergent suffix at the bumped term would poison
    # follower mirrors as untruncatable new-term data. Advertise term
    # -1 so followers reject the row wholesale.
    hb_term = jnp.where(leader.is_leader, leader.term, -1)
    payload = jnp.stack(
        [hb_term, leader.commit_index, leader.match_index[:, 0], state.log_start],
        axis=-1,
    )  # [G, 4]

    fol_dirty, fol_flushed, fol_commit, fol_term = (
        state.fol_dirty,
        state.fol_flushed,
        state.fol_commit,
        state.fol_term,
    )
    installs = jnp.zeros((), jnp.int64)
    replies = []
    for hop in range(1, RF):
        # 2. heartbeat rides ICI to the follower device
        fwd = [(i, (i + hop) % n) for i in range(n)]
        recv = jax.lax.ppermute(payload, axis, fwd)  # groups of device d-hop
        j = hop - 1
        r_term, r_commit, r_dirty, r_start = (
            recv[:, 0],
            recv[:, 1],
            recv[:, 2],
            recv[:, 3],
        )
        # 3. term gate (do_append_entries term check, consensus.cc:1752):
        # heartbeats from a stale term are rejected wholesale. The gate
        # includes the VOTE lane — granting a vote at term T bumps
        # currentTerm in raft, so older-term leaders are refused — while
        # new_term (the truncation trigger) keys on the APPEND lane
        # alone (voting never truncates; the first higher-term append
        # does).
        cur_term = jnp.maximum(fol_term[:, j], state.voted_term[:, j])
        accept = r_term >= cur_term
        new_term = r_term > fol_term[:, j]
        fol_term = fol_term.at[:, j].max(r_term)
        # follower accepts the append. Same term: the mirror only
        # advances. A NEW term: the follower adopts the new leader's
        # log wholesale — a divergent uncommitted suffix from the
        # deposed leader is TRUNCATED down to the new leader's dirty
        # offset (do_append_entries prev-term mismatch rule). Raft's
        # election log_ok gate guarantees the new leader's log covers
        # every committed entry, so the mirror can never truncate below
        # its own commit index (asserted by the multi-device tests).
        new_f_dirty = jnp.where(
            new_term,
            jnp.maximum(r_dirty, fol_commit[:, j]),
            jnp.where(
                accept,
                jnp.maximum(fol_dirty[:, j], r_dirty),
                fol_dirty[:, j],
            ),
        )
        # install_snapshot over ICI: the mirror's next entry fell below
        # the leader's retained log — appends cannot be served, the
        # follower adopts the snapshot boundary wholesale. The boundary
        # is <= the leader's commit (retention is snapshot-gated), so
        # installed state is committed by definition.
        stranded = accept & (fol_dirty[:, j] + 1 < r_start)
        snap = r_start - 1
        new_f_dirty = jnp.where(stranded, snap, new_f_dirty)
        new_f_flushed = jnp.where(
            new_term | stranded,
            new_f_dirty,
            jnp.maximum(fol_flushed[:, j], new_f_dirty),
        )
        proposed = jnp.minimum(r_commit, new_f_flushed)
        new_f_commit = jnp.where(
            accept & (proposed > fol_commit[:, j]), proposed, fol_commit[:, j]
        )
        # (no extra commit bump for installs: snap <= r_commit by the
        # retention invariant, so min(r_commit, flushed=snap) above
        # already commits the installed boundary)
        installs = installs + jnp.sum(stranded)
        fol_dirty = fol_dirty.at[:, j].set(new_f_dirty)
        fol_flushed = fol_flushed.at[:, j].set(new_f_flushed)
        fol_commit = fol_commit.at[:, j].set(new_f_commit)
        # reply returns over the reverse hop
        back = [(i, (i - hop) % n) for i in range(n)]
        reply = jnp.stack([new_f_dirty, new_f_flushed], axis=-1)
        replies.append(jax.lax.ppermute(reply, axis, back))

    # 4. fold replies: ring hop r maps positionally onto replica slot r
    for hop in range(1, RF):
        rep = replies[hop - 1]
        leader = leader._replace(
            match_index=leader.match_index.at[:, hop].max(rep[:, 0]),
            flushed_index=leader.flushed_index.at[:, hop].max(rep[:, 1]),
        )
    leader = quorum_commit_step(leader)

    advanced = jnp.sum(leader.commit_index > old_commit)
    total = jax.lax.psum(advanced, axis)
    total_installs = jax.lax.psum(installs, axis)
    return (
        ClusterState(
            leader, fol_dirty, fol_flushed, fol_commit, fol_term,
            state.voted_term, state.log_start
        ),
        total,
        total_installs,
    )


def election_round(
    state: ClusterState, candidate_mask: jax.Array, candidate_hop: int
) -> tuple[ClusterState, jax.Array, jax.Array]:
    """A cross-device ELECTION for the masked groups: the follower at
    ring hop `candidate_hop` campaigns to replace the (presumed dead)
    leader on the home device.

    The complete RequestVote exchange rides ICI (vote_stm.cc over
    rpc → here ppermute):

      1. the candidate device bumps its follower-side term and sends
         (term, last_dirty) to every OTHER replica device,
      2. each voter applies the raft vote rule — grant iff the
         candidate's term beats anything seen AND the candidate's log
         is at least as long (the log_ok gate, consensus.cc handle_vote
         / vote_stm): this is THE safety property that makes the
         truncation rule in cluster_tick lossless,
      3. grants ride back; candidate + grants >= quorum(RF) elects.

    Returns (state, elected_mask [G] on the candidate's HOME-block
    positions, cand_term [G]). The home device's leader lane observes
    the higher term (steps down: is_leader cleared for elected groups)
    — leadership HANDOFF of the SoA block itself is host-runtime
    bookkeeping (group_manager), exactly like the reference where the
    winning node starts serving and the deposed leader steps down.
    """
    if not (1 <= candidate_hop < RF):
        raise ValueError(f"candidate_hop must be in [1, {RF}): {candidate_hop}")
    axis = SHARD_AXIS
    n = jax.lax.axis_size(axis)
    j = candidate_hop - 1
    leader = state.leader
    fol_term = state.fol_term
    voted_term = state.voted_term

    # candidate_mask is HOME-block aligned (like `elected`): ship it to
    # the candidate device (home+hop), where the campaigning mirror
    # positions for home's groups live
    to_cand = [(i, (i + candidate_hop) % n) for i in range(n)]
    mask_at_cand = jax.lax.ppermute(candidate_mask, axis, to_cand)

    cand_term = jnp.maximum(fol_term[:, j], voted_term[:, j]) + 1
    cand_dirty = state.fol_dirty[:, j]
    payload = jnp.stack(
        [mask_at_cand.astype(jnp.int64), cand_term, cand_dirty], axis=-1
    )

    grants = jnp.ones_like(cand_term, dtype=jnp.int64)  # self-vote
    voter_hops = [h for h in range(RF) if h != candidate_hop]
    for h in voter_hops:
        # route candidate->voter: both arrays are aligned to the HOME
        # block's positions; the voter for hop h holds them at device
        # home+h, and the candidate sits at home+candidate_hop, so the
        # ICI shift is (h - candidate_hop) forward
        fwd = [(i, (i + h - candidate_hop) % n) for i in range(n)]
        recv = jax.lax.ppermute(payload, axis, fwd)
        is_cand, r_term, r_dirty = recv[:, 0] != 0, recv[:, 1], recv[:, 2]
        if h == 0:
            # the home device votes with its LEADER lane state
            my_term = leader.term
            my_dirty = leader.match_index[:, 0]
        else:
            my_term = jnp.maximum(
                fol_term[:, h - 1], voted_term[:, h - 1]
            )
            my_dirty = state.fol_dirty[:, h - 1]
        log_ok = r_dirty >= my_dirty
        grant = is_cand & (r_term > my_term) & log_ok
        # one vote per term (voted_for): granting adopts the candidate
        # term into the VOTE lane only — a later same-term candidate is
        # refused, but the APPEND-path term (fol_term) stays put so the
        # winner's first heartbeat still triggers the new-term
        # truncation of divergent mirrors (raft grants votes without
        # touching the log)
        if h == 0:
            leader = leader._replace(
                term=jnp.maximum(leader.term, jnp.where(grant, r_term, 0)),
                is_leader=leader.is_leader & ~grant,
            )
        else:
            voted_term = voted_term.at[:, h - 1].max(
                jnp.where(grant, r_term, -1)
            )
        back = [(i, (i - (h - candidate_hop)) % n) for i in range(n)]
        grants = grants + jax.lax.ppermute(
            grant.astype(jnp.int64), axis, back
        )

    elected_at_cand = mask_at_cand & (grants >= (RF // 2 + 1))
    # the winner records its own term (its next heartbeat carries it):
    # its mirror IS the new leader log, so the append-path term moves
    fol_term = fol_term.at[:, j].max(
        jnp.where(elected_at_cand, cand_term, -1)
    )
    voted_term = voted_term.at[:, j].max(
        jnp.where(mask_at_cand, cand_term, -1)
    )
    # report election results at the HOME block positions
    home_shift = [(i, (i - candidate_hop) % n) for i in range(n)]
    elected = jax.lax.ppermute(elected_at_cand, axis, home_shift)
    observed_term = jax.lax.ppermute(cand_term, axis, home_shift)

    # the deposed home leader steps down for elected groups
    # (consensus.cc term check -> become follower)
    new_leader = leader._replace(
        is_leader=leader.is_leader & ~elected,
        term=jnp.maximum(leader.term, jnp.where(elected, observed_term, 0)),
    )
    return (
        state._replace(
            leader=new_leader, fol_term=fol_term, voted_term=voted_term
        ),
        elected,
        jnp.where(elected, observed_term, -1),
    )


def _cluster_specs(mesh: Mesh):
    """(spec, ClusterState specs) for `mesh`, guarding the ring size:
    with fewer devices than the replication factor the ring hops wrap
    onto the sender — a leader would count its own payload as a
    follower ack and commit unreplicated data."""
    n = mesh.devices.size
    if n < RF:
        raise ValueError(f"mesh has {n} devices; ring replication needs >= RF={RF}")
    spec = P(SHARD_AXIS)
    state_specs = ClusterState(
        leader=jax.tree.map(lambda _: spec, make_group_state(1)),
        fol_dirty=spec,
        fol_flushed=spec,
        fol_commit=spec,
        fol_term=spec,
        voted_term=spec,
        log_start=spec,
    )
    return spec, state_specs


def election_round_sharded(mesh: Mesh, candidate_hop: int = 1):
    """Build the jitted shard_map'd cross-device election for `mesh`."""
    if not (1 <= candidate_hop < RF):
        raise ValueError(f"candidate_hop must be in [1, {RF}): {candidate_hop}")
    spec, state_specs = _cluster_specs(mesh)
    fn = jax.shard_map(
        lambda s, m: election_round(s, m, candidate_hop),
        mesh=mesh,
        in_specs=(state_specs, spec),
        out_specs=(state_specs, spec, spec),
    )
    return devplane.instrument(
        compileguard.instrument(jax.jit(fn), "cluster.election_round"),
        "cluster.election_round",
    )


def cluster_tick_sharded(mesh: Mesh):
    """Build the jitted shard_map'd cluster step for `mesh`."""
    spec, state_specs = _cluster_specs(mesh)
    fn = jax.shard_map(
        cluster_tick,
        mesh=mesh,
        in_specs=(state_specs, spec),
        out_specs=(state_specs, P(), P()),
    )
    return devplane.instrument(
        compileguard.instrument(jax.jit(fn), "cluster.tick"),
        "cluster.tick",
    )
