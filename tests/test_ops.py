"""Device-kernel tests: differential vs the scalar reference backend.

SURVEY.md §8b: device-batched quorum math must stay bit-identical to
scalar semantics — these tests randomize cluster states and compare
every group's decision against redpanda_tpu.raft.quorum_scalar.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from redpanda_tpu.models.consensus_state import (
    SELF_SLOT,
    GroupState,
    make_group_state,
)
from redpanda_tpu.ops import crc32c as dev_crc
from redpanda_tpu.ops import quorum as q
from redpanda_tpu.raft import quorum_scalar as ref
from redpanda_tpu.utils import crc as host_crc

I64_MIN = -(2**63)


def random_state(rng, g=64, r=8, joint_prob=0.2):
    state = make_group_state(g, r)
    n_voters = rng.integers(1, r + 1, g)
    voter = np.zeros((g, r), bool)
    for i in range(g):
        voter[i, : n_voters[i]] = True
    old = np.zeros((g, r), bool)
    for i in range(g):
        if rng.random() < joint_prob:
            k = rng.integers(1, r + 1)
            slots = rng.permutation(r)[:k]
            old[i, slots] = True
    match = rng.integers(-1, 1000, (g, r)).astype(np.int64)
    flushed = match - rng.integers(0, 50, (g, r)).astype(np.int64)
    commit = rng.integers(-1, 500, g).astype(np.int64)
    term_start = rng.integers(0, 600, g).astype(np.int64)
    return state._replace(
        is_leader=jnp.asarray(rng.random(g) < 0.8),
        is_voter=jnp.asarray(voter),
        is_voter_old=jnp.asarray(old),
        match_index=jnp.asarray(match),
        flushed_index=jnp.asarray(flushed),
        commit_index=jnp.asarray(commit),
        term_start=jnp.asarray(term_start),
    )


def scalar_expected_commit(state: GroupState):
    # pull tensors host-side once; per-element jnp reads are device ops
    match = np.asarray(state.match_index)
    flushed = np.asarray(state.flushed_index)
    voter = np.asarray(state.is_voter)
    voter_old = np.asarray(state.is_voter_old)
    is_leader = np.asarray(state.is_leader)
    commit = np.asarray(state.commit_index)
    term_start = np.asarray(state.term_start)
    g, r = match.shape
    out = []
    for i in range(g):
        if not is_leader[i]:
            out.append(int(commit[i]))
            continue
        replicas = [
            ref.ReplicaState(
                match_index=int(match[i, j]),
                flushed_index=int(flushed[i, j]),
                is_voter=bool(voter[i, j]),
                is_voter_old=bool(voter_old[i, j]),
            )
            for j in range(r)
        ]
        out.append(
            ref.leader_commit_index(
                replicas,
                leader_flushed=int(flushed[i, SELF_SLOT]),
                commit_index=int(commit[i]),
                term_start=int(term_start[i]),
            )
        )
    return np.array(out, dtype=np.int64)


class TestQuorumCommit:
    @pytest.mark.parametrize("seed", range(8))
    def test_differential_vs_scalar(self, seed):
        rng = np.random.default_rng(seed)
        state = random_state(rng)
        new = q.quorum_commit_step(state)
        expected = scalar_expected_commit(state)
        np.testing.assert_array_equal(np.asarray(new.commit_index), expected)

    def test_simple_majority(self):
        # 3 voters: self flushed 10, followers at 8 and 5 → commit 8
        state = make_group_state(1, 4)
        state = state._replace(
            is_leader=jnp.array([True]),
            is_voter=jnp.array([[True, True, True, False]]),
            match_index=jnp.array([[10, 8, 5, I64_MIN]], jnp.int64),
            flushed_index=jnp.array([[10, 8, 5, I64_MIN]], jnp.int64),
            term_start=jnp.array([0], jnp.int64),
        )
        new = q.quorum_commit_step(state)
        assert int(new.commit_index[0]) == 8

    def test_flush_clamp(self):
        # followers ahead of leader's own flush → clamp to leader flushed
        state = make_group_state(1, 4)
        state = state._replace(
            is_leader=jnp.array([True]),
            is_voter=jnp.array([[True, True, True, False]]),
            match_index=jnp.array([[20, 20, 20, I64_MIN]], jnp.int64),
            flushed_index=jnp.array([[7, 20, 20, I64_MIN]], jnp.int64),
            term_start=jnp.array([0], jnp.int64),
        )
        new = q.quorum_commit_step(state)
        assert int(new.commit_index[0]) == 7

    def test_term_gate_blocks_old_term_entries(self):
        # majority at 8 but current term starts at 9 → no commit
        state = make_group_state(1, 4)
        state = state._replace(
            is_leader=jnp.array([True]),
            is_voter=jnp.array([[True, True, True, False]]),
            match_index=jnp.array([[10, 8, 8, I64_MIN]], jnp.int64),
            flushed_index=jnp.array([[10, 8, 8, I64_MIN]], jnp.int64),
            term_start=jnp.array([9], jnp.int64),
            commit_index=jnp.array([3], jnp.int64),
        )
        new = q.quorum_commit_step(state)
        assert int(new.commit_index[0]) == 3

    def test_joint_config_takes_min(self):
        state = make_group_state(1, 6)
        state = state._replace(
            is_leader=jnp.array([True]),
            is_voter=jnp.array([[True, True, True, False, False, False]]),
            is_voter_old=jnp.array([[False, False, False, True, True, True]]),
            match_index=jnp.array([[10, 10, 10, 4, 4, 4]], jnp.int64),
            flushed_index=jnp.array([[10, 10, 10, 4, 4, 4]], jnp.int64),
            term_start=jnp.array([0], jnp.int64),
        )
        new = q.quorum_commit_step(state)
        assert int(new.commit_index[0]) == 4

    def test_non_leader_untouched(self):
        state = make_group_state(4, 4)
        state = state._replace(
            is_voter=jnp.ones((4, 4), bool),
            match_index=jnp.full((4, 4), 100, jnp.int64),
            flushed_index=jnp.full((4, 4), 100, jnp.int64),
        )
        new = q.quorum_commit_step(state)
        assert np.all(np.asarray(new.commit_index) == -1)


class TestFollowerCommit:
    @pytest.mark.parametrize("seed", range(4))
    def test_differential(self, seed):
        rng = np.random.default_rng(100 + seed)
        g = 128
        state = make_group_state(g, 4)
        flushed = rng.integers(-1, 100, g).astype(np.int64)
        commit = rng.integers(-1, 80, g).astype(np.int64)
        leader_commit = rng.integers(-1, 150, g).astype(np.int64)
        state = state._replace(
            flushed_index=state.flushed_index.at[:, SELF_SLOT].set(jnp.asarray(flushed)),
            commit_index=jnp.asarray(commit),
        )
        new = q.follower_commit_step(state, jnp.asarray(leader_commit))
        got = np.asarray(new.commit_index)
        for i in range(g):
            exp = ref.follower_commit_index(int(commit[i]), int(flushed[i]), int(leader_commit[i]))
            assert int(got[i]) == exp


class TestFoldReplies:
    def test_seq_guard_drops_stale(self):
        state = make_group_state(2, 4)
        state = state._replace(last_seq=state.last_seq.at[0, 1].set(10))
        new = q.fold_replies(
            state,
            group_idx=jnp.array([0, 0]),
            replica_slot=jnp.array([1, 2]),
            last_dirty=jnp.array([50, 60], jnp.int64),
            last_flushed=jnp.array([50, 60], jnp.int64),
            seq=jnp.array([5, 1], jnp.int64),  # seq 5 <= 10 → stale for slot 1
        )
        assert int(new.match_index[0, 1]) == -1  # dropped
        assert int(new.match_index[0, 2]) == 60  # applied

    def test_monotone_and_duplicates(self):
        state = make_group_state(1, 4)
        new = q.fold_replies(
            state,
            group_idx=jnp.array([0, 0]),
            replica_slot=jnp.array([1, 1]),
            last_dirty=jnp.array([30, 20], jnp.int64),
            last_flushed=jnp.array([25, 22], jnp.int64),
            seq=jnp.array([2, 3], jnp.int64),
        )
        # duplicates resolve via max
        assert int(new.match_index[0, 1]) == 30
        assert int(new.flushed_index[0, 1]) == 25
        assert int(new.last_seq[0, 1]) == 3

    def test_heartbeat_tick_end_to_end(self):
        state = make_group_state(3, 4)
        state = state._replace(
            is_leader=jnp.ones(3, bool),
            is_voter=jnp.zeros((3, 4), bool).at[:, :3].set(True),
            match_index=state.match_index.at[:, 0].set(100),
            flushed_index=state.flushed_index.at[:, 0].set(100),
            term_start=jnp.zeros(3, jnp.int64),
        )
        # replies from both followers of each group at offset 100
        gi = jnp.array([0, 0, 1, 1, 2, 2])
        slot = jnp.array([1, 2, 1, 2, 1, 2])
        off = jnp.full(6, 100, jnp.int64)
        seq = jnp.ones(6, jnp.int64)
        new = q.heartbeat_tick(state, gi, slot, off, off, seq)
        assert np.all(np.asarray(new.commit_index) == 100)


class TestDeviceCrc32c:
    @pytest.mark.parametrize("seed,stride", [(0, 64), (1, 256), (2, 1024)])
    def test_differential_vs_host(self, seed, stride):
        rng = np.random.default_rng(seed)
        n = 32
        lens = rng.integers(0, stride + 1, n).astype(np.int64)
        mat = np.zeros((n, stride), dtype=np.uint8)
        for i in range(n):
            mat[i, : lens[i]] = rng.integers(0, 256, lens[i], dtype=np.uint8)
        dev = dev_crc.crc32c_batch_device(mat, lens)
        host = host_crc.crc32c_batch(mat, lens.astype(np.uint64))
        np.testing.assert_array_equal(dev, host)

    def test_known_vector(self):
        data = np.zeros((1, 16), dtype=np.uint8)
        payload = b"123456789"
        data[0, :9] = np.frombuffer(payload, np.uint8)
        out = dev_crc.crc32c_batch_device(data, np.array([9]))
        assert int(out[0]) == 0xE3069283

    # One differential test of the chunk-parallel body against the host
    # CRC, a case each: the lengths around a chunk's and the stride's
    # edges, every row bucket the benchmark's warmer makes (8 to 64),
    # chunk counts that are no power of two (ops/fused.py's crc_w), and
    # a matrix above _TILE chunk rows, which runs the tiled loop.
    @pytest.mark.parametrize(
        "rows,stride,lens",
        [
            *[(1, 2048, [n]) for n in (0, 1, 511, 512, 513, 2048 - 512, 2048)],
            *[(r, 1024, None) for r in (1, 3, 8, 9, 16, 31, 32, 39, 64)],
            (8, 3 * 512, None),
            (8, 129 * 512, None),
            (17, 262144, None),
        ],
        ids=lambda v: "x".join(map(str, v)) if isinstance(v, list) else str(v),
    )
    def test_chunk_parallel_differential(self, rows, stride, lens):
        rng = np.random.default_rng(rows * 131 + stride)
        if lens is None:
            lens = rng.integers(0, stride + 1, rows)
            lens[0] = stride
            lens[-1] = max(0, stride - 512)
        lens = np.asarray(lens, np.int64)
        mat = np.zeros((rows, stride), dtype=np.uint8)
        for i, n in enumerate(lens):
            mat[i, :n] = rng.integers(0, 256, n, dtype=np.uint8)
        want = [host_crc.crc32c(mat[i, :n].tobytes()) for i, n in enumerate(lens)]
        want = np.array(want, np.uint32)
        if stride & (stride - 1):
            # crc32c_batch_device pads a stride to a power of two: the
            # kernel itself takes any whole number of chunks, as bytes
            # (ops/fused.py) and as words
            got = np.asarray(dev_crc.crc32c_device(mat, lens))
            words = np.asarray(dev_crc.crc32c_device(mat.view("<u4"), lens))
            np.testing.assert_array_equal(words, want)
        else:
            got = dev_crc.crc32c_batch_device(mat, lens)
        np.testing.assert_array_equal(got, want)

    @staticmethod
    def _lowered(rows, lanes, dtype):
        fn = dev_crc.crc32c_device
        while hasattr(fn, "fn"):  # devplane / compileguard wrappers
            fn = fn.fn
        return fn.lower(
            jax.ShapeDtypeStruct((rows, lanes), dtype),
            jax.ShapeDtypeStruct((rows,), jnp.int64),
        ).as_text()

    @pytest.mark.parametrize(
        "lanes,dtype", [(65536, jnp.uint8), (16384, jnp.uint32)]
    )
    def test_live_shape_lowers_to_no_loop(self, lanes, dtype):
        """[8, 65536] bytes is what a fetch of the benchmark's batches
        dispatches (as words): every chunk in one step. Above the tile
        the loop is over tiles."""
        assert "while" not in self._lowered(8, lanes, dtype)
        assert "while" in self._lowered(128, lanes, dtype)

    def test_one_transfer_each_way_and_warmed_shapes_only(self, monkeypatch):
        """A call is one dispatch that takes its host arrays up with it
        (no transfer of its own before it) and one readback, and after
        the warmer's calls (full rows at every row bucket) no count of
        payloads of that body compiles anything."""
        from redpanda_tpu.utils import compileguard

        moved = []
        monkeypatch.setattr(
            dev_crc.devplane,
            "count_transfer",
            lambda nbytes, direction: moved.append((direction, nbytes)),
        )
        kernel = dev_crc.crc32c_device

        def dispatch(*args):
            assert all(type(a) is np.ndarray for a in args)
            moved.append(("dispatch", sum(a.nbytes for a in args)))
            return kernel(*args)

        monkeypatch.setattr(dev_crc, "crc32c_device", dispatch)
        body = 1500
        for rows in (8, 16, 32, 64):  # benchmark/warmers/crc.py
            dev_crc.crc32c_batch_device(
                np.zeros((rows, body), np.uint8), np.full(rows, body, np.int64)
            )
        warmed = compileguard.compile_counts()["crc32c.device"]
        rng = np.random.default_rng(3)
        for n in (1, 3, 39):
            mat = rng.integers(0, 256, (n, body), dtype=np.uint8)
            lens = np.full(n, body, np.int64)
            del moved[:]
            got = dev_crc.crc32c_batch_device(mat, lens)
            assert [d for d, _ in moved] == ["h2d", "dispatch", "d2h"]
            assert moved[0][1] == moved[1][1]  # counted what went up
            np.testing.assert_array_equal(
                got, host_crc.crc32c_batch(mat, lens.astype(np.uint64))
            )
        assert compileguard.compile_counts()["crc32c.device"] == warmed


class TestClusterStep:
    def test_multi_device_tick(self):
        from redpanda_tpu.parallel import (
            cluster_tick_sharded,
            make_cluster_state,
            make_mesh,
            place_rows,
            shard_group_state,
        )

        n_dev = len(jax.devices())
        assert n_dev == 8, "conftest must provide 8 virtual devices"
        mesh = make_mesh(8)
        g = 64  # 8 groups per device
        state = shard_group_state(make_cluster_state(g), mesh)
        tick = cluster_tick_sharded(mesh)
        new_dirty = place_rows(jnp.full(g, 5, jnp.int64), mesh)
        state, total, _inst = tick(state, new_dirty)
        # after one round every leader has both follower acks at 5 and
        # its own flush at 5 → all 64 groups commit
        assert int(total) == g
        assert np.all(np.asarray(state.leader.commit_index) == 5)
        # commit index reaches followers on the NEXT heartbeat (real
        # raft propagation): after tick 1 mirrors still hold -1
        assert np.all(np.asarray(state.fol_commit) == -1)
        # second tick with no new appends: no further leader advancement,
        # but followers learn the commit index
        zero = place_rows(jnp.full(g, -1, jnp.int64), mesh)
        state, total2, _inst = tick(state, zero)
        assert int(total2) == 0
        assert np.all(np.asarray(state.fol_commit) == 5)

    def test_stranded_follower_installs_snapshot_over_ici(self):
        """A mirror whose next entry fell below the leader's retained
        log cannot be append-served: one tick installs the snapshot
        boundary (committed by construction), and the NEXT tick
        catches it up to the leader's head normally."""
        from redpanda_tpu.parallel import (
            cluster_tick_sharded,
            make_cluster_state,
            make_mesh,
        )
        from redpanda_tpu.parallel.mesh import group_sharding

        mesh = make_mesh(8)
        g = 64
        state = make_cluster_state(g)
        sharding = group_sharding(mesh)
        put = lambda s: jax.tree.map(
            lambda a: jax.device_put(a, sharding), s
        )
        state = put(state)
        tick = cluster_tick_sharded(mesh)
        dirty9 = jax.device_put(jnp.full(g, 9, jnp.int64), sharding)
        none = jax.device_put(jnp.full(g, -1, jnp.int64), sharding)
        state, total, inst = tick(state, dirty9)
        assert int(total) == g and int(inst) == 0

        # strand hop-1 mirrors at 2; retention moves leaders' log
        # start to 8 (snapshot boundary 7 <= commit 9)
        state = put(
            state._replace(
                fol_dirty=state.fol_dirty.at[:, 0].set(2),
                fol_flushed=state.fol_flushed.at[:, 0].set(2),
                fol_commit=state.fol_commit.at[:, 0].set(2),
                log_start=jnp.full(g, 8, jnp.int64),
            )
        )
        state, _, inst = tick(state, none)
        assert int(inst) == g
        fd = np.asarray(state.fol_dirty)
        fc = np.asarray(state.fol_commit)
        # installed exactly to the boundary, commit jumped with it
        assert (fd[:, 0] == 7).all(), fd[:, 0]
        assert (fc[:, 0] >= 7).all(), fc[:, 0]
        # healthy hop-2 mirrors never install
        assert (fd[:, 1] == 9).all()
        # next tick: normal appends resume from the boundary
        state, _, inst2 = tick(state, none)
        assert int(inst2) == 0
        fd = np.asarray(state.fol_dirty)
        assert (fd[:, 0] == 9).all(), fd[:, 0]


class TestHostDeviceTickParity:
    """The numpy host fold (shard_state.host_tick) must be bit-identical
    to the compiled device sweep (ops.quorum.heartbeat_tick) — the
    backend choice is a pure performance decision."""

    def test_differential_random(self):
        import numpy as np

        from redpanda_tpu.raft.shard_state import ShardGroupArrays

        rng = np.random.default_rng(7)
        for trial in range(5):
            g, r = 64, 8
            mk = lambda: ShardGroupArrays(capacity=g, replica_slots=r)
            a_host, a_dev = mk(), mk()
            # random-but-valid state, mirrored into both
            for arrs in (a_host, a_dev):
                arrs.is_leader[:] = rng.random(g) < 0.7
                nv = rng.integers(1, 4, g)
                for row in range(g):
                    arrs.is_voter[row, : 2 * nv[row] + 1] = True
                    if rng.random() < 0.2:
                        arrs.is_voter_old[row, : 2 * nv[row] - 1] = True
                arrs.match_index[:] = rng.integers(-1, 50, (g, r))
                arrs.flushed_index[:] = np.minimum(
                    arrs.match_index, rng.integers(-1, 50, (g, r))
                )
                arrs.commit_index[:] = rng.integers(-1, 10, g)
                arrs.term_start[:] = rng.integers(0, 5, g)
                arrs.last_visible[:] = arrs.commit_index
                arrs.last_seq[:] = rng.integers(0, 3, (g, r))
            # identical state in both (copy from host arrays)
            for name in ("is_leader", "is_voter", "is_voter_old",
                         "match_index", "flushed_index", "commit_index",
                         "term_start", "last_visible", "last_seq"):
                getattr(a_dev, name)[:] = getattr(a_host, name)

            m = 96
            rows = rng.integers(0, g, m).astype(np.int64)
            slots = rng.integers(1, r, m).astype(np.int64)
            dirty = rng.integers(-1, 60, m).astype(np.int64)
            flushed = np.minimum(dirty, rng.integers(-1, 60, m)).astype(np.int64)
            seqs = rng.integers(0, 6, m).astype(np.int64)

            adv_h = a_host.host_tick(rows, slots, dirty, flushed, seqs)
            import os
            os.environ["RP_QUORUM_BACKEND"] = "device"
            try:
                adv_d = a_dev.device_tick(rows, slots, dirty, flushed, seqs)
            finally:
                del os.environ["RP_QUORUM_BACKEND"]

            assert np.array_equal(adv_h, adv_d), f"trial {trial}"
            for name in ("match_index", "flushed_index", "commit_index",
                         "last_visible", "last_seq"):
                assert np.array_equal(
                    getattr(a_host, name), getattr(a_dev, name)
                ), f"trial {trial}: {name} diverged"

    def test_incremental_sweep_flush_clamp_release(self):
        """The incremental sweep must not starve the flush-clamp
        release: followers fully ack, leader's local fsync lands only
        BETWEEN ticks (no remote change) — the next tick must still
        advance commit via the SELF-slot change detection."""
        import numpy as np

        from redpanda_tpu.models.consensus_state import SELF_SLOT
        from redpanda_tpu.raft.shard_state import ShardGroupArrays

        a = ShardGroupArrays(capacity=8, replica_slots=8)
        row = 0
        a.is_leader[row] = True
        a.is_voter[row, :3] = True  # self + 2 peers
        a.term_start[row] = 0
        # self appended to 10, fsync lags at 5
        a.match_index[row, SELF_SLOT] = 10
        a.flushed_index[row, SELF_SLOT] = 5

        rows = np.array([row, row], np.int64)
        slots = np.array([1, 2], np.int64)
        ten = np.array([10, 10], np.int64)
        # tick 1: both followers ack dirty=flushed=10 → commit clamps
        # to the leader's own flushed offset (5)
        adv = a.host_tick(rows, slots, ten, ten, np.array([1, 1], np.int64))
        assert list(adv) == [row]
        assert a.commit_index[row] == 5
        # local fsync completes between ticks; no remote values change
        a.flushed_index[row, SELF_SLOT] = 10
        # tick 2: replies identical except the seq guard — the sweep
        # must detect the SELF-slot movement and release the clamp
        adv = a.host_tick(rows, slots, ten, ten, np.array([2, 2], np.int64))
        assert list(adv) == [row]
        assert a.commit_index[row] == 10
        # tick 3: true steady state — nothing changed, nothing advances
        adv = a.host_tick(rows, slots, ten, ten, np.array([3, 3], np.int64))
        assert len(adv) == 0
        assert a.commit_index[row] == 10
        # seq guard still folded on the skip path
        assert a.last_seq[row, 1] == 3 and a.last_seq[row, 2] == 3


class TestClusterElection:
    """Cross-device elections + divergence truncation over the ICI ring
    (the beyond-happy-path multi-chip semantics: vote_stm's log_ok gate
    and do_append_entries' new-term truncation, as collectives)."""

    def _sharded_state(self, g=64):
        from redpanda_tpu.parallel import make_cluster_state, make_mesh
        from redpanda_tpu.parallel.mesh import group_sharding

        mesh = make_mesh(8)
        state = make_cluster_state(g)
        sharding = group_sharding(mesh)
        state = jax.tree.map(lambda a: jax.device_put(a, sharding), state)
        return mesh, state, sharding, g

    def test_failover_election_log_ok_gate(self):
        from redpanda_tpu.parallel import (
            cluster_tick_sharded,
            election_round_sharded,
        )

        mesh, state, sharding, g = self._sharded_state()
        tick = cluster_tick_sharded(mesh)
        dirty5 = jax.device_put(jnp.full(g, 5, jnp.int64), sharding)
        none = jax.device_put(jnp.full(g, -1, jnp.int64), sharding)
        state, _, _ = tick(state, dirty5)
        state, _, _ = tick(state, none)  # commit=5 known everywhere

        # home leaders die after appending a divergent UNCOMMITTED
        # suffix (dirty 9) that never replicated
        state = state._replace(
            leader=state.leader._replace(
                match_index=state.leader.match_index.at[:, 0].set(9),
                flushed_index=state.leader.flushed_index.at[:, 0].set(9),
            )
        )

        # hop-1 followers (log dirty=5 == every voter's committed data)
        # campaign for ALL groups and must WIN: quorum = self + hop-2
        # voter (log_ok 5>=5), without the dead home's vote
        elect = election_round_sharded(mesh, candidate_hop=1)
        mask = jax.device_put(jnp.ones(g, bool), sharding)
        state, elected, term = elect(state, mask)
        assert bool(np.all(np.asarray(elected))), "log_ok quorum failed"
        assert np.all(np.asarray(term) == 1)
        # deposed home leaders stepped down and observed the new term
        assert not np.any(np.asarray(state.leader.is_leader))
        assert np.all(np.asarray(state.leader.term) == 1)

    def test_short_log_candidate_loses(self):
        from redpanda_tpu.parallel import (
            cluster_tick_sharded,
            election_round_sharded,
        )

        mesh, state, sharding, g = self._sharded_state()
        tick = cluster_tick_sharded(mesh)
        dirty5 = jax.device_put(jnp.full(g, 5, jnp.int64), sharding)
        none = jax.device_put(jnp.full(g, -1, jnp.int64), sharding)
        state, _, _ = tick(state, dirty5)
        state, _, _ = tick(state, none)

        # hop-1 candidate artificially LOSES its tail (mirror dirty 3 <
        # committed 5): the hop-2 voter's log_ok must reject it — the
        # gate that makes truncation-on-new-term lossless
        state = state._replace(
            fol_dirty=state.fol_dirty.at[:, 0].set(3),
            fol_flushed=state.fol_flushed.at[:, 0].set(3),
            fol_commit=state.fol_commit.at[:, 0].set(3),
        )
        elect = election_round_sharded(mesh, candidate_hop=1)
        mask = jax.device_put(jnp.ones(g, bool), sharding)
        state, elected, _term = elect(state, mask)
        assert not np.any(np.asarray(elected)), (
            "a candidate missing committed entries won an election"
        )

    def test_non_uniform_mask_targets_home_blocks(self):
        """candidate_mask is HOME-block aligned: masking only device
        0's groups must elect exactly those groups, nothing else."""
        from redpanda_tpu.parallel import (
            cluster_tick_sharded,
            election_round_sharded,
        )

        mesh, state, sharding, g = self._sharded_state()
        tick = cluster_tick_sharded(mesh)
        dirty5 = jax.device_put(jnp.full(g, 5, jnp.int64), sharding)
        none = jax.device_put(jnp.full(g, -1, jnp.int64), sharding)
        state, _, _ = tick(state, dirty5)
        state, _, _ = tick(state, none)
        per_dev = g // 8
        mask = jnp.zeros(g, bool).at[:per_dev].set(True)  # device 0 only
        elect = election_round_sharded(mesh, candidate_hop=1)
        state, elected, _t = elect(state, jax.device_put(mask, sharding))
        e = np.asarray(elected)
        assert e[:per_dev].all(), "home block 0 not elected"
        assert not e[per_dev:].any(), "election leaked to other blocks"
        # only block 0's home leaders stepped down
        il = np.asarray(state.leader.is_leader)
        assert not il[:per_dev].any() and il[per_dev:].all()

    def test_one_vote_per_term(self):
        """Granting adopts the candidate's term (voted_for): a SECOND
        candidate at the same term (the other ring follower) must not
        also win — no two leaders for one group and term."""
        from redpanda_tpu.parallel import (
            cluster_tick_sharded,
            election_round_sharded,
        )

        mesh, state, sharding, g = self._sharded_state()
        tick = cluster_tick_sharded(mesh)
        dirty5 = jax.device_put(jnp.full(g, 5, jnp.int64), sharding)
        none = jax.device_put(jnp.full(g, -1, jnp.int64), sharding)
        state, _, _ = tick(state, dirty5)
        state, _, _ = tick(state, none)
        mask = jax.device_put(jnp.ones(g, bool), sharding)
        state, won1, t1 = election_round_sharded(mesh, 1)(state, mask)
        assert np.all(np.asarray(won1))
        assert np.all(np.asarray(t1) == 1)
        # a STALE hop-2 candidate that never heard of the election
        # (both its append-path and vote records forced back to 0)
        # campaigns at the SAME term 1: every voter's voted_term
        # already adopted term 1 when granting, so it gets only its
        # self-vote and loses everywhere
        state = state._replace(
            fol_term=jax.device_put(
                jnp.asarray(state.fol_term).at[:, 1].set(0), sharding
            ),
            voted_term=jax.device_put(
                jnp.asarray(state.voted_term).at[:, 1].set(0), sharding
            ),
        )
        state, won2, _t2 = election_round_sharded(mesh, 2)(state, mask)
        assert not np.any(np.asarray(won2)), "two leaders at one term"
        # once it LEARNS term 1 through the APPEND path, its next
        # candidacy runs at term 2 and wins legitimately — elections
        # stay live. (Reset the vote lane too: the failed candidacy
        # self-recorded term 1 there, which would mask the append-path
        # learning this step exists to exercise.)
        state = state._replace(
            fol_term=jax.device_put(
                jnp.asarray(state.fol_term).at[:, 1].set(1), sharding
            ),
            voted_term=jax.device_put(
                jnp.asarray(state.voted_term).at[:, 1].set(0), sharding
            ),
        )
        state, won3, t3 = election_round_sharded(mesh, 2)(state, mask)
        assert np.all(np.asarray(won3))
        assert np.all(np.asarray(t3) == 2)

    def test_new_term_heartbeat_truncates_divergent_mirror(self):
        from redpanda_tpu.parallel import cluster_tick_sharded

        mesh, state, sharding, g = self._sharded_state()
        tick = cluster_tick_sharded(mesh)
        dirty5 = jax.device_put(jnp.full(g, 5, jnp.int64), sharding)
        none = jax.device_put(jnp.full(g, -1, jnp.int64), sharding)
        state, _, _ = tick(state, dirty5)
        state, _, _ = tick(state, none)
        assert np.all(np.asarray(state.fol_commit) == 5)

        # followers mirrored a deposed leader's uncommitted suffix
        # (dirty 7 > committed 5); the NEW leader (term 1) has dirty 5
        state = state._replace(
            fol_dirty=jax.device_put(
                jnp.full_like(state.fol_dirty, 7), sharding
            ),
            fol_flushed=jax.device_put(
                jnp.full_like(state.fol_flushed, 7), sharding
            ),
            leader=state.leader._replace(
                term=state.leader.term + 1,  # new-term leadership
            ),
        )
        state, _, _ = tick(state, none)
        fd = np.asarray(state.fol_dirty)
        fc = np.asarray(state.fol_commit)
        # divergent suffix truncated to the new leader's log...
        assert np.all(fd == 5), fd[:4]
        # ...and NEVER below anything committed
        assert np.all(fc == 5) and np.all(fd >= fc)
