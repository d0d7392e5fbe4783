"""Transactions end-to-end: tx coordinator, markers, LSO, aborted
filtering, transactional offset commits, coordinator failover.

Reference test model: src/v/cluster/tests/rm_stm_tests.cc,
tm_stm_tests.cc, kafka/server/tests (produce_consume + tx paths) and
rptest/tests/transactions_test.py.
"""

import asyncio

import pytest

from redpanda_tpu.kafka.client import (
    KafkaClient,
    KafkaClientError,
    TransactionalProducer,
)
from redpanda_tpu.kafka.protocol import ErrorCode
from redpanda_tpu.models.fundamental import kafka_ntp

from test_kafka_e2e import broker_cluster, client_for


def _partition(brokers, ntp):
    for b in brokers:
        p = b.partition_manager.get(ntp)
        if p is not None and p.is_leader:
            return p
    return None


async def _commit_roundtrip(tmp_path, n):
    async with broker_cluster(tmp_path, n) as brokers:
        async with client_for(brokers) as client:
            rf = 1 if n == 1 else 3
            await client.create_topic("t", partitions=2, replication_factor=rf)
            tx = TransactionalProducer(client, "tx-1")
            await tx.init()
            assert tx.pid >= 0 and tx.epoch == 0

            tx.begin()
            await tx.produce("t", 0, [(b"a", b"1"), (b"b", b"2")])
            await tx.produce("t", 1, [(b"c", b"3")])

            # before commit: uncommitted data invisible to READ_COMMITTED
            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=50
            )
            assert got == []
            # ...but visible to READ_UNCOMMITTED
            got = await client.fetch("t", 0, 0, max_wait_ms=50)
            assert [(k, v) for _o, k, v in got] == [(b"a", b"1"), (b"b", b"2")]

            await tx.commit()

            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=500
            )
            assert [(k, v) for _o, k, v in got] == [(b"a", b"1"), (b"b", b"2")]
            got = await client.fetch(
                "t", 1, 0, read_committed=True, max_wait_ms=500
            )
            assert [(k, v) for _o, k, v in got] == [(b"c", b"3")]


def test_tx_commit_single(tmp_path):
    asyncio.run(_commit_roundtrip(tmp_path, 1))


@pytest.mark.timing
def test_tx_commit_rf3(tmp_path):
    asyncio.run(_commit_roundtrip(tmp_path, 3))


async def _abort_invisible(tmp_path):
    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("t", partitions=1, replication_factor=1)
            tx = TransactionalProducer(client, "tx-abort")
            await tx.init()

            tx.begin()
            await tx.produce("t", 0, [(b"dead", b"x")])
            await tx.abort()

            tx.begin()
            await tx.produce("t", 0, [(b"live", b"y")])
            await tx.commit()

            # READ_COMMITTED: aborted records filtered out
            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=500
            )
            assert [(k, v) for _o, k, v in got] == [(b"live", b"y")]

            # interleaved with a plain producer
            await client.produce("t", 0, [(b"plain", b"z")])
            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=500
            )
            assert [k for _o, k, _v in got] == [b"live", b"plain"]


def test_tx_abort_invisible(tmp_path):
    asyncio.run(_abort_invisible(tmp_path))


async def _lso_blocks_read_committed(tmp_path):
    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("t", partitions=1, replication_factor=1)
            # a committed prefix
            await client.produce("t", 0, [(b"k0", b"v0")])

            tx = TransactionalProducer(client, "tx-lso")
            await tx.init()
            tx.begin()
            await tx.produce("t", 0, [(b"open", b"tx")])

            p = _partition(brokers, kafka_ntp("t", 0))
            assert p is not None
            # LSO pinned at the open tx's first offset
            assert p.last_stable_offset() == 1
            assert p.high_watermark() == 2

            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=50
            )
            assert [k for _o, k, _v in got] == [b"k0"]

            await tx.commit()
            assert p.last_stable_offset() == p.high_watermark() == 3
            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=500
            )
            assert [k for _o, k, _v in got] == [b"k0", b"open"]


def test_tx_lso(tmp_path):
    asyncio.run(_lso_blocks_read_committed(tmp_path))


async def _txn_offset_commit(tmp_path):
    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("src", partitions=1, replication_factor=1)
            await client.create_topic("dst", partitions=1, replication_factor=1)
            await client.produce("src", 0, [(b"in", b"1")])

            # consume-transform-produce with EOS offsets
            tx = TransactionalProducer(client, "tx-eos")
            await tx.init()
            tx.begin()
            await tx.produce("dst", 0, [(b"out", b"1")])
            await tx.send_offsets("g-eos", {("src", 0): 1})

            # offsets invisible until commit
            g = client.group("g-eos")
            offs = await g.fetch_offsets({"src": [0]})
            assert offs == {}

            await tx.commit()
            offs = await g.fetch_offsets({"src": [0]})
            assert offs == {("src", 0): 1}


def test_txn_offset_commit(tmp_path):
    asyncio.run(_txn_offset_commit(tmp_path))


async def _txn_offset_abort(tmp_path):
    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("src", partitions=1, replication_factor=1)
            tx = TransactionalProducer(client, "tx-eos-abort")
            await tx.init()
            tx.begin()
            await tx.send_offsets("g-ab", {("src", 0): 7})
            await tx.abort()
            g = client.group("g-ab")
            offs = await g.fetch_offsets({"src": [0]})
            assert offs == {}


def test_txn_offset_abort(tmp_path):
    asyncio.run(_txn_offset_abort(tmp_path))


async def _epoch_fencing(tmp_path):
    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("t", partitions=1, replication_factor=1)
            old = TransactionalProducer(client, "tx-fence")
            await old.init()
            old.begin()
            await old.produce("t", 0, [(b"zombie-tx", b"x")])

            # a new incarnation takes over: aborts the old tx, bumps epoch
            new = TransactionalProducer(client, "tx-fence")
            await new.init()
            assert new.pid == old.pid
            assert new.epoch == old.epoch + 1

            # the zombie's writes were aborted
            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=50
            )
            assert got == []

            # zombie produce is fenced
            with pytest.raises(KafkaClientError) as ei:
                await old.produce("t", 0, [(b"more", b"x")])
            assert ei.value.code in (
                int(ErrorCode.invalid_producer_epoch),
                int(ErrorCode.producer_fenced),
            )
            # zombie end_txn is fenced at the coordinator
            with pytest.raises(KafkaClientError):
                await old.commit()

            # the new incarnation works
            new.begin()
            await new.produce("t", 0, [(b"fresh", b"y")])
            await new.commit()
            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=500
            )
            assert [k for _o, k, _v in got] == [b"fresh"]


def test_tx_epoch_fencing(tmp_path):
    asyncio.run(_epoch_fencing(tmp_path))


async def _coordinator_failover(tmp_path):
    """A tx prepared on one coordinator completes after leadership
    moves: the new leader's replay resumes marker delivery."""
    async with broker_cluster(tmp_path, 3) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("t", partitions=1, replication_factor=3)
            tx = TransactionalProducer(client, "tx-failover")
            await tx.init()
            tx.begin()
            await tx.produce("t", 0, [(b"k", b"v")])

            # find the tx coordinator partition and transfer leadership
            coord = brokers[0].tx_coordinator
            ntp = coord.ntp_for("tx-failover")
            leader_broker = None
            for b in brokers:
                p = b.partition_manager.get(ntp)
                if p is not None and p.is_leader:
                    leader_broker = b
                    break
            assert leader_broker is not None
            others = [
                b.node_id for b in brokers if b.node_id != leader_broker.node_id
            ]
            p = leader_broker.partition_manager.get(ntp)
            await p.consensus.transfer_leadership(others[0])

            # the client re-resolves the coordinator and commits
            await asyncio.sleep(0.3)
            await tx.commit()
            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=1000
            )
            assert [(k, v) for _o, k, v in got] == [(b"k", b"v")]


def test_tx_coordinator_failover(tmp_path):
    asyncio.run(_coordinator_failover(tmp_path))


async def _tx_timeout_abort(tmp_path):
    """An abandoned transaction is aborted by the expiry sweep and the
    producer fenced by the epoch bump."""
    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("t", partitions=1, replication_factor=1)
            tx = TransactionalProducer(client, "tx-expire", timeout_ms=300)
            await tx.init()
            tx.begin()
            await tx.produce("t", 0, [(b"stale", b"x")])

            p = _partition(brokers, kafka_ntp("t", 0))
            deadline = asyncio.get_event_loop().time() + 5.0
            while p.last_stable_offset() != p.high_watermark():
                assert asyncio.get_event_loop().time() < deadline, (
                    "expiry sweep never aborted the tx"
                )
                await asyncio.sleep(0.1)
            got = await client.fetch(
                "t", 0, 0, read_committed=True, max_wait_ms=50
            )
            assert got == []


def test_tx_timeout_abort(tmp_path):
    asyncio.run(_tx_timeout_abort(tmp_path))


def test_a_transaction_bounds_the_lso_until_its_marker_is_committed():
    """rm_stm applies a marker when it commits; the tracker observes at
    append, so it keeps a closed transaction under the LSO until the
    high watermark has passed its marker (Kafka's unreplicatedTxns)."""
    from redpanda_tpu.cluster.tx_state import TxTracker

    t = TxTracker()
    t.observe_data(7, 0, 10)
    assert t.first_unstable_offset(11) == 10           # open
    t.observe_marker(7, 0, True, 11, high_watermark=11)
    assert t.first_unstable_offset(11) == 10           # marker appended, not committed
    assert t.first_unstable_offset(12) is None         # the marker is below the high watermark
    assert t.closing == []
    # an aborted range is reported from the append on, as before
    t.observe_data(8, 0, 12)
    t.observe_marker(8, 0, False, 13, high_watermark=12)
    assert t.aborted_in(12, 14) == [(8, 12)] and t.first_unstable_offset(13) == 12
    # a follower is never asked: it prunes by the high watermark each marker arrives under
    for i in range(50):
        t.observe_data(9, 0, 20 + 2 * i)
        t.observe_marker(9, 0, True, 21 + 2 * i, high_watermark=20 + 2 * i)
    assert len(t.closing) == 1
    t.clear()
    assert t.closing == [] and t.first_unstable_offset(0) is None


# -- KIP-447: the group's metadata in TxnOffsetCommit v3 -----------------


def _group_of(brokers, group_id):
    """The group as its coordinator holds it."""
    for b in brokers:
        gc = b.group_coordinator
        for shard in gc._groups.values():
            if group_id in shard:
                return shard[group_id]
    return None


async def _a_member_and_a_producer(client, group_id, tx_id, instance=None):
    from redpanda_tpu.kafka.client import GroupClient

    member = GroupClient(client, group_id)
    await member.join([("range", b"")], group_instance_id=instance)
    await member.sync([(member.member_id, b"")])
    tx = TransactionalProducer(client, tx_id)
    await tx.init()
    tx.begin()
    return member, tx


async def _v3_fenced(tmp_path, case):
    import types

    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("src", partitions=1, replication_factor=1)
            member, tx = await _a_member_and_a_producer(
                client, "g-447", "tx-447",
                instance="i-1" if case == "fenced_instance" else None)
            sent = {
                "stale_generation": types.SimpleNamespace(
                    generation=member.generation - 1, member_id=member.member_id,
                    group_instance_id=None),
                "unknown_member": types.SimpleNamespace(
                    generation=member.generation, member_id="gone-member",
                    group_instance_id=None),
                "fenced_instance": types.SimpleNamespace(
                    generation=member.generation, member_id="zombie-member",
                    group_instance_id="i-1"),
            }[case]
            with pytest.raises(KafkaClientError) as ei:
                await tx.send_offsets("g-447", {("src", 0): 5}, member=sent)
            g = _group_of(brokers, "g-447")
            # refused before anything was staged
            assert g.pending_tx == {}
            await tx.commit()
            assert await member.fetch_offsets({"src": [0]}, require_stable=True) == {}
            # the live member's own metadata is taken
            tx.begin()
            await tx.send_offsets("g-447", {("src", 0): 5}, member=member)
            assert list(g.pending_tx) == [tx.pid]
            await tx.commit()
            assert await member.fetch_offsets({"src": [0]}, require_stable=True) == {
                ("src", 0): 5}
            return ei.value.code


@pytest.mark.parametrize("case, code", [
    ("stale_generation", ErrorCode.illegal_generation),
    ("unknown_member", ErrorCode.unknown_member_id),
    ("fenced_instance", ErrorCode.fenced_instance_id),
])
def test_txn_offset_commit_v3_fences_a_member_the_group_no_longer_has(tmp_path, case, code):
    assert asyncio.run(_v3_fenced(tmp_path, case)) == int(code)


async def _older_versions_are_not_fenced_by_member(tmp_path, version):
    """v0-2 carry no group metadata: a producer outside the group stages
    offsets into a group with a live member, as before v3 existed."""
    from redpanda_tpu.kafka.protocol import Msg
    from redpanda_tpu.kafka.protocol.tx_apis import ADD_OFFSETS_TO_TXN, TXN_OFFSET_COMMIT

    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("src", partitions=1, replication_factor=1)
            member, tx = await _a_member_and_a_producer(client, "g-old", "tx-old")
            who = dict(transactional_id="tx-old", producer_id=tx.pid,
                       producer_epoch=tx.epoch)
            conn = await tx._coordinator()
            resp = await conn.request(
                ADD_OFFSETS_TO_TXN, Msg(**who, group_id="g-old"), 1)
            assert resp.error_code == 0
            gconn = await member.coordinator()
            resp = await gconn.request(TXN_OFFSET_COMMIT, Msg(
                **who, group_id="g-old",
                topics=[Msg(name="src", partitions=[Msg(
                    partition_index=0, committed_offset=3,
                    committed_metadata=None)])]), version)
            assert [p.error_code for t in resp.topics for p in t.partitions] == [0]
            await tx.commit()
            return await member.fetch_offsets({"src": [0]})


@pytest.mark.parametrize("version", [0, 1, 2])
def test_txn_offset_commit_below_v3_answers_as_before(tmp_path, version):
    got = asyncio.run(_older_versions_are_not_fenced_by_member(tmp_path, version))
    assert got == {("src", 0): 3}


async def _find_coordinator_once(tmp_path, monkeypatch):
    from redpanda_tpu.kafka import client as client_mod
    from redpanda_tpu.kafka.protocol.group_apis import FIND_COORDINATOR

    asked = []
    request = client_mod.BrokerConnection.request

    async def counted(self, api, req, version):
        if api.key == FIND_COORDINATOR.key:
            asked.append(req.key_type)
        return await request(self, api, req, version)

    monkeypatch.setattr(client_mod.BrokerConnection, "request", counted)
    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("src", partitions=1, replication_factor=1)
            tx = TransactionalProducer(client, "tx-once")
            await tx.init()
            # both coordinator topics made and led: from here on a
            # lookup is answered at its first asking
            await client.group("g-once").coordinator()
            asked.clear()
            for i in range(6):
                tx.begin()
                await tx.send_offsets("g-once", {("src", 0): i})
                await tx.commit()
            found = list(asked)
            assert await client.group("g-once").fetch_offsets({"src": [0]}) == {
                ("src", 0): 5}
    return found


def test_send_offsets_finds_the_group_s_coordinator_once(tmp_path, monkeypatch):
    # six transactions: one lookup of the group's coordinator (key type
    # 0), and none of the transaction coordinator's, which init found
    assert asyncio.run(_find_coordinator_once(tmp_path, monkeypatch)) == [0]


# -- transactions that span many partitions -----------------------------


async def _many_partitions(tmp_path, partitions, monkeypatch):
    """One transactional producer writes one batch to each of
    `partitions` partitions of a 3-broker, RF=3 topic in one transaction
    (one AddPartitionsToTxn, one ProduceRequest a leader), commits, does
    it again and aborts, and a third time and commits; each partition is
    read `read_committed` and `read_uncommitted`."""
    from redpanda_tpu.kafka import client as client_mod
    from redpanda_tpu.kafka.protocol import PRODUCE
    from redpanda_tpu.kafka.protocol.tx_apis import ADD_PARTITIONS_TO_TXN

    sent = []
    request = client_mod.BrokerConnection.request
    holder = {}

    async def counted(self, api, req, version):
        if api.key == PRODUCE.key:
            # with the leaders as the client knew them when it routed
            # this request: an election may move one before the end
            view = {p: leader for (t, p), leader in holder["client"]._leaders.items()
                    if t == "t"}
            sent.append((self, sorted(p.index for t in req.topics for p in t.partitions),
                         view))
        elif api.key == ADD_PARTITIONS_TO_TXN.key:
            sent.append(("add", sorted(p for t in req.topics for p in t.partitions)))
        return await request(self, api, req, version)

    monkeypatch.setattr(client_mod.BrokerConnection, "request", counted)
    async with broker_cluster(tmp_path, 3) as brokers:
        async with client_for(brokers) as client:
            holder["client"] = client
            await client.create_topic("t", partitions=partitions, replication_factor=3)
            tx = TransactionalProducer(client, "tx-many")
            await tx.init()
            rounds = []
            for n, commit in enumerate((True, False, True)):
                tx.begin()
                sent.clear()
                bases = await tx.produce_batches("t", {
                    p: [(b"k%d.%d.%d" % (n, p, i), b"v%d" % n) for i in range(p % 3 + 1)]
                    for p in range(partitions)})
                rounds.append((list(sent), bases))
                await (tx.commit() if commit else tx.abort())
            got = {}
            for p in range(partitions):
                committed = await client.fetch("t", p, 0, read_committed=True, max_wait_ms=50)
                everything = await client.fetch("t", p, 0, max_wait_ms=50)
                got[p] = (committed, everything)
    return rounds, got


@pytest.mark.parametrize("partitions", [3, 32])
def test_a_transaction_of_many_partitions_commits_and_aborts_whole(
        tmp_path, partitions, monkeypatch):
    rounds, got = asyncio.run(_many_partitions(tmp_path, partitions, monkeypatch))
    every = list(range(partitions))
    first, second, third = rounds
    # the first transaction adds every partition in one AddPartitionsToTxn
    # and sends one ProduceRequest to each leader, with all it leads;
    # later transactions add them again, each in one request. The
    # requests go side by side, so the first ones, one a leader the
    # client knew of, carry every partition; a partition answered
    # not-leader goes again after them
    for sent, bases in rounds:
        assert sent[0] == ("add", every)
        leaders = sent[1][2]
        assert sorted(leaders) == every
        n = len(set(leaders.values()))
        produced = [ps for _conn, ps, _view in sent[1:n + 1]]
        assert sorted(p for ps in produced for p in ps) == every
        assert len({conn for conn, _ps, _view in sent[1:n + 1]}) == n
        assert sorted(leaders[ps[0]] for ps in produced) == sorted(set(leaders.values()))
        for ps in produced:
            assert len({leaders[p] for p in ps}) == 1
        assert sorted(bases) == every
    for p in every:
        committed, everything = got[p]
        n = p % 3 + 1
        # a commit marker after the first and the third batch, an abort
        # marker after the second: each takes an offset
        assert first[1][p] == 0
        assert second[1][p] == n + 1 and third[1][p] == 2 * n + 2
        assert [k for _o, k, _v in committed] == (
            [b"k0.%d.%d" % (p, i) for i in range(n)]
            + [b"k2.%d.%d" % (p, i) for i in range(n)])
        assert [o for o, _k, _v in committed] == (
            list(range(n)) + list(range(2 * n + 2, 3 * n + 2)))
        assert len(everything) == 3 * n


async def _read_committed_over_many_aborted_ranges(tmp_path, partitions, rounds):
    """`rounds` transactions of one producer over every partition, every
    other one aborted, then one read_committed FETCH of all partitions."""
    from redpanda_tpu.kafka.client import decode_record_set
    from redpanda_tpu.kafka.protocol import FETCH, Msg

    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic("t", partitions=partitions, replication_factor=1)
            tx = TransactionalProducer(client, "tx-ranges")
            await tx.init()
            for n in range(rounds):
                tx.begin()
                await tx.produce_batches("t", {
                    p: [(b"%d" % n, b"%d" % p)] * (n % 2 + 1) for p in range(partitions)})
                await (tx.abort() if n % 2 else tx.commit())
            conn = await client.leader_conn("t", 0)
            resp = await conn.request(FETCH, Msg(
                rack_id="", replica_id=-1, max_wait_ms=0, min_bytes=0, max_bytes=1 << 24,
                isolation_level=1, session_id=0, session_epoch=-1, forgotten_topics_data=[],
                topics=[Msg(topic="t", partitions=[
                    Msg(partition=p, current_leader_epoch=-1, fetch_offset=0,
                        log_start_offset=0, partition_max_bytes=1 << 20)
                    for p in range(partitions)])]), conn.pick_version(FETCH, 11))
            out = {}
            for pr in resp.responses[0].partitions:
                aborted = [(a.producer_id, a.first_offset) for a in pr.aborted_transactions]
                out[pr.partition_index] = (
                    pr.error_code, pr.high_watermark, pr.last_stable_offset, aborted,
                    decode_record_set(pr.records, aborted=aborted))
    return tx.pid, out


def test_read_committed_drops_every_aborted_range_of_every_partition(tmp_path):
    partitions, rounds = 8, 10
    pid, out = asyncio.run(
        _read_committed_over_many_aborted_ranges(tmp_path, partitions, rounds))
    assert sorted(out) == list(range(partitions))
    for p, (code, hw, lso, aborted, records) in out.items():
        # committed transactions hold one record, aborted ones two; each
        # transaction ends with its marker
        assert code == 0 and hw == lso == (rounds // 2) * (1 + 2 + 2)
        # each aborted transaction named once, at its first offset
        assert aborted == [(pid, 5 * k + 2) for k in range(rounds // 2)]
        assert [(k, v) for _o, k, v in records] == [
            (b"%d" % n, b"%d" % p) for n in range(0, rounds, 2)]
