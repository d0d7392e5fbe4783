"""The fetch long-poll: a fetch that finds under min_bytes parks on the
partitions it read and their commit notification wakes it
(kafka/server.py _ParkedFetch, Consensus._notify_commit).

Counts and events: `reads` and `wakes` off the `kafka.fetch` span, the
listeners left on the groups, what the response holds. A wall-clock
bound here only tells an answer by wake-up from one by the deadline,
and stands seconds away from both.

Reference test model: kafka/server/tests/fetch_test.cc (fetch_empty,
fetch_one, fetch_leader_epoch: a delayed fetch completed by a produce,
by an error, by its deadline).
"""

import asyncio
import contextlib
import types

import pytest

from redpanda_tpu.kafka.client import (
    KafkaClient,
    TransactionalProducer,
    decode_record_set,
)
from redpanda_tpu.kafka.protocol import FETCH, ErrorCode
from redpanda_tpu.kafka.protocol.headers import RequestHeader
from redpanda_tpu.kafka.server import _ParkedFetch
from redpanda_tpu.models.fundamental import kafka_ntp
from redpanda_tpu.observability import trace

from test_kafka_e2e import broker_cluster, client_for

LONG_MS = 8000      # a max_wait no test waits out
WELL_BEFORE_S = 4.0  # an answer by wake-up or error, not by LONG_MS

ISOLATION = pytest.mark.parametrize(
    "read_committed", [False, True], ids=["read_uncommitted", "read_committed"]
)


@pytest.fixture
def window():
    """The process-global span store, keeping raw records for the test."""
    w = trace.WINDOW
    keep = w.keep_raw
    w.keep_raw = True
    w.reset()
    yield w
    w.keep_raw = keep
    w.reset()


def listeners(brokers) -> int:
    """Commit listeners left on any group of any broker."""
    return sum(
        len(c._commit_listeners)
        for b in brokers
        for c in b.group_manager._groups.values()
    )


async def until(cond, timeout=5.0):
    deadline = asyncio.get_event_loop().time() + timeout
    while not cond():
        assert asyncio.get_event_loop().time() < deadline, "never happened"
        await asyncio.sleep(0.005)


async def raw_fetch(client, topic, offset, *, read_committed=False,
                    max_wait_ms=LONG_MS, min_bytes=1, node=None, rack=None):
    """One FETCH of partition 0, answered once: the partition's row and
    the seconds it took (client.fetch would retry an error row)."""
    if node is None:
        conn = await client.leader_conn(topic, 0)
    else:
        conn = await client._connect_addr(node.kafka_advertised)
    req = KafkaClient._fetch_request(
        topic, 0, offset, 1 << 20, max_wait_ms, min_bytes, read_committed,
        rack=rack,
    )
    t0 = asyncio.get_event_loop().time()
    resp = await conn.request(FETCH, req, 11)
    return (
        resp.responses[0].partitions[0],
        asyncio.get_event_loop().time() - t0,
    )


def keys(row, offset=0, committed_only=False):
    aborted = None
    if committed_only:
        aborted = [
            (a.producer_id, a.first_offset)
            for a in (row.aborted_transactions or [])
        ]
    return [
        k for _o, k, _v in
        decode_record_set(row.records, from_offset=offset, aborted=aborted)
    ]


async def fetch_tags(window):
    """(reads, wakes) of the window's `kafka.fetch` spans; a root span
    closes when its response is written, a moment after the client has it."""
    def spans():
        return [s for s in window.status()["spans"] if s[0] == "kafka.fetch"]
    await until(spans)
    return [(s[7]["reads"], s[7]["wakes"]) for s in spans()]


@contextlib.asynccontextmanager
async def one_broker(tmp_path, topic="t"):
    """A broker, a writer and a consumer on connections of their own (a
    connection's requests are served in turn), one topic of one partition."""
    async with broker_cluster(tmp_path, 1) as brokers:
        async with client_for(brokers) as writer, client_for(brokers) as consumer:
            await writer.create_topic(topic, partitions=1, replication_factor=1)
            await consumer.metadata([topic])
            # the backend materialises the partition after the topic's ack
            table = brokers[0].partition_manager
            ntp = kafka_ntp(topic, 0)
            await until(lambda: table.get(ntp) is not None and table.get(ntp).is_leader)
            yield brokers, writer, consumer, table.get(ntp)


async def park(brokers, window, coro):
    """Start a fetch and return its task once it stands parked."""
    window.reset()
    task = asyncio.ensure_future(coro)
    await until(lambda: listeners(brokers) == 1 or task.done())
    assert not task.done(), "the fetch answered where it should have parked"
    return task


# -- the wake-up ----------------------------------------------------------


@ISOLATION
def test_a_commit_wakes_the_parked_fetch_once(tmp_path, window, read_committed):
    async def run():
        async with one_broker(tmp_path) as (brokers, writer, consumer, _p):
            parked = await park(brokers, window, raw_fetch(
                consumer, "t", 0, read_committed=read_committed))
            await writer.produce("t", 0, [(b"late", b"v")])
            row, took = await parked
            assert row.error_code == 0 and keys(row) == [b"late"]
            assert took < WELL_BEFORE_S
            assert await fetch_tags(window) == [(2, 1)]
            assert listeners(brokers) == 0

    asyncio.run(run())


@pytest.mark.parametrize("commit", [True, False], ids=["commit", "abort"])
def test_read_committed_is_woken_by_the_marker_not_the_data(
    tmp_path, window, commit
):
    async def run():
        async with one_broker(tmp_path) as (brokers, writer, consumer, part):
            tx = TransactionalProducer(writer, "tx-longpoll")
            await tx.init()
            parked = await park(brokers, window, raw_fetch(
                consumer, "t", 0, read_committed=True))
            tx.begin()
            await tx.produce("t", 0, [(b"a", b"1"), (b"b", b"2")])
            # the data is committed and the transaction open: the high
            # watermark passed the fetch, the LSO did not
            assert part.high_watermark() == 2
            assert part.last_stable_offset() == 0
            for _ in range(20):
                await asyncio.sleep(0)
            assert not parked.done() and listeners(brokers) == 1
            await (tx.commit() if commit else tx.abort())
            row, took = await parked
            assert took < WELL_BEFORE_S and row.error_code == 0
            # the whole range comes back, with the aborted transaction
            # named where it was aborted, and nothing at or past the LSO
            assert keys(row)[:2] == [b"a", b"b"]
            assert keys(row, committed_only=True) == ([b"a", b"b"] if commit else [])
            assert [a.first_offset for a in row.aborted_transactions or []] == (
                [] if commit else [0])
            assert row.last_stable_offset == part.last_stable_offset() == 3
            assert all(
                o < row.last_stable_offset
                for o, _k, _v in decode_record_set(row.records, from_offset=0)
            )
            # one pass that parked it, one after the marker's wake-up:
            # the data's commit cost it no pass
            assert await fetch_tags(window) == [(2, 1)]
            # and the wait behind the LSO was noted by the listener
            waits = [s for s in window.status()["spans"] if s[0] == "fetch.lso_wait"]
            assert len(waits) == 1
            assert listeners(brokers) == 0

    asyncio.run(run())


def test_an_open_transaction_s_data_wakes_read_uncommitted(tmp_path, window):
    async def run():
        async with one_broker(tmp_path) as (brokers, writer, consumer, part):
            tx = TransactionalProducer(writer, "tx-longpoll-ru")
            await tx.init()
            parked = await park(brokers, window, raw_fetch(consumer, "t", 0))
            tx.begin()
            await tx.produce("t", 0, [(b"open", b"1")])
            row, took = await parked
            assert took < WELL_BEFORE_S and keys(row) == [b"open"]
            assert part.last_stable_offset() == 0
            assert await fetch_tags(window) == [(2, 1)]
            await tx.abort()

    asyncio.run(run())


def test_min_bytes_above_one_batch_waits_for_the_second(tmp_path, window):
    async def run():
        async with one_broker(tmp_path) as (brokers, writer, consumer, part):
            parked = await park(brokers, window, raw_fetch(
                consumer, "t", 0, min_bytes=600))
            await writer.produce("t", 0, [(b"one", b"x" * 400)])
            assert part.high_watermark() == 1
            # woken, read, found under min_bytes, parked again
            await until(lambda: listeners(brokers) == 1 and not parked.done())
            for _ in range(20):
                await asyncio.sleep(0)
            assert not parked.done()
            await writer.produce("t", 0, [(b"two", b"x" * 400)])
            row, took = await parked
            assert took < WELL_BEFORE_S and keys(row) == [b"one", b"two"]
            assert await fetch_tags(window) == [(3, 2)]
            assert listeners(brokers) == 0

    asyncio.run(run())


def test_a_follower_s_commit_wakes_the_fetch_it_serves(tmp_path, window):
    """KIP-392: a rack-aware consumer parked at a follower is woken by
    the follower's own commit index (the append and heartbeat handlers)."""
    from redpanda_tpu.app import Broker, BrokerConfig
    from redpanda_tpu.rpc.loopback import LoopbackNetwork

    async def run():
        net = LoopbackNetwork()
        brokers = [
            Broker(
                BrokerConfig(
                    node_id=i, data_dir=str(tmp_path / f"n{i}"),
                    members=[0, 1, 2], election_timeout_s=0.15,
                    heartbeat_interval_s=0.03, rack=f"rack-{i}",
                ),
                loopback=net,
            )
            for i in range(3)
        ]
        for b in brokers:
            await b.start()
        addrs = {b.node_id: b.kafka_advertised for b in brokers}
        for b in brokers:
            b.config.peer_kafka_addresses = addrs
        await brokers[0].wait_controller_leader()
        try:
            async with client_for(brokers) as writer, client_for(brokers) as consumer:
                await writer.create_topic("ff", partitions=1, replication_factor=3)
                await writer.produce("ff", 0, [(b"k0", b"v0")], acks=-1)
                ntp = kafka_ntp("ff", 0)
                follower = next(
                    b for b in brokers
                    if not b.partition_manager.get(ntp).is_leader
                )
                fp = follower.partition_manager.get(ntp)
                await until(lambda: fp.high_watermark() == 1)
                parked = await park(brokers, window, raw_fetch(
                    consumer, "ff", 1, node=follower, rack=follower.config.rack))
                assert len(fp.consensus._commit_listeners) == 1
                await writer.produce("ff", 0, [(b"k1", b"v1")], acks=-1)
                row, took = await parked
                assert took < WELL_BEFORE_S and row.error_code == 0
                assert keys(row, offset=1) == [b"k1"]
                assert await fetch_tags(window) == [(2, 1)]
                assert listeners(brokers) == 0
        finally:
            for b in brokers:
                await b.stop()

    asyncio.run(run())


# -- errors end the wait at once -------------------------------------------


@ISOLATION
@pytest.mark.parametrize("how", ["step_down", "remove"])
def test_an_error_under_a_parked_fetch_answers_at_once(
    tmp_path, window, read_committed, how
):
    async def run():
        async with one_broker(tmp_path) as (brokers, _w, consumer, part):
            parked = await park(brokers, window, raw_fetch(
                consumer, "t", 0, read_committed=read_committed))
            if how == "step_down":
                part.consensus._step_down(part.consensus.term + 1)
            else:
                await brokers[0].partition_manager.remove(part.ntp)
            row, took = await parked
            assert took < WELL_BEFORE_S
            assert row.error_code == int(ErrorCode.not_leader_for_partition)
            assert not row.records
            assert await fetch_tags(window) == [(2, 1)]
            assert listeners(brokers) == 0
            assert not part.consensus._commit_listeners

    asyncio.run(run())


# -- the deadline -----------------------------------------------------------


@ISOLATION
def test_an_idle_fetch_answers_empty_at_its_deadline(tmp_path, window, read_committed):
    async def run():
        async with one_broker(tmp_path) as (brokers, _w, consumer, _p):
            window.reset()
            row, took = await raw_fetch(
                consumer, "t", 0, read_committed=read_committed, max_wait_ms=300)
            assert row.error_code == 0 and not row.records
            assert 0.25 < took < WELL_BEFORE_S
            ((reads, wakes),) = await fetch_tags(window)
            assert reads <= 2 and wakes == 0
            assert listeners(brokers) == 0

    asyncio.run(run())


@ISOLATION
def test_min_bytes_zero_registers_no_listener(
    tmp_path, window, monkeypatch, read_committed
):
    from redpanda_tpu.raft.consensus import Consensus

    added = []
    add = Consensus.add_commit_listener
    monkeypatch.setattr(
        Consensus, "add_commit_listener",
        lambda self, cb: (added.append(cb), add(self, cb)),
    )

    async def run():
        async with one_broker(tmp_path) as (brokers, _w, consumer, _p):
            window.reset()
            row, took = await raw_fetch(
                consumer, "t", 0, read_committed=read_committed, min_bytes=0)
            assert row.error_code == 0 and not row.records
            assert took < WELL_BEFORE_S
            assert await fetch_tags(window) == [(1, 0)]
            assert added == [] and listeners(brokers) == 0

    asyncio.run(run())


# -- nothing is left behind --------------------------------------------------


@pytest.mark.parametrize(
    "how", ["handler_cancelled", "client_gone", "server_stopped"]
)
def test_no_listener_outlives_its_fetch(tmp_path, window, how):
    async def run():
        async with one_broker(tmp_path) as (brokers, _w, consumer, part):
            server = brokers[0].kafka_server
            if how == "handler_cancelled":
                # the handler as the connection's task runs it
                req = KafkaClient._fetch_request(
                    "t", 0, 0, 1 << 20, LONG_MS, 1, True)
                task = await park(brokers, window, server.handle_fetch(
                    RequestHeader(FETCH.key, 11, 1, "longpoll"), req))
                task.cancel()
                with pytest.raises(asyncio.CancelledError):
                    await task
                assert listeners(brokers) == 0
                return
            wait_ms = 400 if how == "client_gone" else LONG_MS
            task = await park(brokers, window, raw_fetch(
                consumer, "t", 0, max_wait_ms=wait_ms))
            assert len(part.consensus._commit_listeners) == 1
            if how == "client_gone":
                # a connection is served in turn: the server learns of
                # the close when the fetch's deadline ends its wait
                await consumer.close()
                await until(lambda: listeners(brokers) == 0, timeout=4.0)
            else:
                await server.stop()
                assert listeners(brokers) == 0
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(run())


# -- the listener alone -------------------------------------------------------


class _Replica:
    """What a listener reads of a partition."""

    def __init__(self):
        self.ntp = "ntp"
        self.is_leader = True
        self.hw = self.lso = 5
        self.cbs = []

    def high_watermark(self):
        return self.hw

    def last_stable_offset(self):
        return self.lso

    def add_commit_listener(self, cb):
        self.cbs.append(cb)

    def remove_commit_listener(self, cb):
        self.cbs.remove(cb)

    def notify(self):
        for cb in list(self.cbs):
            cb()


def _parked(replica, read_committed):
    table = types.SimpleNamespace(get=lambda ntp: table.held)
    table.held = replica
    fetch = _ParkedFetch(table, read_committed)
    fetch.park([(replica, 5)])
    return fetch, table


@ISOLATION
def test_several_notifications_before_the_fetch_runs_are_one_wake(read_committed):
    async def run():
        replica = _Replica()
        fetch, _ = _parked(replica, read_committed)
        replica.notify()                 # nothing moved: no wake
        assert fetch.wakes == 0
        for hw in (6, 7, 8):
            replica.hw = replica.lso = hw
            replica.notify()
        assert fetch.wakes == 1
        assert await fetch.wait(asyncio.get_event_loop().time() + 5.0)
        fetch.park([(replica, 8)])       # under min_bytes: parks again
        replica.notify()
        assert fetch.wakes == 1
        assert not await fetch.wait(asyncio.get_event_loop().time() + 0.05)
        fetch.unpark()
        assert replica.cbs == []

    asyncio.run(run())


@ISOLATION
def test_the_high_watermark_alone_wakes_read_uncommitted_only(read_committed):
    async def run():
        replica = _Replica()
        fetch, _ = _parked(replica, read_committed)
        replica.hw = 9               # an open transaction's data
        replica.notify()
        assert fetch.wakes == (0 if read_committed else 1)
        assert bool(fetch.lso_wait_ns) == read_committed
        noted = fetch.lso_wait_ns
        replica.notify()
        assert fetch.lso_wait_ns == noted    # the first moment stands
        replica.lso = 9              # its marker
        replica.notify()
        assert fetch.wakes == 1
        fetch.unpark()

    asyncio.run(run())


@pytest.mark.parametrize("what", ["leadership", "gone"])
def test_a_replica_that_changed_hands_wakes_whatever_the_offsets(what):
    async def run():
        replica = _Replica()
        fetch, table = _parked(replica, True)
        if what == "leadership":
            replica.is_leader = False
        else:
            table.held = None
        replica.notify()
        assert fetch.wakes == 1 and not fetch.lso_wait_ns
        fetch.unpark()

    asyncio.run(run())


# -- the audit's one finding: a fold with nobody to tell ------------------------


@pytest.mark.parametrize("backend", ["host", "device"])
def test_prewarm_moves_no_commit_index_it_cannot_report(monkeypatch, backend):
    """`ShardGroupArrays.prewarm` folds with no caller to hand the
    advanced rows to `_notify_commit`: a row that waits for a recompute
    waits for the next live fold, which moves it and says so."""
    import numpy as np

    from redpanda_tpu.raft.shard_state import ShardGroupArrays

    monkeypatch.setenv("RP_QUORUM_BACKEND", backend)
    arrays = ShardGroupArrays(capacity=16)
    row = arrays.alloc_row()
    arrays.is_leader[row] = True
    arrays.is_voter[row, :3] = True
    arrays.voter_epoch += 1
    arrays.match_index[row, :3] = 7
    arrays.flushed_index[row, :3] = 7
    arrays.mark_quorum_dirty(row)   # as a configuration change leaves it
    before = int(arrays.commit_index[row])
    arrays.prewarm()
    assert int(arrays.commit_index[row]) == before and arrays.quorum_dirty[row]
    empty = np.empty(0, np.int64)
    advanced = arrays.device_tick(empty, empty, empty, empty, empty)
    assert list(advanced) == [row] and int(arrays.commit_index[row]) == 7


# -- a row this shard cannot observe ---------------------------------------------


def test_a_row_another_shard_serves_keeps_the_timed_re_read(tmp_path, window):
    """With a shard router and a partition served by another shard, the
    commit happens in another process: such a fetch, and only such a
    fetch, re-reads on its timer and registers nothing."""
    from redpanda_tpu.app import BrokerConfig
    from redpanda_tpu.ssx.sharded_broker import ShardedBroker

    async def run():
        sb = ShardedBroker(
            BrokerConfig(
                node_id=0, data_dir=str(tmp_path / "n0"), members=[0],
                election_timeout_s=0.3, heartbeat_interval_s=0.05,
                enable_admin=False,
            ),
            n_shards=2,
        )
        await sb.start()
        assert sb.active, f"unexpected stand-down: {sb.standdown}"
        writer = KafkaClient([("127.0.0.1", sb.kafka_port)])
        consumer = KafkaClient([("127.0.0.1", sb.kafka_port)])
        try:
            async def created():
                try:
                    await writer.create_topic("t", partitions=4, replication_factor=1)
                except Exception:
                    pass
                return sb.broker.shard_table.counts().get(1, 0) > 0
            deadline = asyncio.get_event_loop().time() + 15.0
            while not await created():
                assert asyncio.get_event_loop().time() < deadline
                await asyncio.sleep(0.1)
            away = next(
                p for p in range(4)
                if sb.broker.shard_table.shard_for(kafka_ntp("t", p)) == 1
            )
            await consumer.metadata(["t"])
            # the other shard elects and serves: a first record says so
            deadline = asyncio.get_event_loop().time() + 15.0
            while True:
                try:
                    assert await writer.produce("t", away, [(b"near", b"v")]) == 0
                    break
                except Exception:
                    assert asyncio.get_event_loop().time() < deadline
                    await asyncio.sleep(0.1)

            async def fetch():
                conn = await consumer.leader_conn("t", away)
                req = KafkaClient._fetch_request(
                    "t", away, 1, 1 << 20, LONG_MS, 1, False)
                t0 = asyncio.get_event_loop().time()
                resp = await conn.request(FETCH, req, 11)
                return (resp.responses[0].partitions[0],
                        asyncio.get_event_loop().time() - t0)

            window.reset()
            parked = asyncio.ensure_future(fetch())
            await asyncio.sleep(0.3)
            assert not parked.done() and listeners([sb.broker]) == 0
            passes = window.status()["host"]["fetch.read"]["count"]
            assert passes >= 3          # re-reading while nothing arrives
            await writer.produce("t", away, [(b"far", b"v")])
            row, took = await parked
            assert took < WELL_BEFORE_S and row.error_code == 0
            assert keys(row, offset=1) == [b"far"]
            assert listeners([sb.broker]) == 0
        finally:
            await writer.close()
            await consumer.close()
            await sb.stop()

    asyncio.run(run())
