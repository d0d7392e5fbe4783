"""The span tags that say how loaded the tick frame and the front end
are (ISSUE 27): `tick.upload` carries `rows`, `replies` and `bucket`
beside `seed`; `kafka.produce` carries `open`, the produce requests
its broker had open when this one arrived. Both cost no clock read,
are kept with the raw records a window keeps, and reach
`devplane.status()["spans"]`."""

import asyncio

import numpy as np
import pytest

from benchmark.reference import encode_batch
from redpanda_tpu.app import Broker, BrokerConfig
from redpanda_tpu.kafka.client import KafkaClient
from redpanda_tpu.kafka.protocol import (
    PRODUCE,
    RequestHeader,
    encode_request_header,
    produce_fast,
)
from redpanda_tpu.kafka.server import ConnectionContext
from redpanda_tpu.observability import trace
from redpanda_tpu.raft.shard_state import ShardGroupArrays
from redpanda_tpu.rpc.loopback import LoopbackNetwork
from test_devplane import _run_armed

NAME, TAGS = 0, 7
LIMIT_S = 60


@pytest.fixture
def raw_spans(monkeypatch):
    """The window store keeping raw records, as in a traced run."""
    monkeypatch.setattr(trace, "ENABLED", True)
    monkeypatch.setattr(trace.WINDOW, "keep_raw", True)
    trace.WINDOW.reset()
    yield lambda name: [
        s[TAGS] for s in trace.WINDOW.status()["spans"] if s[NAME] == name
    ]
    trace.WINDOW.reset()


def _leaders(arrays: ShardGroupArrays, n: int) -> np.ndarray:
    rows = np.array([arrays.alloc_row() for _ in range(n)], np.int64)
    arrays.is_leader[rows] = True
    arrays.is_voter[rows, :3] = True
    arrays.voter_epoch += 1
    return rows


@pytest.mark.parametrize(
    "k, m, bucket", [(1, 2, 8), (8, 3, 8), (9, 1, 16), (40, 64, 64)],
    ids=["1_row", "8_rows", "9_rows", "40_rows_64_replies"],
)
def test_fold_records_rows_replies_and_bucket(monkeypatch, raw_spans, k, m, bucket):
    monkeypatch.setenv("RP_QUORUM_BACKEND", "device")
    arrays = ShardGroupArrays(capacity=64)
    rows = _leaders(arrays, 40)
    window = (
        np.resize(rows[:k], m), np.resize(np.array([1, 2], np.int64), m),
        np.full(m, 7, np.int64), np.full(m, 7, np.int64), np.full(m, 1, np.int64),
    )
    arrays._fold_on_device(rows[:k], window, bucket)
    assert raw_spans("tick.upload") == [
        {"seed": 1, "rows": k, "replies": m, "bucket": bucket}
    ]
    # a second fold finds the resident state: only `seed` differs
    arrays._fold_on_device(rows[:k], window, bucket)
    assert raw_spans("tick.upload")[1] == {
        "seed": 0, "rows": k, "replies": m, "bucket": bucket}


@pytest.mark.parametrize("k", [1, 8, 9, 40])
def test_device_tick_tags_the_bucket_it_used(monkeypatch, raw_spans, k):
    """Through `device_tick`, which picks the bucket itself: the power
    of two at or above the larger of the replies and the touched rows,
    8 at the least."""
    monkeypatch.setenv("RP_QUORUM_BACKEND", "device")
    arrays = ShardGroupArrays(capacity=64)
    rows = _leaders(arrays, 40)
    empty = np.empty(0, np.int64)
    arrays.device_tick(empty, empty, empty, empty, empty)  # the dirty rows
    picked = rows[:k]
    arrays.match_index[picked, 0] = 5
    arrays.flushed_index[picked, 0] = 5
    r = np.repeat(picked, 2)
    s = np.tile(np.array([1, 2], np.int64), k)
    off = np.full(2 * k, 5, np.int64)
    advanced = arrays.device_tick(r, s, off, off, off)
    assert sorted(advanced) == list(picked)
    tags = raw_spans("tick.upload")[-1]
    want = 8
    while want < 2 * k:
        want *= 2
    assert tags == {"seed": 0, "rows": k, "replies": 2 * k, "bucket": want}


def test_fold_tags_reach_devplane_status(tmp_path):
    """Armed and at full fidelity, as the traced benchmark run is: the
    tags are in `devplane.status()["spans"]`, where the readers look."""
    body = """\
import numpy as np
from redpanda_tpu.observability import devplane
from redpanda_tpu.raft.shard_state import ShardGroupArrays

a = ShardGroupArrays(capacity=16)
rows = np.array([a.alloc_row() for _ in range(9)], np.int64)
a.is_leader[rows] = True
a.is_voter[rows, :3] = True
a.voter_epoch += 1
empty = np.empty(0, np.int64)
devplane.reset()
a.device_tick(empty, empty, empty, empty, empty, force_rows=rows)
tags = [s[7] for s in devplane.status()["spans"] if s[0] == "tick.upload"]
assert tags == [{"seed": 1, "rows": 9, "replies": 0, "bucket": 16}], tags
print("TAGS-OK")
"""
    out = _run_armed(
        tmp_path, body, {"RP_DEVPLANE_SAMPLE": "1", "RP_QUORUM_BACKEND": "device"}
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "TAGS-OK" in out.stdout


def _produce_frame(corr: int, wire: bytes) -> bytes:
    v = 7
    body = produce_fast.encode_request_single(
        v, PRODUCE.flexible(v), None, -1, 10000, "t", 0, wire
    )
    return encode_request_header(RequestHeader(PRODUCE.key, v, corr, "c")) + body


async def _with_broker(tmp_path, body) -> None:
    broker = Broker(
        BrokerConfig(node_id=0, data_dir=str(tmp_path / "n0"), members=[0],
                     enable_admin=False),
        loopback=LoopbackNetwork(),
    )
    await broker.start()
    try:
        await broker.wait_controller_leader()
        client = KafkaClient([broker.kafka_advertised])
        try:
            await client.create_topic("t", partitions=1, replication_factor=1)
            await client.produce("t", 0, [(b"k", b"v")], acks=-1)
        finally:
            await client.close()
        trace.WINDOW.reset()
        await body(broker.kafka_server)
    finally:
        await broker.stop()


def _wire() -> bytes:
    return encode_batch([(b"k", b"v")])


def test_open_counts_up_and_down_across_overlapping_requests(tmp_path, raw_spans):
    """Two produce requests open at once, then a third alone: the tag
    is what the counter read at each arrival, and the counter is back
    at nought when every response has been written."""

    async def body(server) -> None:
        ctx = ConnectionContext()
        wire = _wire()
        first = await server._process(_produce_frame(1, wire), ctx)
        assert (server._produce_open, ctx.produce_open) == (1, 1)
        second = await server._process(_produce_frame(2, wire), ctx)
        assert (server._produce_open, ctx.produce_open) == (2, 2)
        for resp in (first, second):
            assert await resp.resp is not None
            resp.on_written()
        assert (server._produce_open, ctx.produce_open) == (0, 0)
        third = await server._process(_produce_frame(3, wire), ctx)
        await third.resp
        third.on_written()
        third.on_written()  # a span ends once, and is counted out once
        assert (server._produce_open, ctx.produce_open) == (0, 0)

    asyncio.run(asyncio.wait_for(_with_broker(tmp_path, body), LIMIT_S))
    assert [t["open"] for t in raw_spans("kafka.produce")] == [0, 1, 0]


def test_open_is_absent_with_tracing_off(tmp_path, raw_spans, monkeypatch):
    """RP_TRACE=0: no root span, no tag, and the counter never moves."""

    async def body(server) -> None:
        monkeypatch.setattr(trace, "ENABLED", False)
        ctx = ConnectionContext()
        resp = await server._process(_produce_frame(1, _wire()), ctx)
        assert (server._produce_open, ctx.produce_open) == (0, 0)
        await resp.resp
        resp.on_written()
        assert (server._produce_open, ctx.produce_open) == (0, 0)

    asyncio.run(asyncio.wait_for(_with_broker(tmp_path, body), LIMIT_S))
    assert raw_spans("kafka.produce") == []
