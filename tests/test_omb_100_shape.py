"""The mechanism the `omb_100` cell works (ISSUE 27), against the plain
reference, at toy size on the CPU: three brokers with the device
switches on take several batches on distinct replicated partitions at
once, so that folds overlap and one fold can carry more than one
group's rows. What was acknowledged is on every replica byte for byte
(benchmark/reference.py), every group's commit index is the scalar
rule's (raft/quorum_scalar.py) for the lanes it was computed from, and
at least one fold carried more than one row."""

import asyncio
import time

import pytest

from benchmark import run
from benchmark.reference import CRC_AT, make_templates
from redpanda_tpu.app import Broker, BrokerConfig
from redpanda_tpu.kafka.client import KafkaClient
from redpanda_tpu.models.fundamental import kafka_ntp
from redpanda_tpu.observability import trace
from redpanda_tpu.raft.quorum_scalar import ReplicaState, leader_commit_index
from redpanda_tpu.raft.shard_state import SELF_SLOT
from redpanda_tpu.rpc.loopback import LoopbackNetwork

PARTITIONS, CAPACITY, BATCHES = 12, 64, 16
LIMIT_S = 120
# the device switches the cell is served with, and its toy sizes
CONFIG = run.load_json(run.HERE, "configs", "omb_100.json")
assert CONFIG["toy"] == {"partitions": PARTITIONS, "lane_capacity": CAPACITY}


def _scalar_commit(arrays, row: int) -> int:
    """The commit index the scalar rule gives for the row's lanes."""
    replicas = [
        ReplicaState(
            match_index=int(arrays.match_index[row, s]),
            flushed_index=int(arrays.flushed_index[row, s]),
            is_voter=bool(arrays.is_voter[row, s]),
            is_voter_old=bool(arrays.is_voter_old[row, s]),
        )
        for s in range(arrays.replica_slots)
    ]
    return leader_commit_index(
        replicas, int(arrays.flushed_index[row, SELF_SLOT]), -1,
        int(arrays.term_start[row]),
    )


async def _until(cond, what: str, limit_s: float = 30.0) -> None:
    deadline = time.monotonic() + limit_s
    while not cond():
        assert time.monotonic() < deadline, what
        await asyncio.sleep(0.05)


async def _drive(tmp_path) -> dict:
    net = LoopbackNetwork()
    members = [0, 1, 2]
    brokers = [
        Broker(
            BrokerConfig(
                node_id=i, data_dir=str(tmp_path / f"n{i}"), members=members,
                enable_admin=False, **CONFIG["broker"],
            ),
            loopback=net,
        )
        for i in members
    ]
    for b in brokers:
        b.group_manager.arrays.reserve(CAPACITY)
        b.group_manager.arrays.prewarm(max_replies=2 * PARTITIONS)
    templates = make_templates(27, 8, 39, CONFIG["record_bytes"])
    clients: list[KafkaClient] = []
    try:
        for b in brokers:
            await b.start()
        addrs = {b.node_id: b.kafka_advertised for b in brokers}
        for b in brokers:
            b.config.peer_kafka_addresses = addrs
        await brokers[0].wait_controller_leader()
        bootstrap = [b.kafka_advertised for b in brokers]
        clients = [KafkaClient(bootstrap) for _ in range(BATCHES)]
        await clients[0].create_topic(
            "bench", partitions=PARTITIONS, replication_factor=3, timeout_ms=60000
        )
        # warm partitions, as the cell's are: one batch each, first
        for p in range(PARTITIONS):
            await clients[p].produce_wire("bench", p, templates[p % 8].wire, acks=-1)
        trace.WINDOW.reset()
        sent = [(i % PARTITIONS, templates[i % 8]) for i in range(BATCHES)]
        bases = await asyncio.gather(*(
            clients[i].produce_wire("bench", p, t.wire, acks=-1)
            for i, (p, t) in enumerate(sent)
        ))
        folds = [
            s[7] for s in trace.WINDOW.status()["spans"] if s[0] == "tick.upload"
        ]

        def parts(p: int) -> list:
            found = [b.partition_manager.get(kafka_ntp("bench", p)) for b in brokers]
            return [x for x in found if x is not None]

        ends = {}
        for (p, t), base in zip(sent, bases):
            ends[p] = max(ends.get(p, 0), base + t.records)
        await _until(
            lambda: all(
                x.high_watermark() >= end for p, end in ends.items() for x in parts(p)
            ),
            "a follower never caught up",
        )
        stored = {
            (p, base): [
                x.read_kafka(base, 1, upto_kafka=base + t.records)[0][1]
                .to_kafka_wire()[CRC_AT:]
                for x in parts(p)
            ]
            for (p, t), base in zip(sent, bases)
        }
        commits = []
        for p in range(PARTITIONS):
            leader = next(x for x in parts(p) if x.is_leader)
            arrays, row = leader.consensus.arrays, leader.consensus.row
            await _until(
                lambda: int(arrays.commit_index[row]) == _scalar_commit(arrays, row),
                f"partition {p}: commit index is not the scalar rule's",
            )
            commits.append((int(arrays.commit_index[row]),
                            int(arrays.match_index[row, SELF_SLOT])))
        return {"sent": sent, "bases": bases, "stored": stored, "folds": folds,
                "commits": commits, "replicas": [len(parts(p)) for p in range(PARTITIONS)]}
    finally:
        for c in clients:
            await c.close()
        for b in brokers:
            await b.stop()


@pytest.fixture(scope="module")
def drove(tmp_path_factory):
    """One boot for all the cases below: about ten seconds."""
    mp = pytest.MonkeyPatch()
    for k, v in CONFIG["env"].items():
        mp.setenv(k, v)
    mp.setattr(trace, "ENABLED", True)
    mp.setattr(trace.WINDOW, "keep_raw", True)
    try:
        yield asyncio.run(asyncio.wait_for(
            _drive(tmp_path_factory.mktemp("omb_100")), LIMIT_S))
    finally:
        mp.undo()
        trace.WINDOW.reset()


def test_every_batch_is_acknowledged_in_order(drove):
    assert len(drove["bases"]) == BATCHES and min(drove["bases"]) >= 1
    by_partition: dict = {}
    for (p, t), base in zip(drove["sent"], drove["bases"]):
        by_partition.setdefault(p, []).append(base)
    # each partition held one batch before; the new ones follow it, no gaps
    for p, got in by_partition.items():
        assert sorted(got) == [39 * (k + 1) for k in range(len(got))], (p, got)


def test_every_replica_stores_the_reference_bytes(drove):
    assert drove["replicas"] == [3] * PARTITIONS
    for ((p, t), base) in zip(drove["sent"], drove["bases"]):
        copies = drove["stored"][(p, base)]
        assert len(copies) == 3
        assert all(c == t.tail for c in copies), (p, base)


def test_commit_index_is_the_scalar_rule_s(drove):
    """Already held to `quorum_scalar.leader_commit_index` for the same
    match and flushed lanes inside the drive; here: it reached the
    leader's own last offset on every group."""
    assert len(drove["commits"]) == PARTITIONS
    assert all(commit == dirty and commit >= 0 for commit, dirty in drove["commits"])


def test_a_fold_carried_more_than_one_row(drove):
    folds = drove["folds"]
    assert folds and all({"rows", "replies", "bucket", "seed"} <= set(f) for f in folds)
    assert max(f["rows"] for f in folds) > 1, folds
    assert all(f["bucket"] >= max(8, f["rows"], f["replies"]) for f in folds)
    assert all(f["seed"] == 0 for f in folds)
