"""The brokers a cell runs, in the harness's own process: made, warmed,
started, given their topics and one acknowledged batch a partition, read
and stopped. Copied from chip_smoke.py's `cluster` leg (proved on the
chip, PR 21) and made to follow a configuration file instead of a table
of sizes."""

from __future__ import annotations

import asyncio
import copy
import os
import time

import numpy as np

from benchmark import log


def sized(config: dict, dry_run: bool, control: str | None) -> dict:
    """The configuration as this run boots it: the file's own sizes, or
    its `toy` ones under --cpu-dry-run; the `rf1` control creates every
    topic with one replica where the configuration promises more."""
    config = copy.deepcopy(config)
    if dry_run:
        for t in config["topics"]:
            t["partitions"] = min(t["partitions"], config["toy"]["partitions"])
        config["lane_capacity"] = config["toy"]["lane_capacity"]
    for t in config["topics"]:
        t["create_replication_factor"] = (
            1 if control == "rf1" else t["replication_factor"]
        )
    return config


def toy_traffic(traffic: dict, dry_run: bool) -> dict:
    """Under --cpu-dry-run the traffic's `toy` values replace its own."""
    return {**traffic, **traffic.get("toy", {})} if dry_run else traffic


def make_brokers(config: dict, data_dir: str) -> list:
    from redpanda_tpu.app import Broker, BrokerConfig
    from redpanda_tpu.rpc.loopback import LoopbackNetwork

    net = LoopbackNetwork()
    members = list(range(config["brokers"]))
    return [
        Broker(
            BrokerConfig(
                node_id=i,
                data_dir=os.path.join(data_dir, f"n{i}"),
                members=members,
                enable_admin=False,
                **config.get("broker", {}),
            ),
            loopback=net,
        )
        for i in members
    ]


def reserve(brokers: list, config: dict) -> None:
    """Size every broker's lane space once, before anything ticks."""
    for b in brokers:
        b.group_manager.arrays.reserve(int(config["lane_capacity"]))


def lanes(brokers: list) -> dict:
    """The lane shape the tick folds over, as the program holds it."""
    arrays = brokers[0].group_manager.arrays
    return {"capacity": int(arrays.capacity), "slots": int(arrays.replica_slots)}


FLUSH_LAG_S = 0.5


def lag_flushes() -> None:
    """The `flush_lagged` control: a log tells raft at once that all it
    appended is flushed, and syncs its file `FLUSH_LAG_S` later: the
    later flush that would tempt a later PR. Acknowledgements then run
    ahead of what storage has made durable."""
    from redpanda_tpu.storage.log import Log

    flush = Log.flush

    def later(log) -> None:
        try:
            flush(log)
        except Exception:
            pass  # the log was closed meanwhile

    def lagged(self) -> int:
        asyncio.get_event_loop().call_later(FLUSH_LAG_S, later, self)
        return self.offsets().dirty_offset

    async def lagged_async(self) -> int:
        return lagged(self)

    Log.flush, Log.flush_async = lagged, lagged_async


async def retry(fn, deadline: float, what: str):
    while True:
        try:
            return await fn()
        except Exception as e:
            if time.monotonic() > deadline:
                raise RuntimeError(f"{what}: still failing: {e!r}") from e
            await asyncio.sleep(0.25)


async def start(brokers: list, config: dict) -> list:
    """Start the brokers and create the topics; returns the bootstrap
    addresses."""
    from redpanda_tpu.kafka.client import KafkaClient

    for b in brokers:
        await b.start()
    addrs = {b.node_id: b.kafka_advertised for b in brokers}
    for b in brokers:
        b.config.peer_kafka_addresses = addrs
    await brokers[0].wait_controller_leader()
    bootstrap = [list(b.kafka_advertised) for b in brokers]
    admin = KafkaClient([tuple(a) for a in bootstrap])
    try:
        deadline = time.monotonic() + 120
        for t in config["topics"]:
            await retry(
                lambda t=t: admin.create_topic(
                    t["name"], partitions=t["partitions"],
                    replication_factor=t["create_replication_factor"],
                    configs=t.get("configs") or None, timeout_ms=60000,
                ),
                deadline, f"create_topic {t['name']}",
            )
    finally:
        await admin.close()
    return bootstrap


async def first_ack_everywhere(bootstrap: list, config: dict, tpl: list) -> None:
    """One acknowledged batch on every partition: leaders are elected
    and every log is open before the window starts."""
    from redpanda_tpu.kafka.client import KafkaClient

    n = 8
    clients = [KafkaClient([tuple(a) for a in bootstrap]) for _ in range(n)]
    work = [(t["name"], p) for t in config["topics"] for p in range(t["partitions"])]

    async def first(i: int) -> None:
        for topic, p in work[i::n]:
            await retry(
                lambda: clients[i].produce_wire(
                    topic, p, tpl[p % len(tpl)].wire, acks=config["acks"]
                ),
                time.monotonic() + 180, f"first produce {topic}/{p}",
            )

    try:
        await asyncio.gather(*(first(i) for i in range(n)))
    finally:
        for c in clients:
            await c.close()


def elections(brokers: list) -> int:
    """Leadership terms won so far across the cluster (a group's first
    leader is term 1; every later election adds one)."""
    total = 0
    for b in brokers:
        arrays = b.group_manager.arrays
        live = arrays.row_active & arrays.is_leader
        total += int(np.sum(arrays.term[live]))
    return total


def replicas(brokers: list, topic: str, partition: int) -> list:
    """The partition's replica on every broker that holds one."""
    from redpanda_tpu.models.fundamental import kafka_ntp

    ntp = kafka_ntp(topic, partition)
    found = [b.partition_manager.get(ntp) for b in brokers]
    return [x for x in found if x is not None]


async def stop(brokers: list) -> None:
    for b in brokers:
        try:
            await b.stop()
        except Exception as e:
            log(f"broker {b.node_id} stop: {e!r}")
