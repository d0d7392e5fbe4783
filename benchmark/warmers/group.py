"""Warm-up of what a consumer group adds to a deployment (see
warmers/tick.py for how a warmer is named and called)."""

from __future__ import annotations

#: what an exactly-once member asks of the group coordinator (KIP-447):
#: the group's metadata in TxnOffsetCommit, and OffsetFetch that
#: refuses offsets a transaction has not settled yet
NEEDS = {"txn_offset_commit": 3, "offset_fetch": 7}


def coordinator_and_offsets(brokers: list, config: dict, traffic: dict, tpl: list) -> None:
    """The tick program at the reply buckets of a cluster that also
    holds the group coordinator's `__consumer_offsets` (and the
    transaction coordinator's partitions, which `tx.coordinator_and_markers`
    counts as well): a leader gets one reply a follower a partition a
    heartbeat. The group's own traffic, a staged offset and a group
    marker, adds no device program of its own; the generator's set-up
    makes and leads `__consumer_offsets` and runs both through it, as
    the transaction generator's InitProducerId makes the coordinator's
    topic, before the window opens.

    A program whose group coordinator does not serve what the members
    ask is refused here, before the brokers start, and not a minute
    later in the generator's set-up."""
    from redpanda_tpu.kafka.protocol.group_apis import OFFSET_FETCH
    from redpanda_tpu.kafka.protocol.tx_apis import TXN_OFFSET_COMMIT

    for api in (TXN_OFFSET_COMMIT, OFFSET_FETCH):
        if api.max_version < NEEDS[api.name]:
            raise SystemExit(
                f"benchmark: the brokers serve {api.name} up to v{api.max_version}; "
                f"this cell's members need v{NEEDS[api.name]} (KIP-447)")
    arrays = brokers[0].group_manager.arrays
    if arrays._backend() == "device":
        arrays.prewarm(max_replies=sum(
            t["partitions"] * (t["replication_factor"] - 1)
            for t in (*config["topics"], config["coordinator_topic"],
                      config["group_coordinator_topic"])))
