"""Warm-up of the raft tick's device programs. A configuration names
its warmers (`"warm": ["tick.reply_buckets", ...]`); each is called once
in set-up, before the brokers start and while nothing is ticking (a cold
compile runs on the event loop the brokers share, and seconds without
heartbeats are an election storm), with the brokers, the configuration,
the traffic and the templates."""

from __future__ import annotations


def reply_buckets(brokers: list, config: dict, traffic: dict, tpl: list) -> None:
    """The tick program at every reply bucket a window can fall in: a
    leader gets one reply a follower a partition a heartbeat."""
    arrays = brokers[0].group_manager.arrays
    if arrays._backend() != "device":
        return
    followers = sum(
        t["partitions"] * (t["replication_factor"] - 1) for t in config["topics"]
    )
    arrays.prewarm(max_replies=followers)
