"""Warm-up of what transactions add to a deployment's device programs
(see warmers/tick.py for how a warmer is named and called)."""

from __future__ import annotations

import os

import numpy as np

from benchmark.opsbytes import ROW_FLOOR, crc_shape
from benchmark.reference import BODY_AT


def coordinator_and_markers(brokers: list, config: dict, traffic: dict, tpl: list) -> None:
    """The tick program at the reply buckets of a cluster that also
    holds the coordinator's partitions (`tick.reply_buckets` counts the
    topics' followers alone), and `crc32c.device` at every shape a
    `read_committed` fetch of this traffic stages that
    `crc.fetch_verify` does not: a data batch's transaction ends in a
    control batch of under 100 bytes, so one partition of a fetch can
    hold a marker before, between and after as many data batches as
    `fetch_max_bytes` holds (more rows at the data batches' stride), and
    a fetch that begins behind a data batch holds markers alone (the
    smallest stride, at most one a partition)."""
    arrays = brokers[0].group_manager.arrays
    if arrays._backend() == "device":
        arrays.prewarm(max_replies=sum(
            t["partitions"] * (t["replication_factor"] - 1)
            for t in (*config["topics"], config["coordinator_topic"])))
    if os.environ.get("RP_FETCH_VERIFY") != "1":
        return
    from redpanda_tpu.cluster.tx_state import control_record_key
    from redpanda_tpu.models.record import RecordBatchBuilder
    from redpanda_tpu.ops.crc32c import crc32c_batch_device

    # a marker as Partition.write_tx_marker makes it
    marker = RecordBatchBuilder(
        producer_id=0, producer_epoch=0, transactional=True, control=True)
    marker.add(value=b"", key=control_record_key(True))
    marker_body = len(marker.build().to_kafka_wire()) - BODY_AT
    data_bytes = max(len(t.wire) for t in tpl)
    consumers = int(traffic["consumers"])
    partitions = sum(-(-t["partitions"] // consumers) for t in config["topics"])
    a_partition = 2 * max(1, int(traffic["fetch_max_bytes"]) // data_bytes) + 1
    for body, most in ((marker_body, partitions),
                       (data_bytes - BODY_AT, partitions * a_partition)):
        top, _stride = crc_shape(body, most)
        rows = ROW_FLOOR
        while rows <= top:
            crc32c_batch_device(
                np.zeros((rows, body), np.uint8), np.full(rows, body, np.int64))
            rows *= 2
