"""Warm-up of what a re-keying pipeline adds to a deployment (see
warmers/tick.py for how a warmer is named and called)."""

from __future__ import annotations

import os

import numpy as np

from benchmark.opsbytes import CRC_CHUNK, ROW_FLOOR, pow2_at_least
from benchmark.reference import BODY_AT, RECORDS_AT

#: bytes a re-keyed record adds to its batch beside its key and value:
#: length, attributes, timestamp and offset deltas, key and value
#: lengths and the header count, each a varint of at most 2 bytes
RECORD_OVERHEAD = 10
#: the most records of one transaction a sink partition's batch is
#: warmed for: a source batch of 39 spreads over about 32 of 100
#: partitions, so a batch holds one or two records, more where a poll
#: returned several source batches
RECORDS_A_BATCH = 15


def sink_batches(brokers: list, config: dict, traffic: dict, tpl: list) -> None:
    """`crc32c.device` at every shape a sink consumer's `read_committed`
    fetch stages while it keeps up: at every stride of a fetch of
    control batches alone, or of control batches beside data batches of
    1 to `RECORDS_A_BATCH` re-keyed records, and at every row bucket up
    to a data batch and its marker for each partition the consumer
    tails. A fetch that holds more (a consumer that fell behind, the
    generator's read of each sink partition after the drain) compiles
    its shape when it first comes.

    A program whose client cannot write one transaction's batches to
    many partitions, one request a leader, is refused here, before the
    brokers start, and not a minute later in the generator's set-up."""
    from redpanda_tpu.kafka.client import TransactionalProducer

    if not hasattr(TransactionalProducer, "produce_batches"):
        raise SystemExit(
            "benchmark: the program's TransactionalProducer has no "
            "produce_batches: this cell's members write a transaction's "
            "re-keyed records to many partitions in one request a leader")
    if os.environ.get("RP_FETCH_VERIFY") != "1":
        return
    from redpanda_tpu.ops.crc32c import crc32c_batch_device

    record = int(config["record_bytes"]) + RECORD_OVERHEAD
    tails = -(-int(config["topics"][1]["partitions"]) // int(traffic["consumers"]))
    most = pow2_at_least(2 * tails, ROW_FLOOR)
    strides = {CRC_CHUNK} | {pow2_at_least(RECORDS_AT - BODY_AT + n * record, CRC_CHUNK)
                             for n in range(1, RECORDS_A_BATCH + 1)}
    for stride in sorted(strides):
        rows = ROW_FLOOR
        while rows <= most:
            crc32c_batch_device(
                np.zeros((rows, stride), np.uint8), np.full(rows, stride, np.int64))
            rows *= 2
