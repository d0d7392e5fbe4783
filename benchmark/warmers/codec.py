"""Warm-up of the device programs of a topic whose broker recompresses
(see warmers/tick.py for how a warmer is named and called)."""

from __future__ import annotations

import os

from benchmark.opsbytes import crc_shape
from benchmark.reference import BODY_AT
from benchmark.templates.compressible import topic_codec
from benchmark.warmers.crc import verify_stored


def recompressed(brokers: list, config: dict, traffic: dict, tpl: list) -> None:
    """Every template through the program's own recompression, as a
    produce to the topic takes it: that compiles the fused CRC and codec
    program at the shape of this traffic's batches. What it stores is
    shorter than what was sent, so a fetch verifies it at a smaller
    stride, and returns more batches a partition, than
    `crc.fetch_verify` warms: `crc32c.device` at the stride of the
    batches as stored (the shortest of each stride, of which a fetch
    holds most)."""
    codec = topic_codec(config)
    if codec is None or os.environ.get("RP_CODEC_BACKEND") != "device":
        return
    from redpanda_tpu.models.record import CompressionType, RecordBatch

    shortest: dict[int, int] = {}
    for t in tpl:
        batch = RecordBatch.from_kafka_wire(t.wire, verify=False)
        wire = batch.recompressed(
            CompressionType[codec], verify_crc=batch.header.crc
        ).to_kafka_wire()
        _rows, stride = crc_shape(len(wire) - BODY_AT, 1)
        shortest[stride] = min(len(wire), shortest.get(stride, len(wire)))
    if os.environ.get("RP_FETCH_VERIFY") == "1":
        for batch_bytes in shortest.values():
            verify_stored(config, traffic, batch_bytes)
