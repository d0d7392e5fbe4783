"""Warm-up of the verify-on-read CRC's device programs (see
warmers/tick.py for how a warmer is named and called)."""

from __future__ import annotations

import os

import numpy as np

from benchmark.opsbytes import ROW_FLOOR, fetch_crc_shape
from benchmark.reference import BODY_AT


def fetch_verify(brokers: list, config: dict, traffic: dict, tpl: list) -> None:
    """`crc32c.device` at the stride of this traffic's batches as they
    are sent, which is as a pass-through topic stores them."""
    if os.environ.get("RP_FETCH_VERIFY") == "1":
        verify_stored(config, traffic, max(len(t.wire) for t in tpl))


def verify_stored(config: dict, traffic: dict, batch_bytes: int) -> None:
    """`crc32c.device` at the stride of a stored batch of `batch_bytes`
    on the wire and every row bucket up to the most such batches one
    fetch of this traffic can return (a fetch after a stall names many
    partitions)."""
    from redpanda_tpu.ops.crc32c import crc32c_batch_device

    most, _stride = fetch_crc_shape(config, traffic, batch_bytes)
    body = batch_bytes - BODY_AT
    rows = ROW_FLOOR
    while True:
        crc32c_batch_device(
            np.zeros((rows, body), np.uint8), np.full(rows, body, np.int64)
        )
        if rows >= most:
            break
        rows *= 2
