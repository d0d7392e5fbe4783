"""Bytes a kernel has to move for one dispatch, from its shapes. Kept
with the benchmark so that no later PR can change what a roofline share
is measured against. Both kernels here are bound by bytes, not by
operations: the tick is compares and selects over int64 lanes, the CRC
is one pass over the rows."""

from __future__ import annotations

from benchmark.reference import BODY_AT

#: ops/shapes.row_bucket's floor and ops/crc32c._CHUNK, as dispatched
ROW_FLOOR = 8
CRC_CHUNK = 512


def pow2_at_least(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def tick_bytes(capacity: int, slots: int, replies: int = ROW_FLOOR) -> int:
    """Least bytes one `quorum.heartbeat_tick` fold reads and writes at
    `capacity` groups x `slots` replica slots with a reply window of
    `replies` entries.

    Reads: every lane of GroupState (term, commit_index, term_start,
    last_visible as int64 and is_leader as bool per group; match_index,
    flushed_index, last_seq as int64 and is_voter, is_voter_old as bool
    per group and slot) and the window's five int64 columns. Writes:
    the five lanes the fold changes (commit_index, last_visible per
    group; match_index, flushed_index, last_seq per group and slot)."""
    per_group_read = 4 * 8 + 1
    per_slot_read = 3 * 8 + 2
    read = capacity * per_group_read + capacity * slots * per_slot_read
    read += 5 * 8 * replies
    write = capacity * 2 * 8 + capacity * slots * 3 * 8
    return read + write


def crc_shape(body_bytes: int, batches: int) -> tuple[int, int]:
    """(rows, stride) of the `crc32c.device` dispatch that verifies
    `batches` batches whose crc-covered part is `body_bytes` long."""
    return pow2_at_least(batches, ROW_FLOOR), pow2_at_least(body_bytes, CRC_CHUNK)


def fetch_crc_shape(config: dict, traffic: dict, batch_bytes: int) -> tuple[int, int]:
    """(rows, stride) of the largest `crc32c.device` dispatch a fetch of
    this traffic stages where a stored batch is `batch_bytes` long on
    the wire. The broker verifies all of a fetch's batches in one
    dispatch, and a consumer's fetch can name every partition it tails:
    its share of each topic's partitions, with as many whole batches of
    each as `fetch_max_bytes` holds (one at the least), each taken from
    its first crc-covered byte on."""
    a_partition = max(1, int(traffic["fetch_max_bytes"]) // batch_bytes)
    consumers = int(traffic["consumers"])
    partitions = sum(-(-t["partitions"] // consumers) for t in config["topics"])
    return crc_shape(batch_bytes - BODY_AT, partitions * a_partition)


def crc_bytes(rows: int, stride: int) -> int:
    """Least bytes one `crc32c.device` dispatch moves at [rows, stride]:
    every byte of the padded matrix read once, the row lengths read
    (int64) and one uint32 written per row."""
    return rows * stride + rows * 8 + rows * 4
