"""What a `read_committed` consumer may see of a partition's log, written
out from KIP-98's description and importing nothing of the program.

A transactional producer's data batches carry its producer id, epoch
and base sequence and the `isTransactional` attribute bit. When its
transaction ends, the coordinator has a control batch written to every
partition the transaction touched: `isControl` set, the producer's id
and epoch, one record whose key is (version i16, type i16), type 0 an
ABORT marker and 1 a COMMIT marker. A partition's last stable offset is
the first offset of its earliest transaction still open.

Two readings of that, independent of each other:

`Filter` is the consumer's side, as the Kafka consumer applies it to
each partition of a `read_committed` fetch response: the response names
the aborted transactions that overlap it, (producerId, firstOffset); the
consumer walks the batches in order, counts a producer as aborted from
its entry's first offset until that producer's ABORT marker, drops such
a producer's transactional batches, and hands on no control batch.

`replay` is the log's side: given everything a partition holds, read
`read_uncommitted` (data and control batches alike), the visible
sequence is the data batches of transactions that a COMMIT marker
closed, in offset order; and for every producer the sequences of its
data batches, committed or aborted, are continuous: nothing stored
twice, nothing missing (a producer's first batch on a partition has
sequence 0: the log is read from before its first). It also says
which marker closed each data batch, so that a reader can be held to
isolation: a `read_committed` response may hold a transaction's data
only once its marker is below the response's high watermark. After a
run the generator holds what its consumers were handed to that replay
(generators/transactional.py).
"""

from __future__ import annotations

import struct
from typing import NamedTuple

TRANSACTIONAL_BIT = 0x10
CONTROL_BIT = 0x20
ABORT, COMMIT = 0, 1
_FIELDS = struct.Struct(">hiqqqhii")   # attributes ... recordCount, from offset 21
_FIELDS_AT = 21
_RECORDS_AT = 61


class Head(NamedTuple):
    base: int
    last: int            # the batch's last offset
    producer_id: int
    epoch: int
    base_sequence: int
    records: int
    transactional: bool
    control: bool


def head_of(batch: bytes) -> Head:
    """The header fields of a whole v2 wire batch that the rules here
    read."""
    (base,) = struct.unpack_from(">q", batch, 0)
    attributes, last_delta, _t0, _t1, pid, epoch, seq, count = _FIELDS.unpack_from(
        batch, _FIELDS_AT)
    return Head(base, base + last_delta, pid, epoch, seq, count,
                bool(attributes & TRANSACTIONAL_BIT), bool(attributes & CONTROL_BIT))


def _varint(data: bytes, pos: int) -> tuple[int, int]:
    """(zig-zag decoded value, position after it)."""
    shift = z = 0
    while True:
        b = data[pos]
        pos += 1
        z |= (b & 0x7F) << shift
        if not b & 0x80:
            return (z >> 1) ^ -(z & 1), pos
        shift += 7


def marker_of(batch: bytes) -> int | None:
    """ABORT or COMMIT for a control batch's marker; None where its
    first record's key is no (version 0, type) pair."""
    pos = _RECORDS_AT
    try:
        _length, pos = _varint(batch, pos)
        pos += 1                                  # record attributes
        _ts, pos = _varint(batch, pos)
        _off, pos = _varint(batch, pos)
        key_len, pos = _varint(batch, pos)
        if key_len < 4:
            return None
        version, kind = struct.unpack_from(">hh", batch, pos)
    except (IndexError, struct.error):
        return None
    return kind if version == 0 and kind in (ABORT, COMMIT) else None


class Filter:
    """One partition of one `read_committed` fetch response, as the
    consumer reads it: `aborted` is the response's
    `aborted_transactions` for the partition, [(producerId,
    firstOffset)]. `take(batch)` is called for each batch in offset
    order and says what becomes of it: "deliver", "aborted" (dropped on
    the broker's word) or "control" (never handed on)."""

    def __init__(self, aborted: list[tuple[int, int]]) -> None:
        self._pending = sorted(aborted, key=lambda a: a[1])
        self._aborted: set[int] = set()

    def take(self, batch: bytes) -> str:
        head = head_of(batch)
        while self._pending and self._pending[0][1] <= head.last:
            self._aborted.add(self._pending.pop(0)[0])
        if head.control:
            if marker_of(batch) == ABORT:
                self._aborted.discard(head.producer_id)
            return "control"
        if head.transactional and head.producer_id in self._aborted:
            return "aborted"
        return "deliver"


class Replayed(NamedTuple):
    visible: list[int]             # base offsets a read_committed reader sees, in order
    aborted: list[int]             # base offsets of data an ABORT marker closed
    open: list[int]                # base offsets of data no marker closed
    closed_by: dict[int, int]      # data base offset -> its marker's offset
    sequence_breaks: list[tuple[int, int, int, int]]  # (producerId, base, expected, found)


def replay(batches: list[bytes]) -> Replayed:
    """A partition's whole log, in offset order, replayed."""
    visible: list[int] = []                    # committed data, and plain data
    aborted: list[int] = []
    pending: dict[int, list[int]] = {}         # producerId -> bases of its open transaction
    next_sequence: dict[tuple[int, int], int] = {}
    breaks: list[tuple[int, int, int, int]] = []
    closed_by: dict[int, int] = {}
    for batch in batches:
        head = head_of(batch)
        if head.control:
            kind = marker_of(batch)
            bases = pending.pop(head.producer_id, [])
            closed_by.update((b, head.base) for b in bases)
            if kind == COMMIT:
                visible.extend(bases)
            elif kind == ABORT:
                aborted.extend(bases)
            continue
        if head.producer_id >= 0 and head.base_sequence >= 0:
            key = (head.producer_id, head.epoch)
            expected = next_sequence.get(key, 0)
            if head.base_sequence != expected:
                breaks.append((head.producer_id, head.base, expected, head.base_sequence))
            next_sequence[key] = head.base_sequence + head.records
        if head.transactional:
            pending.setdefault(head.producer_id, []).append(head.base)
        else:
            visible.append(head.base)
    still_open = sorted(b for bases in pending.values() for b in bases)
    return Replayed(sorted(visible), sorted(aborted), still_open, closed_by, breaks)
