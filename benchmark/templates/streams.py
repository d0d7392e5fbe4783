"""Templates for a re-keying pipeline (see templates/plain.py for how a
maker is named and called): the source batches of a Kafka Streams
application's input topic, whose records carry in their value the key
field the application re-keys by and an event id, as events of such a
topic do.

A record's value is `record_bytes - 16` bytes: its first 16 the key
field (`KEY_FIELD`: drawn from the seed, one for each record of the
template pool, so `count` x `batch_records` distinct keys), the next 16
the event id (`EVENT_ID`: partition i32, producer id i64, sequence i32,
which the source producer writes as it sends the batch: zero in the
template), the rest random. The record's own key is the template's,
`k<template>.<record>`, as `make_templates` makes it.

A source producer stamps a template (`stamp_event`): its producer id,
epoch and base sequence into the header, as the idempotent producer of
templates/ctp.py does, and every record's event id into its value. The
CRC is mended by CRC-32C's linearity (templates/transactional.py): the
stamp changes the 36 header bytes and 16 bytes a record, and each
changed run costs its own table steps and one matrix product, so a
stamp does not pass over the 40 KB. `came_back` holds a stored source
batch to the same: the tolerance is none. Nothing of the program is
imported.
"""

from __future__ import annotations

import struct

import numpy as np

from benchmark.reference import (
    AFTER_ATTRIBUTES, ATTRIBUTES_AT, BODY_AT, CRC_AT, RECORDS_AT, encode_batch,
)
from benchmark.templates.ctp import CopiedTemplate
from benchmark.templates.transactional import (
    _PRODUCER, AFTER_PRODUCER, PRODUCER_AT, _apply, _raw, zeros_advance,
)
from benchmark.txreplay import _varint

KEY_FIELD = slice(0, 16)
EVENT_ID = slice(16, 32)
_EVENT = struct.Struct(">iqi")   # partition, producer id, sequence
_ZERO_ID = bytes(_EVENT.size)


def value_offsets(wire: bytes) -> list[int]:
    """Where each record's value begins in a whole uncompressed v2
    batch."""
    (count,) = struct.unpack_from(">i", wire, AFTER_PRODUCER)
    out, pos = [], RECORDS_AT
    for _ in range(count):
        length, pos = _varint(wire, pos)
        end = pos + length
        pos += 1                                   # attributes
        _ts, pos = _varint(wire, pos)
        _off, pos = _varint(wire, pos)
        key_len, pos = _varint(wire, pos)
        pos += max(0, key_len)
        _value_len, pos = _varint(wire, pos)
        out.append(pos)
        pos = end
    return out


class KeyedTemplate(CopiedTemplate):
    """A source batch whose records carry a key field and an event id
    in their values; see the head of this file. It is known by its
    records section with the event ids zeroed."""

    def __init__(self, wire: bytes, records: list[tuple[bytes, bytes]]):
        super().__init__(wire, records)
        self.ids_at = [at + EVENT_ID.start for at in value_offsets(wire)]
        if any(wire[at:at + _EVENT.size] != _ZERO_ID for at in self.ids_at):
            raise ValueError("a keyed template is made with its event ids zero")
        self._id_advance = [zeros_advance(len(wire) - at - _EVENT.size) for at in self.ids_at]

    def key_of(self, batch: bytes):
        if len(batch) != len(self.wire):
            return None
        section = bytearray(batch[RECORDS_AT:])
        for at in self.ids_at:
            section[at - RECORDS_AT:at - RECORDS_AT + _EVENT.size] = _ZERO_ID
        return bytes(section)

    def _crc_with(self, head: bytes, ids: list[bytes]) -> int:
        """The CRC of this template's batch with the 36 header bytes a
        stamp can change replaced by `head` and the event ids by `ids`."""
        crc = self._crc_of(head)
        for event, advance in zip(ids, self._id_advance):
            crc ^= _apply(advance, _raw(event))
        return crc

    def stamp_event(self, producer_id: int, epoch: int, base_sequence: int,
                    partition: int) -> bytes:
        """The batch as the idempotent producer (`producer_id`, `epoch`)
        sends it to `partition` with `base_sequence`: record i's event
        id is (partition, producer id, base_sequence + i)."""
        out = bytearray(self.wire)
        _PRODUCER.pack_into(out, PRODUCER_AT, producer_id, epoch, base_sequence)
        ids = [_EVENT.pack(partition, producer_id, base_sequence + i)
               for i in range(len(self.ids_at))]
        for at, event in zip(self.ids_at, ids):
            out[at:at + _EVENT.size] = event
        struct.pack_into(">I", out, CRC_AT, self._crc_with(out[BODY_AT:AFTER_PRODUCER], ids))
        return bytes(out)

    def came_back(self, batch: bytes) -> bool:
        """A stored source batch of this template: the attributes as
        sent, a producer's id, epoch and base sequence, every other byte
        from lastOffsetDelta on as sent but the event ids, record i's
        naming that producer and base sequence + i, and the batch's own
        CRC holding."""
        if len(batch) != len(self.wire):
            return False
        length, _epoch, magic = struct.unpack_from(">iib", batch, 8)
        if magic != 2 or length != len(batch) - 12:
            return False
        (attributes,) = struct.unpack_from(">h", batch, ATTRIBUTES_AT)
        producer_id, epoch, base_sequence = _PRODUCER.unpack_from(batch, PRODUCER_AT)
        if (attributes != self.attributes or producer_id < 0 or epoch < 0
                or base_sequence < 0
                or batch[AFTER_ATTRIBUTES:PRODUCER_AT] != self.wire[AFTER_ATTRIBUTES:PRODUCER_AT]
                or self.key_of(batch) != self.key):
            return False
        ids = [batch[at:at + _EVENT.size] for at in self.ids_at]
        for i, event in enumerate(ids):
            _partition, pid, sequence = _EVENT.unpack(event)
            if pid != producer_id or sequence != base_sequence + i:
                return False
        return struct.unpack_from(">I", batch, CRC_AT)[0] == self._crc_with(
            batch[BODY_AT:AFTER_PRODUCER], ids)


def keyed(seed: int, traffic: dict, config: dict) -> list[KeyedTemplate]:
    """`count` batches of the traffic's `batch_records` records of the
    configuration's `record_bytes` bytes: a 16-byte record key and a
    value of a key field, a zero event id and random bytes, from the
    seed; no two key fields of the pool alike."""
    rng = np.random.default_rng(seed)
    n, per = int(traffic["templates"]["count"]), int(traffic["batch_records"])
    vlen = int(config["record_bytes"]) - 16
    fields: set[bytes] = set()
    out = []
    for t in range(n):
        raw = rng.integers(0, 256, (per, vlen), dtype=np.uint8)
        raw[:, EVENT_ID] = 0
        for i in range(per):
            while raw[i, KEY_FIELD].tobytes() in fields:
                raw[i, KEY_FIELD] = rng.integers(0, 256, 16, dtype=np.uint8)
            fields.add(raw[i, KEY_FIELD].tobytes())
        recs = [(b"k%03d.%011d" % (t, i), raw[i].tobytes()) for i in range(per)]
        out.append(KeyedTemplate(encode_batch(recs), recs))
    return out
