"""Templates for a transactional producer (see templates/plain.py for
how a maker is named and called): pre-encoded plain batches into which
the generator stamps its producer id, epoch, base sequence and the
transactional bit, as a Kafka client fills them in when it drains a
batch (KIP-98), and what has to come back of such a batch.

A stamp changes bytes of the header alone: the attributes (offset 21,
bit 4 is `isTransactional`), producerId (43), producerEpoch (51) and
baseSequence (53). The CRC covers them, so every stamp needs the CRC
made anew. CRC-32C is linear over GF(2): for two messages of one length
crc(a xor d) = crc(a) xor raw(d), where raw() is the CRC's register run
from zero with no final inversion. `d` is nonzero in the first 36
covered bytes only, so raw(d) is the register after those 36 bytes
advanced over the zeros that follow, and advancing over n zero bytes is
one fixed 32 x 32 bit matrix (`zeros_advance`, made once a length by
squaring). A stamp therefore costs 36 table steps and one matrix
product, not a pass over 40 KB: that is how a generator in Python
keeps sixteen producers under half a core. The reference's plain
`crc32c` stays the definition; the tests hold this path to it.

Nothing of the program is imported.
"""

from __future__ import annotations

import struct

from benchmark.reference import (
    _TABLE, AFTER_ATTRIBUTES, ATTRIBUTES_AT, BODY_AT, CRC_AT, RECORDS_AT,
    Template, make_templates,
)
from benchmark.txreplay import CONTROL_BIT, TRANSACTIONAL_BIT

PRODUCER_AT = 43           # producerId i64 | producerEpoch i16 | baseSequence i32
AFTER_PRODUCER = 57        # recordCount
_PRODUCER = struct.Struct(">qhi")


def _raw(data: bytes, c: int = 0) -> int:
    """The CRC-32C register after `data`, from `c`, not inverted."""
    table = _TABLE
    for b in data:
        c = table[(c ^ b) & 0xFF] ^ (c >> 8)
    return c


def _apply(matrix: list[int], v: int) -> int:
    out = 0
    for column in matrix:
        if v & 1:
            out ^= column
        v >>= 1
    return out


_ADVANCE: dict[int, list[int]] = {}


def zeros_advance(n: int) -> list[int]:
    """The matrix (32 columns, one an input bit) that takes the CRC's
    register over `n` zero bytes."""
    if n not in _ADVANCE:
        power = [_raw(b"\x00", 1 << bit) for bit in range(32)]   # one byte
        result = [1 << bit for bit in range(32)]                 # identity
        k = n
        while k:
            if k & 1:
                result = [_apply(power, column) for column in result]
            power = [_apply(power, column) for column in power]
            k >>= 1
        _ADVANCE[n] = result
    return _ADVANCE[n]


class TransactionalTemplate(Template):
    """One pre-encoded plain batch that a transactional producer stamps
    before it sends it, on a topic that passes batches through. It is
    known by its records section, since every stamp changes the CRC. It
    came back when: the batch's own CRC holds; the attributes carry the
    transactional bit and are otherwise as sent; producer id, epoch and
    base sequence are a producer's (none negative); and every byte from
    lastOffsetDelta on, those three fields apart, is as sent. The
    tolerance is none. Whose id and which sequence is not the
    template's to know: the replay holds that (benchmark/txreplay.py)."""

    def __init__(self, wire: bytes, records: list[tuple[bytes, bytes]]):
        super().__init__(wire, records)
        self.key = wire[RECORDS_AT:]
        (self.attributes,) = struct.unpack_from(">h", wire, ATTRIBUTES_AT)
        if self.attributes & (TRANSACTIONAL_BIT | CONTROL_BIT):
            raise ValueError("a transactional template is made from a plain batch")
        self._head = wire[BODY_AT:AFTER_PRODUCER]
        self._advance = zeros_advance(len(wire) - AFTER_PRODUCER)

    def key_of(self, batch: bytes):
        return batch[RECORDS_AT:]

    def _crc_of(self, head: bytes) -> int:
        """The CRC of this template's batch with its covered bytes up
        to recordCount, the 36 a stamp can change, replaced by `head`."""
        diff = bytes(a ^ b for a, b in zip(head, self._head))
        return self.crc ^ _apply(self._advance, _raw(diff))

    def stamp(self, producer_id: int, epoch: int, base_sequence: int) -> bytes:
        """The batch as producer (`producer_id`, `epoch`) sends it with
        `base_sequence`, inside a transaction."""
        out = bytearray(self.wire)
        struct.pack_into(">h", out, ATTRIBUTES_AT, self.attributes | TRANSACTIONAL_BIT)
        _PRODUCER.pack_into(out, PRODUCER_AT, producer_id, epoch, base_sequence)
        struct.pack_into(">I", out, CRC_AT, self._crc_of(out[BODY_AT:AFTER_PRODUCER]))
        return bytes(out)

    def came_back(self, batch: bytes) -> bool:
        if len(batch) != len(self.wire):
            return False
        length, _epoch, magic = struct.unpack_from(">iib", batch, 8)
        if magic != 2 or length != len(batch) - 12:
            return False
        (attributes,) = struct.unpack_from(">h", batch, ATTRIBUTES_AT)
        producer_id, epoch, base_sequence = _PRODUCER.unpack_from(batch, PRODUCER_AT)
        return (
            attributes == self.attributes | TRANSACTIONAL_BIT
            and producer_id >= 0 and epoch >= 0 and base_sequence >= 0
            and batch[AFTER_ATTRIBUTES:PRODUCER_AT] == self.wire[AFTER_ATTRIBUTES:PRODUCER_AT]
            and batch[AFTER_PRODUCER:] == self.wire[AFTER_PRODUCER:]
            # all other covered bytes are the template's, so the CRC the
            # batch must carry follows from the 36 that may differ
            and struct.unpack_from(">I", batch, CRC_AT)[0]
            == self._crc_of(batch[BODY_AT:AFTER_PRODUCER])
        )


def incompressible(seed: int, traffic: dict, config: dict) -> list[TransactionalTemplate]:
    """`count` batches of the traffic's `batch_records` records of the
    configuration's `record_bytes` bytes, random values, for a
    transactional producer to stamp."""
    return make_templates(
        seed, int(traffic["templates"]["count"]), int(traffic["batch_records"]),
        int(config["record_bytes"]), make=TransactionalTemplate,
    )
