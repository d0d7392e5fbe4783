"""The plain reference: Kafka v2 record batches and CRC-32C, written out
from the Kafka protocol's own description and importing nothing of the
program. The load generator sends what `make_templates` encodes; what has
to come back of a batch, from a fetch and from every replica's log, is
its template's to say (`came_back`), and the load generator and the
comparison ask the template and compare nothing themselves.

A v2 record batch on the wire:

    baseOffset i64 | batchLength i32 | partitionLeaderEpoch i32 | magic i8
    | crc u32 | attributes i16 | lastOffsetDelta i32 | baseTimestamp i64
    | maxTimestamp i64 | producerId i64 | producerEpoch i16
    | baseSequence i32 | recordCount i32 | records...

The CRC is CRC-32C (Castagnoli) over everything after the crc field.
A broker assigns baseOffset and may stamp partitionLeaderEpoch.

On a pass-through topic (no `compression.type`, or `producer`) it may
not touch a byte from the crc field on (offset `CRC_AT`), or the CRC no
longer holds. So "what was acknowledged is what is read back" is
`wire[CRC_AT:]` equal, byte for byte, with the base offset the ack gave:
`Template`.

On a topic that sets a codec the broker compresses the records section
of a batch that was sent plain, sets the codec in the attributes and
makes the CRC anew. No reference can say which bytes a compressor emits,
so `RewrittenTemplate` says what they have to mean: the batch's own CRC
holds; the attributes name the topic's codec and are otherwise as sent;
every header field from lastOffsetDelta to recordCount is as sent; and
the records section, decoded by the reference's own decoder
(benchmark/codecs/<codec>.py), is byte for byte the one that was sent.
"""

from __future__ import annotations

import importlib
import struct

import numpy as np

CRC_AT = 17          # offset of the crc field in a wire batch
BODY_AT = 21         # first byte the crc covers (attributes)
ATTRIBUTES_AT = 21   # attributes i16: bits 0-2 name the codec
AFTER_ATTRIBUTES = 23  # lastOffsetDelta
RECORDS_AT = 61      # first record
CODEC_BITS = 0x07
_HEAD = struct.Struct(">qiibI")          # base, length, epoch, magic, crc
_AFTER_CRC = struct.Struct(">hiqqqhii")  # attributes ... recordCount
#: fixed, not the clock: the same seed gives the same bytes
BASE_TIMESTAMP_MS = 1_700_000_000_000


def _crc_table() -> list[int]:
    poly = 0x82F63B78  # Castagnoli, reflected
    table = []
    for n in range(256):
        c = n
        for _ in range(8):
            c = (c >> 1) ^ poly if c & 1 else c >> 1
        table.append(c)
    return table


_TABLE = _crc_table()


def crc32c(data: bytes) -> int:
    """CRC-32C of `data`, one table look-up a byte."""
    c = 0xFFFFFFFF
    table = _TABLE
    for b in data:
        c = table[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def _varint(n: int) -> bytes:
    """Zig-zag varint, as Kafka's records use for every length."""
    z = (n << 1) ^ (n >> 63)
    out = bytearray()
    while z & ~0x7F:
        out.append((z & 0x7F) | 0x80)
        z >>= 7
    out.append(z)
    return bytes(out)


def encode_batch(records: list[tuple[bytes, bytes]]) -> bytes:
    """One uncompressed v2 batch of (key, value) records, base offset 0,
    no producer id, CreateTime timestamps one millisecond apart."""
    body = bytearray()
    for i, (key, value) in enumerate(records):
        rec = (
            b"\x00"                       # record attributes
            + _varint(i)                  # timestampDelta
            + _varint(i)                  # offsetDelta
            + _varint(len(key)) + key
            + _varint(len(value)) + value
            + _varint(0)                  # no headers
        )
        body += _varint(len(rec)) + rec
    n = len(records)
    after = _AFTER_CRC.pack(
        0, n - 1, BASE_TIMESTAMP_MS, BASE_TIMESTAMP_MS + n - 1, -1, -1, -1, n
    ) + bytes(body)
    # batchLength counts from partitionLeaderEpoch on
    head = _HEAD.pack(0, 4 + 1 + 4 + len(after), -1, 2, crc32c(after))
    return head + after


class Template:
    """One pre-encoded batch and what has to come back of it, on a topic
    whose broker passes batches through. The harness asks a template two
    things. `key_of(batch)` is what a stored batch is known by, whatever
    template it is of (any template of a run answers for all of them);
    the batch is of the template whose `key` that is. `came_back(batch)`
    says whether a whole wire batch, as a fetch or a replica's log
    returns it, is this template's batch as it had to be stored."""

    def __init__(self, wire: bytes, records: list[tuple[bytes, bytes]]):
        self.wire = wire
        self.tail = wire[CRC_AT:]       # what no broker may change
        self.crc = struct.unpack_from(">I", wire, CRC_AT)[0]
        self.key = self.crc
        self.records = len(records)
        self.payload_bytes = sum(len(k) + len(v) for k, v in records)

    def key_of(self, batch: bytes):
        return struct.unpack_from(">I", batch, CRC_AT)[0]

    def came_back(self, batch: bytes) -> bool:
        return batch[CRC_AT:] == self.tail


class Stored:
    """What the batches of a topic with a codec hold, read with the
    reference's own decoder: one for all the templates of a run, so
    that a stored batch is decoded once however often it is fetched.
    The verdict on a batch follows from its bytes from the crc field on
    alone, so remembering it by those bytes changes no verdict."""

    REMEMBERED = 256

    def __init__(self, codec: str) -> None:
        module = importlib.import_module(f"benchmark.codecs.{codec}")
        self.bits = int(module.BITS)
        self.decode = module.decode
        self._seen: dict[bytes, bytes | None] = {}

    def records_section(self, batch: bytes) -> bytes | None:
        """The decoded records section of a whole wire batch; None
        where the batch is not a v2 batch of its own length whose CRC
        holds, whose attributes name this codec and whose records
        section the decoder takes."""
        if len(batch) < RECORDS_AT:
            return None
        length, _epoch, magic = struct.unpack_from(">iib", batch, 8)
        if magic != 2 or length != len(batch) - 12:
            return None
        tail = batch[CRC_AT:]
        if tail not in self._seen:
            if len(self._seen) >= self.REMEMBERED:
                self._seen.clear()
            self._seen[tail] = self._read(batch)
        return self._seen[tail]

    def _read(self, batch: bytes) -> bytes | None:
        (attributes,) = struct.unpack_from(">h", batch, ATTRIBUTES_AT)
        if (attributes & CODEC_BITS) != self.bits or not batch_holds(batch):
            return None
        return self.decode(batch[RECORDS_AT:])


class RewrittenTemplate(Template):
    """One pre-encoded plain batch and what has to come back of it on a
    topic whose broker compresses the records section with `stored`'s
    codec: see the head of this file. The tolerance is none."""

    def __init__(
        self, wire: bytes, records: list[tuple[bytes, bytes]], stored: Stored
    ):
        super().__init__(wire, records)
        self.stored = stored
        self.key = wire[RECORDS_AT:]    # the records section as sent
        (self.attributes,) = struct.unpack_from(">h", wire, ATTRIBUTES_AT)
        if self.attributes & CODEC_BITS:
            raise ValueError("a rewritten template is made from a plain batch")

    def key_of(self, batch: bytes):
        return self.stored.records_section(batch)

    def came_back(self, batch: bytes) -> bool:
        section = self.stored.records_section(batch)
        if section is None or section != self.key:
            return False
        (attributes,) = struct.unpack_from(">h", batch, ATTRIBUTES_AT)
        return (
            (attributes & ~CODEC_BITS) == self.attributes
            and batch[AFTER_ATTRIBUTES:RECORDS_AT]
            == self.wire[AFTER_ATTRIBUTES:RECORDS_AT]
        )


def make_templates(
    seed: int, n: int, batch_records: int, record_bytes: int,
    random_share: float = 1.0, make=Template,
) -> list[Template]:
    """`n` batches of `batch_records` records of `record_bytes` bytes,
    from `seed`: a 16-byte key and a value whose first `random_share`
    is random and whose rest is zero (at 1.0, the default, nothing
    compresses). `make(wire, records)` makes each template."""
    rng = np.random.default_rng(seed)
    vlen = record_bytes - 16
    random_bytes = int(vlen * random_share)
    out = []
    for t in range(n):
        raw = rng.integers(0, 256, (batch_records, vlen), dtype=np.uint8)
        raw[:, random_bytes:] = 0
        recs = [
            (b"k%03d.%011d" % (t, i), raw[i].tobytes())
            for i in range(batch_records)
        ]
        out.append(make(encode_batch(recs), recs))
    return out


def split_batches(wire: bytes) -> list[tuple[int, bytes]]:
    """A fetched record set as [(baseOffset, whole batch bytes)]; a
    truncated last batch, which Kafka allows, is dropped."""
    out = []
    pos = 0
    while pos + 12 <= len(wire):
        base, length = struct.unpack_from(">qi", wire, pos)
        if length <= 0 or pos + 12 + length > len(wire):
            break
        out.append((base, wire[pos : pos + 12 + length]))
        pos += 12 + length
    return out


def batch_holds(batch: bytes) -> bool:
    """The batch's own CRC field matches its bytes."""
    if len(batch) < RECORDS_AT:
        return False
    return struct.unpack_from(">I", batch, CRC_AT)[0] == crc32c(batch[BODY_AT:])
