"""The plain reference: Kafka v2 record batches and CRC-32C, written out
from the Kafka protocol's own description and importing nothing of the
program. The load generator sends what `make_templates` encodes; the
comparison holds what comes back, and what every replica stores, to the
same bytes.

A v2 record batch on the wire:

    baseOffset i64 | batchLength i32 | partitionLeaderEpoch i32 | magic i8
    | crc u32 | attributes i16 | lastOffsetDelta i32 | baseTimestamp i64
    | maxTimestamp i64 | producerId i64 | producerEpoch i16
    | baseSequence i32 | recordCount i32 | records...

The CRC is CRC-32C (Castagnoli) over everything after the crc field.
A broker assigns baseOffset and may stamp partitionLeaderEpoch; it may
not touch a byte from the crc field on (offset `CRC_AT`), or the CRC no
longer holds. So "what was acknowledged is what is read back" is
`wire[CRC_AT:]` equal, byte for byte, with the base offset the ack gave.
"""

from __future__ import annotations

import struct

import numpy as np

CRC_AT = 17          # offset of the crc field in a wire batch
BODY_AT = 21         # first byte the crc covers (attributes)
RECORDS_AT = 61      # first record
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
    """One pre-encoded batch and what has to come back of it."""

    def __init__(self, wire: bytes, records: list[tuple[bytes, bytes]]):
        self.wire = wire
        self.tail = wire[CRC_AT:]       # what no broker may change
        self.crc = struct.unpack_from(">I", wire, CRC_AT)[0]
        self.records = len(records)
        self.payload_bytes = sum(len(k) + len(v) for k, v in records)


def make_templates(
    seed: int, n: int, batch_records: int, record_bytes: int
) -> list[Template]:
    """`n` batches of `batch_records` records of `record_bytes` bytes
    (a 16-byte key and a random value: nothing compresses), from
    `seed`."""
    rng = np.random.default_rng(seed)
    vlen = record_bytes - 16
    out = []
    for t in range(n):
        raw = rng.integers(0, 256, (batch_records, vlen), dtype=np.uint8)
        recs = [
            (b"k%03d.%011d" % (t, i), raw[i].tobytes())
            for i in range(batch_records)
        ]
        out.append(Template(encode_batch(recs), recs))
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
