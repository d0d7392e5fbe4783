"""The reference's LZ4 decoder: the frame a Kafka v2 batch with codec 3
carries as its records section, written out from the LZ4 frame format
and block format descriptions and importing nothing of the program and
no LZ4 library.

A frame:

    magic 0x184D2204 (le) | FLG | BD | [content size u64] | [dict id u32]
    | HC | blocks... | end mark 0x00000000 | [content checksum u32]

FLG: bits 7-6 version (01), 5 block independence, 4 block checksums,
3 content size present, 2 content checksum present, 1 reserved (0),
0 dictionary id present. BD: bit 7 and bits 3-0 reserved (0), bits 6-4
the largest block: 4 = 64 KiB, 5 = 256 KiB, 6 = 1 MiB, 7 = 4 MiB. HC is
the second byte of xxHash32, seed 0, over the descriptor (FLG up to HC).
A block is a u32 (le) size, its high bit set where the block is stored
as it was, then that many bytes, then its xxHash32 where FLG says so.

A compressed block is a run of sequences: a token (high nibble the
literal length, low nibble the match length less 4; a nibble of 15 goes
on in bytes that are added until one is under 255), the literals, a
16-bit (le) offset back into what is decoded, and the match, which may
overlap what it writes. The last sequence of a block ends after its
literals.

`decode` refuses what the format refuses, by returning None: another
magic or version, a reserved bit set, a dictionary (there is none to
decode with), a wrong header, block or content checksum, a block longer
than BD allows or than the bytes that are there, an offset of 0 or past
the start of what it may reach, a block that ends inside a sequence or
after a match, a content size that is not the content's, no end mark,
and anything after the frame: a Kafka consumer reads one frame and
stops, so records in a second frame would be lost to it.
"""

from __future__ import annotations

import struct

#: the compression bits of a v2 batch's attributes that name this codec
BITS = 3

MAGIC = 0x184D2204
_P1, _P2, _P3, _P4, _P5 = 2654435761, 2246822519, 3266489917, 668265263, 374761393
_M = 0xFFFFFFFF


def xxh32(data: bytes, seed: int = 0) -> int:
    """xxHash32 of `data`, from its description: four lanes over
    16-byte stripes, the tail by words and bytes, the avalanche."""
    n = len(data)
    pos = 0
    if n >= 16:
        v1 = (seed + _P1 + _P2) & _M
        v2 = (seed + _P2) & _M
        v3 = seed & _M
        v4 = (seed - _P1) & _M
        stripes = n // 16
        words = struct.unpack_from("<%dI" % (4 * stripes), data, 0)
        for i in range(0, 4 * stripes, 4):
            v1 = (v1 + words[i] * _P2) & _M
            v1 = (((v1 << 13) | (v1 >> 19)) * _P1) & _M
            v2 = (v2 + words[i + 1] * _P2) & _M
            v2 = (((v2 << 13) | (v2 >> 19)) * _P1) & _M
            v3 = (v3 + words[i + 2] * _P2) & _M
            v3 = (((v3 << 13) | (v3 >> 19)) * _P1) & _M
            v4 = (v4 + words[i + 3] * _P2) & _M
            v4 = (((v4 << 13) | (v4 >> 19)) * _P1) & _M
        pos = 16 * stripes
        h = (
            ((v1 << 1) | (v1 >> 31)) + ((v2 << 7) | (v2 >> 25))
            + ((v3 << 12) | (v3 >> 20)) + ((v4 << 18) | (v4 >> 14))
        ) & _M
    else:
        h = (seed + _P5) & _M
    h = (h + n) & _M
    while pos + 4 <= n:
        h = (h + struct.unpack_from("<I", data, pos)[0] * _P3) & _M
        h = (((h << 17) | (h >> 15)) * _P4) & _M
        pos += 4
    while pos < n:
        h = (h + data[pos] * _P5) & _M
        h = (((h << 11) | (h >> 21)) * _P1) & _M
        pos += 1
    h = ((h ^ (h >> 15)) * _P2) & _M
    h = ((h ^ (h >> 13)) * _P3) & _M
    return h ^ (h >> 16)


def decode_block(block: bytes, out: bytearray, floor: int, limit: int) -> bool:
    """Decode one compressed block onto the end of `out`. A match may
    reach back as far as `out[floor]`; the block may add at most
    `limit` bytes. False where the block is not one the format allows."""
    pos, end = 0, len(block)
    start = len(out)
    while True:
        token = block[pos]
        pos += 1
        run = token >> 4
        if run == 15:
            while True:
                if pos >= end:
                    return False
                more = block[pos]
                pos += 1
                run += more
                if more != 255:
                    break
        if pos + run > end:
            return False
        out += block[pos : pos + run]
        pos += run
        if len(out) - start > limit:
            return False
        if pos == end:
            return True  # the last sequence: literals alone
        if pos + 2 > end:
            return False
        offset = block[pos] | (block[pos + 1] << 8)
        pos += 2
        length = (token & 15) + 4
        if length == 19:
            while True:
                if pos >= end:
                    return False
                more = block[pos]
                pos += 1
                length += more
                if more != 255:
                    break
        if pos >= end:
            return False  # a block never ends on a match
        at = len(out) - offset
        if offset == 0 or at < floor or len(out) - start + length > limit:
            return False
        if offset >= length:
            out += out[at : at + length]
        else:  # the match overlaps what it writes: the pattern repeats
            pattern = bytes(out[at:])
            out += (pattern * (length // offset + 1))[:length]


def decode(frame: bytes) -> bytes | None:
    """The content of the one LZ4 frame that `frame` is, or None where
    it is not one frame the format allows, whole and alone."""
    frame = bytes(frame)
    n = len(frame)
    if n < 7 or struct.unpack_from("<I", frame, 0)[0] != MAGIC:
        return None
    flg, bd = frame[4], frame[5]
    if flg >> 6 != 1 or flg & 0x02 or flg & 0x01 or bd & 0x8F or (bd >> 4) < 4:
        return None
    independent = bool(flg & 0x20)
    block_sums, has_size, content_sum = flg & 0x10, flg & 0x08, flg & 0x04
    pos = 6 + (8 if has_size else 0)
    if pos + 1 > n or (xxh32(frame[4:pos]) >> 8) & 0xFF != frame[pos]:
        return None
    said = struct.unpack_from("<Q", frame, 6)[0] if has_size else None
    pos += 1
    largest = 1 << (8 + 2 * (bd >> 4))
    out = bytearray()
    while True:
        if pos + 4 > n:
            return None  # no end mark
        (word,) = struct.unpack_from("<I", frame, pos)
        pos += 4
        if word == 0:
            break
        size, stored = word & 0x7FFFFFFF, word >> 31
        if size > largest or pos + size > n:
            return None
        block = frame[pos : pos + size]
        pos += size
        if block_sums:
            if pos + 4 > n or struct.unpack_from("<I", frame, pos)[0] != xxh32(block):
                return None
            pos += 4
        if stored:
            out += block
        else:
            # a dependent block's matches may reach into the blocks before it
            if not decode_block(block, out, len(out) if independent else 0, largest):
                return None
    if content_sum:
        if pos + 4 > n or struct.unpack_from("<I", frame, pos)[0] != xxh32(out):
            return None
        pos += 4
    if pos != n or (said is not None and said != len(out)):
        return None
    return bytes(out)
