"""What has to come back of a batch is its template's to say: the plain
template's verdicts are the byte rule's, unchanged; a rewritten
template takes what the program's two LZ4 encoders store and refuses
every batch that means something else; and the comparison holds the
copies of one batch to each other."""

import asyncio
import os
import struct
import subprocess
import sys

import pytest

from benchmark import compare, reference as ref
from benchmark.codecs import lz4
from benchmark.templates import compressible
from benchmark.tests.conftest import ROOT

SEED = 2**31 + 31
LZ4_TOPIC = {"topics": [{"name": "b", "configs": {"compression.type": "lz4"}}],
             "record_bytes": 256}
TRAFFIC = {"templates": {"count": 3, "random_share": 0.5}, "batch_records": 6}


def templates(config=LZ4_TOPIC):
    return compressible.random_share(SEED, TRAFFIC, config)


def stored_frame(content: bytes, block: int = 65536) -> bytes:
    """A legal LZ4 frame of blocks stored as they are: what the
    simplest broker could write."""
    desc = bytes([0x60, 0x40])
    out = struct.pack("<I", lz4.MAGIC) + desc + bytes([(lz4.xxh32(desc) >> 8) & 0xFF])
    for at in range(0, len(content), block):
        piece = content[at : at + block]
        out += struct.pack("<I", len(piece) | 0x80000000) + piece
    return out + struct.pack("<I", 0)


def rewritten(wire: bytes, section: bytes, codec: int = lz4.BITS, **fields) -> bytes:
    """The batch `wire` as a broker would store it with `section` as its
    records section: the codec in the attributes, the length and the
    CRC made good. `fields` overwrite header fields first."""
    head = bytearray(wire[: ref.RECORDS_AT])
    (attributes,) = struct.unpack_from(">h", head, ref.ATTRIBUTES_AT)
    struct.pack_into(">h", head, ref.ATTRIBUTES_AT, (attributes & ~7) | codec)
    if "record_count" in fields:
        struct.pack_into(">i", head, 57, fields["record_count"])
    if "last_offset_delta" in fields:
        struct.pack_into(">i", head, 23, fields["last_offset_delta"])
    if "attributes" in fields:
        struct.pack_into(">h", head, ref.ATTRIBUTES_AT, fields["attributes"])
    struct.pack_into(">i", head, 8, len(head) + len(section) - 12)
    after = bytes(head[ref.BODY_AT :]) + section
    struct.pack_into(">I", head, ref.CRC_AT, ref.crc32c(after))
    return bytes(head[: ref.BODY_AT]) + after


def test_the_maker_follows_the_topic_and_the_share():
    plain = templates({**LZ4_TOPIC, "topics": [{"name": "b", "configs": {}}]})
    codec = templates()
    assert [type(t) for t in plain] == [ref.Template] * 3
    assert [type(t) for t in codec] == [ref.RewrittenTemplate] * 3
    # the same bytes go out either way, half of every value zero
    assert [t.wire for t in plain] == [t.wire for t in codec]
    assert plain[0].payload_bytes == 6 * 256
    value = codec[0].wire[-120:-1]
    assert value == bytes(119)
    assert len({t.key for t in codec}) == 3 and len({t.key for t in plain}) == 3
    # a share of 1 is the plain maker's batch, byte for byte
    whole = compressible.random_share(
        SEED, {**TRAFFIC, "templates": {"count": 3, "random_share": 1.0}}, LZ4_TOPIC)
    assert [t.wire for t in whole] == [t.wire for t in ref.make_templates(SEED, 3, 6, 256)]
    with pytest.raises(ValueError):
        compressible.topic_codec({"topics": [
            {"configs": {"compression.type": "lz4"}}, {"configs": {}}]})
    for passes in ("producer", "none", "uncompressed", ""):
        assert compressible.topic_codec(
            {"topics": [{"configs": {"compression.type": passes}}]}) is None


def by_hand(t) -> dict:
    """Stored batches made by hand from template `t`: name -> bytes."""
    wire = t.wire
    flipped = bytearray(wire)
    flipped[-1] ^= 1
    stale = bytearray(wire)
    stale[ref.CRC_AT] ^= 0x80
    moved = bytearray(wire)
    struct.pack_into(">qi", moved, 0, 4242, struct.unpack_from(">i", wire, 8)[0])
    struct.pack_into(">i", moved, 12, 7)        # partitionLeaderEpoch
    return {
        "as_sent": wire,
        "offset_and_epoch_stamped": bytes(moved),
        "last_byte_flipped": bytes(flipped),
        "crc_field_flipped": bytes(stale),
        "value_changed_crc_made_good": rewritten(
            wire, bytes(flipped[ref.RECORDS_AT :]), codec=0),
        "truncated": wire[:-1],
        "stored_in_a_frame": rewritten(wire, stored_frame(wire[ref.RECORDS_AT :])),
    }


def test_the_plain_template_gives_the_byte_rule_s_verdicts():
    """`came_back` and `key_of` against the rule as open_loop.py and
    compare.py held it before PR 31: found by the crc field, accepted
    when equal from the crc field on."""
    tpl = ref.make_templates(SEED, 3, 6, 256)
    by_crc = {t.crc: i for i, t in enumerate(tpl)}
    by_key = {t.key: i for i, t in enumerate(tpl)}
    for i, t in enumerate(tpl):
        for name, batch in by_hand(t).items():
            crc = struct.unpack_from(">I", batch, ref.CRC_AT)[0]
            old = by_crc.get(crc, -1)
            if old >= 0 and batch[ref.CRC_AT :] != tpl[old].tail:
                old = -1
            new = by_key.get(tpl[0].key_of(batch), -1)
            if new >= 0 and not tpl[new].came_back(batch):
                new = -1
            assert new == old, name
            assert t.came_back(batch) == (batch[ref.CRC_AT :] == t.tail), name
            assert (new == i) == (name in ("as_sent", "offset_and_epoch_stamped")), name
    # another template's batch is known as the other's, and is not this one's
    assert by_key[tpl[0].key_of(tpl[1].wire)] == 1
    assert not tpl[0].came_back(tpl[1].wire)


def test_a_legal_rewrite_comes_back():
    tpl = templates()
    for i, t in enumerate(tpl):
        batch = rewritten(t.wire, stored_frame(t.wire[ref.RECORDS_AT :], block=100))
        assert t.came_back(batch)
        assert [u.key for u in tpl].index(tpl[0].key_of(batch)) == i
        assert not tpl[(i + 1) % 3].came_back(batch)
        # where the broker put it is the broker's to say
        moved = bytearray(batch)
        struct.pack_into(">q", moved, 0, 99)
        struct.pack_into(">i", moved, 12, 3)
        assert t.came_back(bytes(moved))


def _drop_last_record(section: bytes, records: int) -> bytes:
    pos = 0
    for _ in range(records - 1):
        n = shift = 0
        while True:
            b = section[pos]
            pos += 1
            n |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        pos += (n >> 1) ^ -(n & 1)
    return section[:pos]


def faults(t) -> dict:
    """Stored batches that must not come back: name -> bytes."""
    section = t.wire[ref.RECORDS_AT :]
    good = stored_frame(section)
    changed = bytearray(section)
    changed[40] ^= 0x01                     # inside the first value
    off_by_one = bytearray(good)
    off_by_one[6] = (off_by_one[6] + 1) & 0xFF
    stale = bytearray(rewritten(t.wire, good))
    stale[ref.CRC_AT + 3] ^= 0x01
    # one literal, then a match four bytes back where one is decoded
    past_start = bytes([0x10]) + b"a" + struct.pack("<H", 4) + bytes([0x50]) + b"bcdef"
    desc = bytes([0x60, 0x40])
    compressed = (
        struct.pack("<I", lz4.MAGIC) + desc + bytes([(lz4.xxh32(desc) >> 8) & 0xFF])
        + struct.pack("<I", len(past_start)) + past_start + struct.pack("<I", 0)
    )
    return {
        "value_byte_changed_crc_made_good": rewritten(t.wire, stored_frame(bytes(changed))),
        "record_dropped_count_made_good": rewritten(
            t.wire, stored_frame(_drop_last_record(section, t.records)),
            record_count=t.records - 1, last_offset_delta=t.records - 2),
        "record_dropped_count_as_sent": rewritten(
            t.wire, stored_frame(_drop_last_record(section, t.records))),
        "another_codec_s_bits": rewritten(t.wire, good, codec=2),
        "stale_crc": bytes(stale),
        "stored_plain": t.wire,
        "plain_with_lz4_bits": rewritten(t.wire, section),
        "header_checksum_off_by_one": rewritten(t.wire, bytes(off_by_one)),
        "match_offset_past_the_start": rewritten(t.wire, compressed),
        "transactional_bit_set": rewritten(t.wire, good, attributes=0x10 | lz4.BITS),
        "a_second_frame_after_the_first": rewritten(t.wire, good + stored_frame(b"")),
        "one_byte_short": rewritten(t.wire, good)[:-1],
        "length_field_wrong": rewritten(t.wire, good)[:8] + b"\x00\x00\x00\x01"
        + rewritten(t.wire, good)[12:],
    }


FAULTS = sorted(faults(templates()[0]))


@pytest.mark.parametrize("fault", FAULTS)
def test_a_rewritten_batch_that_means_something_else_does_not_come_back(fault):
    tpl = templates()
    for t in tpl:
        batch = faults(t)[fault]
        assert not t.came_back(batch)
        known = tpl[0].key_of(batch)
        ti = [u.key for u in tpl].index(known) if known in [u.key for u in tpl] else -1
        assert ti < 0 or not tpl[ti].came_back(batch)
    # and the control of the case itself: the same hand, no fault
    good = rewritten(tpl[0].wire, stored_frame(tpl[0].wire[ref.RECORDS_AT :]))
    assert tpl[0].came_back(good)


def test_a_verdict_is_remembered_by_the_stored_bytes_and_not_past_its_room():
    (t,) = compressible.random_share(
        SEED, {**TRAFFIC, "templates": {"count": 1, "random_share": 0.5}}, LZ4_TOPIC)
    t.stored.REMEMBERED = 2
    calls = []
    decode = t.stored.decode
    t.stored.decode = lambda frame: calls.append(1) or decode(frame)
    good = rewritten(t.wire, stored_frame(t.wire[ref.RECORDS_AT :]))
    other = rewritten(t.wire, stored_frame(t.wire[ref.RECORDS_AT :], block=64))
    third = rewritten(t.wire, stored_frame(t.wire[ref.RECORDS_AT :], block=32))
    assert t.came_back(good) and t.came_back(good) and len(calls) == 1
    assert t.came_back(other) and t.came_back(third) and len(calls) == 3
    assert t.came_back(good) and len(calls) == 4    # forgotten, read again


ENCODE = """
import sys
from benchmark.templates import compressible
from redpanda_tpu.models.record import CompressionType, RecordBatch
config = {"topics": [{"name": "b", "configs": {"compression.type": "lz4"}}],
          "record_bytes": 256}
traffic = {"templates": {"count": 3, "random_share": 0.5}, "batch_records": 6}
tpl = compressible.random_share(%d, traffic, config)
for t in tpl:
    sent = RecordBatch.from_kafka_wire(t.wire, verify=True)
    stored = sent.recompressed(CompressionType.lz4, verify_crc=sent.header.crc)
    wire = stored.to_kafka_wire()
    assert len(wire) < len(t.wire)
    assert t.came_back(wire), "the program's batch did not come back"
    assert tpl[0].key_of(wire) == t.key
    assert not tpl[(tpl.index(t) + 1) %% 3].came_back(wire)
print("came_back", len(tpl))
"""


@pytest.mark.parametrize("backend", ["host", "device"])
def test_what_the_program_s_encoders_store_comes_back(backend):
    """The reference's decoder against the program's two LZ4 encoders at
    a small size: the host frame (liblz4) and the device's cell parse,
    here on the CPU backend. In an interpreter of its own: the switch is
    read from the environment."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("RP_CODEC_BACKEND", None)
    if backend == "device":
        env["RP_CODEC_BACKEND"] = "device"
    got = subprocess.run([sys.executable, "-c", ENCODE % SEED], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    assert got.stdout.strip().splitlines()[-1] == "came_back 3"


class _Held:
    """A replica's partition as compare.replicas_missing reads it."""

    def __init__(self, base: int, wire: bytes | None):
        self.base, self.wire = base, wire

    def high_watermark(self) -> int:
        return 10**9

    def read_kafka(self, base, n, upto_kafka=None):
        if self.wire is None:
            return []
        return [(self.base, self)]

    def to_kafka_wire(self) -> bytes:
        return self.wire


def _missing(monkeypatch, tpl, copies) -> int:
    monkeypatch.setattr(
        compare.cluster, "replicas", lambda brokers, topic, p: [_Held(5, w) for w in copies])
    config = {"topics": [{"name": "b", "replication_factor": 3}]}
    rows = [["b", 0, 0, 5, 0.0, 0.0, None]]
    return asyncio.run(compare.replicas_missing([], config, rows, tpl))


def test_the_copies_of_a_batch_are_held_to_the_template_and_to_each_other(monkeypatch):
    plain = ref.make_templates(SEED, 2, 6, 256)
    assert _missing(monkeypatch, plain, [plain[0].wire] * 3) == 0
    assert _missing(monkeypatch, plain, [plain[0].wire, plain[1].wire, plain[0].wire]) == 1
    assert _missing(monkeypatch, plain, [plain[0].wire, None, plain[0].wire]) == 1
    assert _missing(monkeypatch, plain, [plain[0].wire] * 2) == 1
    codec = templates()
    section = codec[0].wire[ref.RECORDS_AT :]
    leader = rewritten(codec[0].wire, stored_frame(section))
    own = rewritten(codec[0].wire, stored_frame(section, block=512))
    assert codec[0].came_back(leader) and codec[0].came_back(own)
    assert _missing(monkeypatch, codec, [leader] * 3) == 0
    # a follower that compressed for itself holds the records and not the bytes
    assert _missing(monkeypatch, codec, [leader, own, leader]) == 1
    assert _missing(monkeypatch, codec, [leader, codec[0].wire, leader]) == 1
