"""The plain reference against known answers, and against the program's
own decoder (the one place a test here looks at both)."""

import pytest

from benchmark import reference as ref
from benchmark.run import percentile, reduce_records


def test_crc32c_known_vectors():
    assert ref.crc32c(b"123456789") == 0xE3069283
    assert ref.crc32c(b"") == 0
    assert ref.crc32c(bytes(32)) == 0x8A9136AA


def test_templates_repeat_from_the_seed():
    a = ref.make_templates(2**31 + 5, 3, 4, 128)
    b = ref.make_templates(2**31 + 5, 3, 4, 128)
    c = ref.make_templates(2**31 + 6, 3, 4, 128)
    assert [t.wire for t in a] == [t.wire for t in b]
    assert a[0].wire != c[0].wire
    assert a[0].payload_bytes == 4 * 128 and a[0].records == 4
    assert all(ref.batch_holds(t.wire) for t in a)
    assert len({t.crc for t in a}) == 3


def test_program_decodes_the_reference_batch():
    from redpanda_tpu.models.record import RecordBatch

    t = ref.make_templates(7, 1, 5, 64)[0]
    batch = RecordBatch.from_kafka_wire(t.wire, verify=True)
    recs = batch.records()
    assert len(recs) == 5
    assert recs[0].key == b"k000.%011d" % 0 and len(recs[0].value) == 48
    assert batch.to_kafka_wire()[ref.CRC_AT:] == t.tail


def test_split_batches_drops_a_truncated_tail():
    t = ref.make_templates(1, 2, 2, 64)
    blob = t[0].wire + t[1].wire
    assert [b for _o, b in ref.split_batches(blob)] == [t[0].wire, t[1].wire]
    assert len(ref.split_batches(blob[:-1])) == 1
    flipped = bytearray(t[0].wire)
    flipped[-1] ^= 1
    assert not ref.batch_holds(bytes(flipped))


def test_percentile_is_nearest_rank_over_all_values():
    assert percentile(list(range(1, 101)), 0.95) == 95
    assert percentile([5.0], 0.95) == 5.0
    assert percentile(list(range(1, 21)), 0.95) == 19


def _row(base, t_due, t_ack, err, t_fetch, got, tpl=0):
    # topic, partition, template, base, t_due, t_ack, error, t_sent,
    # tries, in_request, t_fetch, fetched_template
    return ["t", 0, tpl, base, t_due, t_ack, err, t_due, 1, 1, t_fetch, got]


def test_reduce_counts_failures_as_missing_the_tail():
    rows = [_row(64 * i, 10.0 + i, 10.5 + i, None, 10.7 + i, 0) for i in range(8)]
    rows.append(_row(-1, 12.0, 12.1, "KafkaClientError(7)", 0.0, -2))
    rows.append(_row(640, 13.0, 13.2, None, 0.0, -2, tpl=1))
    rec = {"t0": 10.0, "seconds": 10.0, "rows": rows, "payload_bytes": 1000,
           "fetch_error_count": 0}
    got = reduce_records(rec, drain_s=60)
    assert got["attempted"] == 10 and got["failed"] == 2 and got["acked"] == 9
    assert got["metrics"]["produce_mb_s"] == 9 * 1000 / 1e6 / 10.0
    assert got["metrics"]["produce_p50_ms"] == pytest.approx(500.0)
    assert got["metrics"]["e2e_p50_ms"] == pytest.approx(700.0)
    assert got["tails"]["produce_p95_ms"] == 60000.0
    assert got["tails"]["e2e_p95_ms"] == 60000.0


def test_latency_counts_from_the_due_time_not_the_send():
    rows = [_row(i, 10.0 + i, 10.25 + i, None, 10.5 + i, 0) for i in range(4)]
    for r in rows:
        r[7] = r[4] + 0.2  # sent late: the wait is the system's to answer for
    rec = {"t0": 10.0, "seconds": 4.0, "rows": rows, "payload_bytes": 1,
           "fetch_error_count": 0}
    got = reduce_records(rec, drain_s=60)["metrics"]
    assert got["produce_p50_ms"] == pytest.approx(250.0)
    assert got["e2e_p50_ms"] == pytest.approx(500.0)


def test_open_loop_due_times_are_even_and_staggered():
    import numpy as np

    from benchmark.generators.open_loop import due_times, steps_of

    steps = steps_of({"batches_per_s": 8}, 2.0)
    assert steps == [(2.0, 8.0)]
    all_due = np.sort(np.concatenate([due_times(steps, 4, i) for i in range(4)]))
    assert len(all_due) == 16
    assert np.allclose(np.diff(all_due), 0.125)
    stairs = steps_of({"schedule": [[1, 4], [1, 8]]}, 99.0)
    assert len(due_times(stairs, 1, 0)) == 12


# -- the reference's LZ4 decoder (benchmark/codecs/lz4.py) ------------

import struct  # noqa: E402

from benchmark.codecs import lz4  # noqa: E402


def test_xxh32_known_vectors():
    assert lz4.xxh32(b"") == 0x02CC5D05
    assert lz4.xxh32(b"abc") == 0x32D153FF
    assert lz4.xxh32(b"Nobody inspects the spammish repetition") == 0xE2293B2F
    assert lz4.xxh32(b"", seed=1) == 0x0B2CB792


def frame(blocks: list, flg: int = 0x60, bd: int = 0x40, size: int | None = None,
          content: bytes | None = None, hc: int | None = None, end: bool = True) -> bytes:
    """An LZ4 frame by hand. `blocks` are (bytes, stored) pairs; the
    checksums follow `flg`; `hc` overrides the header checksum."""
    desc = bytes([flg, bd]) + (struct.pack("<Q", size) if flg & 0x08 else b"")
    out = struct.pack("<I", lz4.MAGIC) + desc
    out += bytes([(lz4.xxh32(desc) >> 8) & 0xFF if hc is None else hc])
    for data, stored in blocks:
        out += struct.pack("<I", len(data) | (0x80000000 if stored else 0)) + data
        if flg & 0x10:
            out += struct.pack("<I", lz4.xxh32(data))
    if end:
        out += struct.pack("<I", 0)
    if flg & 0x04:
        out += struct.pack("<I", lz4.xxh32(content))
    return out


# "ab", then ten bytes from two back (the match overlaps what it
# writes), then five literals: the format's own smallest shapes
OVERLAP = bytes([0x26]) + b"ab" + struct.pack("<H", 2) + bytes([0x50]) + b"vwxyz"
OVERLAP_OUT = b"ab" + b"ab" * 5 + b"vwxyz"
# a literal run and a match that both need length bytes: 15 + 255 + 14
# literals, a match of 4 + 15 + 255 + 3 from 284 back
LONG = (bytes([0xFF, 255, 14]) + bytes(range(256)) + bytes(28) + struct.pack("<H", 284)
        + bytes([255, 3]) + bytes([0x10]) + b"!")
LONG_OUT = bytes(range(256)) + bytes(28) + (bytes(range(256)) + bytes(21)) + b"!"


def test_lz4_decodes_what_the_format_allows():
    assert lz4.decode(frame([(OVERLAP, False)])) == OVERLAP_OUT
    assert lz4.decode(frame([(LONG, False)])) == LONG_OUT
    assert lz4.decode(frame([])) == b""
    assert lz4.decode(frame([(b"plain", True), (OVERLAP, False)])) == b"plain" + OVERLAP_OUT
    both = b"plain" + OVERLAP_OUT
    assert lz4.decode(frame([(b"plain", True), (OVERLAP, False)], flg=0x7C,
                            size=len(both), content=both)) == both
    # a dependent block may reach into the block before it, an
    # independent one may not
    reach = bytes([0x00]) + struct.pack("<H", 5) + bytes([0x10]) + b"."
    assert lz4.decode(frame([(b"plain", True), (reach, False)], flg=0x40)) == b"plainplai."
    assert lz4.decode(frame([(b"plain", True), (reach, False)], flg=0x60)) is None


REFUSED = dict([
    ("empty", b""),
    ("magic", b"\x05" + frame([(b"x", True)])[1:]),
    ("skippable_frame", struct.pack("<II", 0x184D2A50, 0)),
    ("version", frame([(b"x", True)], flg=0xA0)),
    ("reserved_flg_bit", frame([(b"x", True)], flg=0x62)),
    ("dictionary", frame([(b"x", True)], flg=0x61)),
    ("reserved_bd_bit", frame([(b"x", True)], bd=0x41)),
    ("block_size_code_under_4", frame([(b"x", True)], bd=0x30)),
    ("header_checksum", frame([(b"x", True)], hc=(lz4.xxh32(bytes([0x60, 0x40])) >> 8) + 1 & 0xFF)),
    ("no_end_mark", frame([(b"x", True)], end=False)),
    ("block_past_the_end", frame([(b"x", True)])[:-5] + b"\x00\x00\x00"),
    ("block_over_bd_s_size", frame([(bytes(65537), True)])),
    ("decoded_block_over_bd_s_size", frame([(
        bytes([0x1F]) + b"a" + struct.pack("<H", 1) + bytes([255] * 257 + [0]) + bytes([0x10]) + b"b",
        False)])),
    ("literals_over_bd_s_size", frame([(
        bytes([0x1F]) + b"a" + struct.pack("<H", 1) + bytes([255] * 256 + [235]) + bytes([0xA0]) + b"0123456789",
        False)])),
    ("match_length_bytes_cut", frame([(bytes([0x1F]) + b"a" + struct.pack("<H", 1) + bytes([255]), False)])),
    ("block_checksum", frame([(b"x", True)], flg=0x70)[:-8] + b"\x00\x00\x00\x00" + bytes(4)),
    ("content_checksum", frame([(b"x", True)], flg=0x64, content=b"y")),
    ("content_size", frame([(b"x", True)], flg=0x68, size=2)),
    ("trailing_byte", frame([(b"x", True)]) + b"\x00"),
    ("offset_zero", frame([(bytes([0x10]) + b"a" + struct.pack("<H", 0) + bytes([0x10]) + b"b", False)])),
    ("offset_past_the_start", frame([(bytes([0x10]) + b"a" + struct.pack("<H", 2) + bytes([0x10]) + b"b", False)])),
    ("literals_past_the_block", frame([(bytes([0x50]) + b"abc", False)])),
    ("offset_cut", frame([(bytes([0x10]) + b"a" + b"\x01", False)])),
    ("length_bytes_cut", frame([(bytes([0xF0, 255]), False)])),
    ("ends_on_a_match", frame([(bytes([0x10]) + b"a" + struct.pack("<H", 1), False)])),
])


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_lz4_refuses_what_the_format_refuses(name):
    assert lz4.decode(REFUSED[name]) is None


def test_lz4_reads_the_program_s_host_frame():
    from redpanda_tpu.compression import lz4_codec

    for content in (b"", b"a", bytes(3000), bytes(range(256)) * 40,
                    ref.make_templates(3, 1, 5, 200, random_share=0.5)[0].wire):
        assert lz4.decode(lz4_codec.compress_frame(content)) == content


# -- ISSUE 34's in-process cases (test_smoke_over.py), named here so that
# tier-1 collects them with this module's (tests/test_benchmark_reference.py
# takes every `test_*` of this module by import; no PR of the kind that
# wrote them may add a file under tests/) ------------------------------

from benchmark.tests.test_smoke_over import *  # noqa: E402,F401,F403
