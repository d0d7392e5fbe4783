"""Fused device CRC32C + LZ4 and broker-side recompression.

Reference: BASELINE.md north-star #1 ("CRC32c + compress" as one
device program), src/v/compression/compression.h:21 (registry gate),
and Kafka's compression.type topic config semantics (the broker
recompresses uncompressed producer batches).
"""

import asyncio
import os

import numpy as np
import pytest

from redpanda_tpu.compression import CompressionType, lz4_codec
from redpanda_tpu.models.record import (
    CrcMismatch,
    RecordBatch,
    RecordBatchBuilder,
)
from redpanda_tpu.ops.fused import crc_lz4_fused
from redpanda_tpu.utils import crc as host_crc

from test_kafka_e2e import broker_cluster, client_for  # noqa: F401


def _payloads(rng, n, max_len=4000):
    out = []
    for i in range(n):
        if i % 3 == 0:
            out.append(rng.integers(0, 256, rng.integers(32, max_len)).astype(np.uint8).tobytes())
        else:  # compressible
            out.append((b"abcd%d" % i) * (rng.integers(8, max_len // 8)))
    return out


def test_fused_matches_host_crc_and_roundtrips_lz4():
    rng = np.random.default_rng(11)
    bodies = _payloads(rng, 24)
    prefixes = [bytes(rng.integers(0, 256, 40, np.uint8)) for _ in bodies]
    crcs, blocks = crc_lz4_fused(prefixes, bodies)
    for p, b, c, blk in zip(prefixes, bodies, crcs, blocks):
        assert int(c) == host_crc.crc32c(b, host_crc.crc32c(p))
        # the block decompresses (or was stored raw by the fallback)
        if len(blk) < len(b):
            assert lz4_codec.decompress_block(blk, len(b)) == b


def test_fused_frame_assembly_interops_with_frame_decoder():
    rng = np.random.default_rng(5)
    bodies = _payloads(rng, 6, max_len=30000)
    prefixes = [b"\x00" * 40 for _ in bodies]
    _crcs, blocks = crc_lz4_fused(prefixes, bodies)
    for body, blk in zip(bodies, blocks):
        frame = lz4_codec.frame_from_blocks([blk], [body])
        assert lz4_codec.decompress_frame(frame) == body


@pytest.mark.parametrize("bodies,rows", [(1, 1), (2, 2), (3, 4), (9, 16)])
def test_fused_dispatches_the_rows_the_call_holds(monkeypatch, bodies, rows):
    """A dispatch has the rows of its call, to the next power of two (the
    program's device time is its row count's: PERF.md section 5), one
    compile a bucket, and every body's CRC and block are those of a call
    with that body alone."""
    from redpanda_tpu.ops import fused
    from redpanda_tpu.utils import compileguard

    rng = np.random.default_rng(33 + bodies)
    payloads = _payloads(rng, bodies, max_len=500)
    prefixes = [bytes(rng.integers(0, 256, 40, np.uint8)) for _ in payloads]
    shapes = []
    kernel = fused._fused

    def recording(data, body_len, n):
        shapes.append((data.shape, body_len.shape, n))
        return kernel(data, body_len, n)

    monkeypatch.setattr(fused, "_fused", recording)
    crcs, blocks = crc_lz4_fused(prefixes, payloads)
    assert shapes == [((rows, 1024), (rows,), 512)]
    compiled = compileguard.compile_counts()["fused.crc_lz4"]
    again_crcs, again_blocks = crc_lz4_fused(prefixes, payloads)
    assert compileguard.compile_counts()["fused.crc_lz4"] == compiled
    assert list(again_crcs) == list(crcs) and again_blocks == blocks
    for p, b, c, blk in zip(prefixes, payloads, crcs, blocks):
        alone_crc, alone_blk = crc_lz4_fused([p], [b])
        assert shapes[-1] == ((1, 1024), (1,), 512)
        assert int(alone_crc[0]) == int(c) == host_crc.crc32c(b, host_crc.crc32c(p))
        assert alone_blk == [blk]
    # one program a row bucket and no more, however many calls
    assert compileguard.compile_counts()["fused.crc_lz4"] <= compiled + 1


def test_fetch_verify_keeps_its_row_floor(monkeypatch):
    """`row_bucket`'s default floor, which the fetch verify's
    `crc32c_batch_device` stages by and the benchmark's CRC warmer pins
    (`opsbytes.ROW_FLOOR`), is still 8."""
    from benchmark import opsbytes
    from redpanda_tpu.ops import crc32c, shapes

    assert shapes.row_bucket(1) == opsbytes.ROW_FLOOR == 8
    assert shapes.row_bucket(1, floor=1) == 1
    seen = []
    kernel = crc32c.crc32c_device

    def recording(data, lens):
        seen.append((data.shape, lens.shape))
        return kernel(data, lens)

    monkeypatch.setattr(crc32c, "crc32c_device", recording)
    body = np.arange(700, dtype=np.uint8)[None, :]
    got = crc32c.crc32c_batch_device(body, np.array([700]))
    assert seen == [((8, 1024 // 4), (8,))]
    assert [int(c) for c in got] == [host_crc.crc32c(body[0].tobytes())]


def test_recompressed_batch_device_and_host_agree(monkeypatch):
    b = RecordBatchBuilder(base_offset=7)
    for i in range(50):
        b.add(b"value-%d" % i * 20, key=b"k%d" % i)
    batch = b.build()
    host = batch.recompressed(CompressionType.lz4, verify_crc=batch.header.crc)
    monkeypatch.setenv("RP_CODEC_BACKEND", "device")
    dev = batch.recompressed(CompressionType.lz4, verify_crc=batch.header.crc)
    for out in (host, dev):
        assert out.header.compression == CompressionType.lz4
        assert out.verify_crc()
        # records identical after decompression
        got = [(r.key, r.value) for r in out.records()]
        want = [(r.key, r.value) for r in batch.records()]
        assert got == want
    # device verify catches a corrupt wire crc in the same pass
    with pytest.raises(CrcMismatch):
        batch.recompressed(CompressionType.lz4, verify_crc=batch.header.crc ^ 1)


async def _produce_recompression(tmp_path, backend):
    saved = os.environ.get("RP_CODEC_BACKEND")
    if backend:
        os.environ["RP_CODEC_BACKEND"] = backend
    else:
        os.environ.pop("RP_CODEC_BACKEND", None)
    try:
        async with broker_cluster(tmp_path, 1) as brokers:
            async with client_for(brokers) as client:
                await client.create_topic(
                    "comp",
                    partitions=1,
                    replication_factor=1,
                    configs={"compression.type": "lz4"},
                )
                records = [(b"k%d" % i, b"payload-%d" % i * 30) for i in range(40)]
                await client.produce("comp", 0, records)
                # stored batch is LZ4 on disk (the broker recompressed)
                from redpanda_tpu.models.fundamental import kafka_ntp

                p = brokers[0].partition_manager.get(kafka_ntp("comp", 0))
                stored = [
                    bt
                    for bt in p.log.read(0, max_bytes=1 << 24)
                    if bt.header.type.name == "raft_data"
                ]
                assert stored, "no data batches on disk"
                assert all(
                    bt.header.compression == CompressionType.lz4
                    for bt in stored
                )
                # and consumers read the records back transparently
                got = await client.fetch("comp", 0, 0, max_wait_ms=300)
                assert [(k, v) for _o, k, v in got] == records
    finally:
        if saved is None:
            os.environ.pop("RP_CODEC_BACKEND", None)
        else:
            os.environ["RP_CODEC_BACKEND"] = saved


def test_produce_recompression_host(tmp_path):
    asyncio.run(_produce_recompression(tmp_path, None))


def test_produce_recompression_device(tmp_path):
    asyncio.run(_produce_recompression(tmp_path, "device"))


def test_producer_codec_kept_when_config_is_producer(tmp_path):
    async def main():
        async with broker_cluster(tmp_path, 1) as brokers:
            async with client_for(brokers) as client:
                await client.create_topic("plain", partitions=1,
                                          replication_factor=1)
                await client.produce("plain", 0, [(b"k", b"v" * 100)])
                from redpanda_tpu.models.fundamental import kafka_ntp

                p = brokers[0].partition_manager.get(kafka_ntp("plain", 0))
                stored = [
                    bt
                    for bt in p.log.read(0, max_bytes=1 << 24)
                    if bt.header.type.name == "raft_data"
                ]
                assert all(
                    bt.header.compression == CompressionType.none
                    for bt in stored
                )

    asyncio.run(main())


def test_codec_mismatch_transcoded(tmp_path):
    """A producer using gzip against a compression.type=lz4 topic gets
    deep-recompressed to lz4 (Kafka LogValidator semantics)."""

    async def main():
        async with broker_cluster(tmp_path, 1) as brokers:
            async with client_for(brokers) as client:
                await client.create_topic(
                    "xcode", partitions=1, replication_factor=1,
                    configs={"compression.type": "lz4"},
                )
                b = RecordBatchBuilder(compression=CompressionType.gzip)
                recs = [(b"k%d" % i, b"v%d" % i * 40) for i in range(20)]
                for k, v in recs:
                    b.add(v, key=k)
                wire = b.build().to_kafka_wire()
                await client.produce_wire("xcode", 0, wire, acks=-1)
                from redpanda_tpu.models.fundamental import kafka_ntp

                p = brokers[0].partition_manager.get(kafka_ntp("xcode", 0))
                stored = [
                    bt
                    for bt in p.log.read(0, max_bytes=1 << 24)
                    if bt.header.type.name == "raft_data"
                ]
                assert all(
                    bt.header.compression == CompressionType.lz4
                    for bt in stored
                ), [bt.header.compression for bt in stored]
                got = await client.fetch("xcode", 0, 0, max_wait_ms=300)
                assert [(k, v) for _o, k, v in got] == recs

    asyncio.run(main())


def test_matching_codec_still_crc_verified():
    """A batch already in the topic's codec must STILL be rejected on a
    corrupt wire CRC (the server delegates verification here)."""
    b = RecordBatchBuilder(compression=CompressionType.lz4)
    for i in range(5):
        b.add(b"v%d" % i * 50, key=b"k%d" % i)
    batch = b.build()
    assert batch.recompressed(
        CompressionType.lz4, verify_crc=batch.header.crc
    ) is batch
    with pytest.raises(CrcMismatch):
        batch.recompressed(CompressionType.lz4, verify_crc=batch.header.crc ^ 1)


def test_uncompressed_config_forces_decompression(tmp_path):
    """compression.type=uncompressed decompresses producer batches
    (LogValidator semantics)."""

    async def main():
        async with broker_cluster(tmp_path, 1) as brokers:
            async with client_for(brokers) as client:
                await client.create_topic(
                    "unc", partitions=1, replication_factor=1,
                    configs={"compression.type": "uncompressed"},
                )
                b = RecordBatchBuilder(compression=CompressionType.gzip)
                recs = [(b"k%d" % i, b"v%d" % i * 30) for i in range(10)]
                for k, v in recs:
                    b.add(v, key=k)
                await client.produce_wire(
                    "unc", 0, b.build().to_kafka_wire(), acks=-1
                )
                from redpanda_tpu.models.fundamental import kafka_ntp

                p = brokers[0].partition_manager.get(kafka_ntp("unc", 0))
                stored = [
                    bt
                    for bt in p.log.read(0, max_bytes=1 << 24)
                    if bt.header.type.name == "raft_data"
                ]
                assert all(
                    bt.header.compression == CompressionType.none
                    for bt in stored
                )
                got = await client.fetch("unc", 0, 0, max_wait_ms=300)
                assert [(k, v) for _o, k, v in got] == recs

    asyncio.run(main())


# -- the served path against the benchmark's plain reference (ISSUE 32) ---
REF_SEED = 2**31 + 32
REF_RECORDS, REF_RECORD_BYTES = 5, 1024


def _reference_templates(codec_topic: bool):
    """Seeded `compressible.random_share` batches of a few 1 KB records,
    half of every value random: `RewrittenTemplate`s where the topic
    names lz4, plain ones (a record shorter, so that no byte count of
    theirs is one of the others') where it passes batches through."""
    from benchmark.templates import compressible

    configs = {"compression.type": "lz4"} if codec_topic else {}
    records = REF_RECORDS if codec_topic else REF_RECORDS - 1
    return compressible.random_share(
        REF_SEED,
        {"templates": {"count": 2, "random_share": 0.5}, "batch_records": records},
        {"topics": [{"name": "t", "configs": configs}], "record_bytes": REF_RECORD_BYTES},
    )


async def _serve_reference_batches(tmp_path, rewritten, plain):
    """Three brokers, an lz4 topic and a pass-through one at RF=3: what
    a fetch returns of the first, each replica's copy of it, and the
    base offsets the acks gave."""
    from redpanda_tpu.models.fundamental import kafka_ntp
    from benchmark import reference as ref

    async with broker_cluster(tmp_path, 3) as brokers:
        async with client_for(brokers) as client:
            await client.create_topic(
                "codec", partitions=1, replication_factor=3,
                configs={"compression.type": "lz4"})
            await client.create_topic("plain", partitions=1, replication_factor=3)
            bases = [await client.produce_wire("codec", 0, t.wire, acks=-1)
                     for t in rewritten]
            await client.produce_wire("plain", 0, plain[0].wire, acks=-1)
            wire, _next = await client.fetch_raw("codec", 0, 0)
            end = bases[-1] + REF_RECORDS
            copies = []
            for b in brokers:
                part = b.partition_manager.get(kafka_ntp("codec", 0))
                assert part is not None, b.node_id
                for _ in range(200):
                    if part.high_watermark() >= end:
                        break
                    await asyncio.sleep(0.05)
                copies.append([
                    batch.to_kafka_wire()
                    for _base, batch in part.read_kafka(0, 1 << 20, upto_kafka=end)
                ])
    return bases, ref.split_batches(wire), copies


@pytest.mark.parametrize("path", ["device", "host"])
def test_recompressing_topic_agrees_with_the_plain_reference(tmp_path, monkeypatch, path):
    """The program against benchmark/reference.py at a small size: what
    an lz4 topic stores of a plain batch is, by the reference's own
    decoder (benchmark/codecs/lz4.py, no liblz4), the batch that was
    sent; every replica holds the leader's bytes; and every rewritten
    batch left one `produce.recompress` span that says what it did."""
    from benchmark import reference as ref
    from redpanda_tpu.observability import trace

    if path == "device":
        monkeypatch.setenv("RP_CODEC_BACKEND", "device")
    else:
        monkeypatch.delenv("RP_CODEC_BACKEND", raising=False)
    monkeypatch.setattr(trace, "ENABLED", True)
    monkeypatch.setattr(trace.WINDOW, "keep_raw", True)
    trace.WINDOW.reset()
    rewritten, plain = _reference_templates(True), _reference_templates(False)
    assert all(isinstance(t, ref.RewrittenTemplate) for t in rewritten)
    # compile the fused program before anything ticks, as the benchmark's
    # warmer does: a compile on the loop outlasts the election timeout
    RecordBatch.from_kafka_wire(rewritten[0].wire).recompressed(CompressionType.lz4)
    trace.WINDOW.reset()
    try:
        bases, fetched, copies = asyncio.run(
            _serve_reference_batches(tmp_path, rewritten, plain))
        spans = trace.WINDOW.status()["spans"]
    finally:
        trace.WINDOW.reset()

    assert [base for base, _b in fetched] == bases
    for (_base, stored), t in zip(fetched, rewritten):
        assert t.came_back(stored) and t.key_of(stored) == t.key
        assert len(stored) < len(t.wire)
        assert not any(o.came_back(stored) for o in rewritten if o is not t)
        # one flipped stored byte, in the records section or the header
        for at in (len(stored) - 9, ref.RECORDS_AT + 11, ref.AFTER_ATTRIBUTES + 1):
            flipped = bytearray(stored)
            flipped[at] ^= 0x01
            assert not t.came_back(bytes(flipped)), at
        # what was sent is no answer on this topic
        assert not t.came_back(t.wire)
    # every replica holds the leader's stored bytes, from the crc field on
    assert len(copies) == 3
    for held in copies:
        assert [w[ref.CRC_AT:] for w in held] == [b[ref.CRC_AT:] for _o, b in fetched]

    by_id = {s[4]: s for s in spans}
    recompress = [s for s in spans if s[0] == "produce.recompress"]
    # the device path also says which program it dispatched: the one row
    # that holds the batch, at the width bucket of 5 records of 1 KB
    shape = {"rows": 1, "n": 8192} if path == "device" else {}
    said = [
        {"path": path, "codec": int(CompressionType.lz4),
         "bytes_in": len(t.wire) - ref.RECORDS_AT,
         "bytes_out": len(stored) - ref.RECORDS_AT, **shape}
        for t, (_base, stored) in zip(rewritten, fetched)
    ]
    # one a batch the broker rewrote (a produce that was retried on a
    # lost leadership is rewritten again), none for the plain topic
    assert len(recompress) >= len(rewritten)
    assert all(s[7] in said for s in recompress), [s[7] for s in recompress]
    assert all(tags in [s[7] for s in recompress] for tags in said)
    for s in recompress:
        assert s[1] == "run" and by_id[s[5]][0] == "produce.dispatch"
