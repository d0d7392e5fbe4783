#!/usr/bin/env python3
"""chip_smoke.py — prove the broker's device path runs on the attached TPU.

    python chip_smoke.py                      # on the chip, every leg
    python chip_smoke.py --legs lanes,bytes   # a subset
    python chip_smoke.py --cpu-dry-run        # same control flow, toy sizes

This is a smoke, not a benchmark: it answers "does the device path
start, run and give the right answers on this chip", once, through the
entry points a user calls. Every wall time it prints is a smoke time
(one reading, compile included where it says so), never a result.

The parent imports nothing from JAX. Each leg runs as its own child
process, one after another, so exactly one process holds the chip at a
time and all of them share one compile cache (JAX_COMPILATION_CACHE_DIR
when set, else the in-checkout .jax_cache placed by redpanda_tpu's
package init). A leg that finds no TPU fails and names what it found;
nothing here ever falls back to the CPU. `--cpu-dry-run` is the one
explicit exception, for debugging the script in a sandbox without a
chip: its summary says `"dry_run": true`.

Legs (BASELINE.md benchmark configs #1-#4; shapes are the source's,
only scale is cut, with the factor under `reduced`):

  lanes       config #4: 50,000 groups x 8 slots in ShardGroupArrays,
              a seeded reply schedule through frame_tick on the device
              backend and on host_tick; byte-identical lanes, identical
              advanced-row sets, quorum_scalar on a row sample
              (tools/tick_frame_smoke.run_parity).
  bytes       config #2: every byte kernel at its largest bucket, 256
              rows x 64 KiB of seeded record-batch-like bodies, against
              native CRC-32C and liblz4 / libsnappy / zstandard.
  standalone  config #1: `python -m redpanda_tpu --shards 1` with the
              device switches on; a Kafka client produces 1 KB records
              at acks=all, fetches them back, compares, reads the
              broker's device from GET /v1/devplane, SIGTERMs it and
              requires exit code 0.
  cluster     config #3 in the one layout a single chip allows: three
              brokers in ONE process over LoopbackNetwork with real
              Kafka TCP listeners, 1 topic x 1,024 partitions x RF=3,
              acks=all, 64 x 1 KB records per batch, plus a small
              compression.type=lz4 topic for the fused CRC+LZ4 path;
              everything fetched back with verify-on-read and compared
              with the produced ledger, and all three replicas agree.
  mesh        (more than one device visible) the lanes leg under
              RP_QUORUM_BACKEND=mesh RP_MESH_FULL=1.

Exit codes: 0 every leg ok; 1 a leg failed; 4 not a checkout of the
repo; 5 JAX found no TPU (nothing is printed on stdout for 4 and 5).
Stdout carries two JSON lines. The summary (versions, cache directory,
per-leg verdicts, counts and compile seconds, `reduced`, `"claim": null`)
comes first; the last line is the verdict alone, exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`,
with the device as the legs' JAX reported it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

EXIT_LEG_FAILED = 1
EXIT_NOT_A_CHECKOUT = 4
EXIT_NO_ACCELERATOR = 5

#: leg -> its own time limit, in run order: `lanes` first — it reaches
#: the platform check within seconds, so a machine without a chip is
#: refused before any broker boots
LEG_TIMEOUT_S = {"lanes": 300, "bytes": 600, "standalone": 300,
                 "cluster": 600, "mesh": 300}
LEG_ORDER = tuple(LEG_TIMEOUT_S)
#: the whole run must end inside the driver's 1200 s: a leg never
#: gets more than what is left of this
TOTAL_BUDGET_S = 1150

#: the device switches of the served path (devplane._DEVICE_SWITCHES)
DEVICE_ENV = {
    "RP_QUORUM_BACKEND": "device",
    "RP_CRC_BACKEND": "device",
    "RP_CODEC_BACKEND": "device",
    "RP_FETCH_VERIFY": "1",
}
#: every dispatch samples, so a kernel histogram's count IS its
#: dispatch count
DEVPLANE_ENV = {"RP_DEVPLANE": "1", "RP_DEVPLANE_SAMPLE": "1"}

RECORD_BYTES = 1024  # BASELINE.md: 1 KB records
BATCH_RECORDS = 64   # 64 x 1 KB per produce batch (BASELINE.md config #3)
LZ4_BATCH_RECORDS = 30  # body < 32 KiB: inside the fused kernel's bound
KAFKA_BATCH_HEADER = 61  # bytes of a v2 record batch before its records

#: full sizes (the source's own) and the --cpu-dry-run toys
FULL = {
    "groups": 50_000,           # many_partitions_test.py:36-40
    "rows": 256,                # ops/shapes.row_bucket ceiling in use
    "body": 65536,              # 64 KiB: the codec kernels' chunk bound
    "partitions": 1024,         # config #3; 1,000 per shard upstream
    "lz4_partitions": 8,
    "sustain_s": 5.0,
    "standalone_partitions": 4,
    "standalone_batches": 64,   # 64 x 64 x 1 KB = 4 MiB per partition
}
TOY = {
    "groups": 2_000,
    "rows": 8,
    "body": 1024,
    "partitions": 12,
    "lz4_partitions": 2,
    "sustain_s": 1.0,
    "standalone_partitions": 2,
    "standalone_batches": 4,
}


def _log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


# =====================================================================
# leg children — everything below this line runs in a child process
# =====================================================================
class _CompileLog:
    """Totals of what JAX compiled in this process, read off
    jax.monitoring: backend compile seconds (a persistent-cache hit
    still reports here, as its retrieval time) and the persistent
    cache's own hit/miss events."""

    def __init__(self) -> None:
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.saved_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_secs(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
        elif name == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved_s += secs

    def summary(self) -> dict:
        return {
            "programs": self.compiles,
            "compile_s": round(self.compile_s, 3),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_saved_s": round(self.saved_s, 3),
        }


def _require_platform(dry_run: bool) -> dict:
    """First thing a leg does with JAX: the default backend must be the
    TPU (the CPU, and only the CPU, under --cpu-dry-run). Exits
    EXIT_NO_ACCELERATOR naming what was found otherwise."""
    import jax

    want = "cpu" if dry_run else "tpu"
    try:
        found = jax.default_backend()
        devs = jax.devices()
    except RuntimeError as e:  # JAX_PLATFORMS names a backend that won't start
        _log(f"no accelerator: JAX could not start a backend: {e}")
        sys.exit(EXIT_NO_ACCELERATOR)
    if found != want:
        _log(
            f"no accelerator: JAX's default backend is {found!r} "
            f"(devices {devs}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}), this smoke needs "
            f"{want!r} — refusing to run the device path on it"
        )
        sys.exit(EXIT_NO_ACCELERATOR)
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def _require_native() -> None:
    from redpanda_tpu.utils import native

    if native.load() is None:
        raise AssertionError(
            "native library did not load: this smoke never runs on the "
            "pure-Python degradation (native/ is rebuilt by the parent)"
        )


def _devplane_facts(dry_run: bool) -> dict:
    """After a leg's kernels ran: where devplane saw their results
    live (read off the returned arrays), plus its counters."""
    from redpanda_tpu.observability import devplane

    st = devplane.status()
    dev = st.get("device")
    want = "cpu" if dry_run else "tpu"
    assert dev is not None, "no instrumented kernel ran in this leg"
    assert dev["platform"] == want, (
        f"kernel results live on platform {dev['platform']!r}, not {want!r}"
    )
    return _digest(st)


def _digest(st: dict) -> dict:
    """The part of a /v1/devplane status the summary keeps."""
    return {
        "device": st.get("device"),
        "dispatches": {k: v["count"] for k, v in st["kernels"].items()},
        "kernel_p50_ms": {
            k: round(v["p50_ms"], 3) for k, v in st["kernels"].items()
        },
        "frames": st["frames"],
        "folds": st["folds"],
        "transfer_bytes": st["transfer_bytes"],
        "tick_violations": st["tick_violations"],
        "compile_s_by_kernel": {
            k: round(v["seconds"], 3) for k, v in st["compiles"].items()
        },
        "programs_by_kernel": {
            k: int(v["warmup"] + v["steady"])
            for k, v in st["compiles"].items()
        },
    }


def _require_served_kernels(dispatches: dict) -> None:
    """A broker leg drove the device path only if the tick frame, the
    verify-on-read CRC and the fused produce codec all dispatched."""
    for k in ("quorum.heartbeat_tick", "crc32c.device", "fused.crc_lz4"):
        assert dispatches.get(k, 0) > 0, (
            f"kernel {k} never dispatched: {dispatches}"
        )


# ---------------------------------------------------------------- lanes
def leg_lanes(sz: dict, args, backend: str = "device") -> tuple[dict, object]:
    """BASELINE config #4 through the live class: tools/
    tick_frame_smoke.run_parity replays one seeded schedule under
    host_tick and under `backend`'s frame_tick. Returns the leg's
    result and the `backend` run's ShardGroupArrays."""
    from tools import tick_frame_smoke as tfs

    n = sz["groups"]
    t0 = time.perf_counter()
    got = tfs.run_parity(n, args.seed, backend)
    wall = time.perf_counter() - t0
    fold = got["fold_s"]
    out = {
        "groups": n,
        "slots": got["arrays"].replica_slots,
        "folds": got["folds"],
        "advanced_rows": got["advanced"],
        "parity": "byte-identical " + ", ".join(tfs.PARITY_LANES)
        + " and advanced-row sets vs host_tick; quorum_scalar clean "
        "on 2000 sampled rows",
        "smoke_first_fold_s": round(fold[0], 3),
        "smoke_warm_fold_ms": round(min(fold[2:]) * 1e3, 3),
        "smoke_host_warm_fold_ms": round(min(got["host_fold_s"][2:]) * 1e3, 3),
        "smoke_wall_s": round(wall, 2),
    }
    if n < FULL["groups"]:
        out["reduced"] = [f"groups {FULL['groups']} -> {n}"]
    return out, got["arrays"]


def leg_mesh(sz: dict, args) -> dict:
    """The lanes leg on the mesh backend, every frame through the real
    sharded program: all visible chips in the mesh, lanes attributed to
    every chip, one cross-chip fold per frame."""
    import jax

    from redpanda_tpu.observability import devplane

    n_dev = len(jax.devices())
    assert n_dev > 1, f"mesh leg needs more than one device, found {n_dev}"
    out, arrays = leg_lanes(sz, args, backend="mesh")
    assert arrays.chip_count() == n_dev, (
        f"mesh spans {arrays.chip_count()} chips, {n_dev} visible"
    )
    attribution = arrays.lane_attribution()
    for a in attribution:
        assert a["groups"] > 0 and a["changed_rows"] > 0, (
            f"chip {a['chip']} has no lanes attributed: {a}"
        )
    st = devplane.status()
    assert st["frames_total"] > 0, "no mesh frame ran"
    assert st["folds"] == st["frames_total"], (
        f"{st['folds']} cross-chip folds for {st['frames_total']} frames"
    )
    out["chips"] = n_dev
    out["lane_attribution"] = attribution
    out["mesh_totals"] = arrays.mesh_totals()
    return out


# ---------------------------------------------------------------- bytes
_TEXT = (
    b'{"user_id":184467,"event":"page_view","ts":1700000000123,'
    b'"path":"/products/widgets/blue","ref":"https://example.com/search",'
    b'"ua":"Mozilla/5.0 (X11; Linux x86_64)","ok":true,"ms":12.5},'
)


def _record_like_bodies(seed: int, rows: int, size: int):
    """`rows` bodies of `size` bytes shaped like a produce batch of
    1 KB records: per record a 16-byte key, then a value that is half
    JSON-ish text (compressible) and half random bytes."""
    import numpy as np

    rng = np.random.default_rng(seed)
    out = rng.integers(0, 256, (rows, size), dtype=np.uint8)
    pool = np.frombuffer(_TEXT * 64, np.uint8)
    slots = max(1, size // RECORD_BYTES)
    half = min(RECORD_BYTES, size) // 2
    offs = rng.integers(0, len(pool) - half, (rows, slots))
    text = pool[offs[:, :, None] + np.arange(half)]  # [rows, slots, half]
    for j in range(slots):
        lo = j * RECORD_BYTES
        out[:, lo : lo + 16] = np.frombuffer(b"k%015d" % j, np.uint8)
        out[:, lo + 16 : lo + 16 + half] = text[:, j]
    return out


def leg_bytes(sz: dict, args, compiles: "_CompileLog") -> dict:
    """BASELINE config #2: each byte kernel once at (rows x body),
    results held to the host's own implementations."""
    import jax
    import numpy as np
    import zstandard

    from redpanda_tpu.compression import (
        lz4_codec, snappy_codec, tpu_backend, zstd_frame,
    )
    from redpanda_tpu.ops import fused, lz4, snappy
    from redpanda_tpu.ops.crc32c import crc32c_batch_device
    from redpanda_tpu.utils import crc as host_crc

    rows, size = sz["rows"], sz["body"]
    mat = _record_like_bodies(args.seed, rows, size)
    bodies = [mat[i].tobytes() for i in range(rows)]
    rng = np.random.default_rng(args.seed + 1)
    prefix_mat = rng.integers(0, 256, (rows, fused.PREFIX), dtype=np.uint8)
    prefixes = [prefix_mat[i].tobytes() for i in range(rows)]
    # the layout models.record.batch_crcs stages: crc_prefix || body
    crc_in = np.concatenate([prefix_mat, mat], axis=1)
    crc_lens = np.full(rows, crc_in.shape[1], np.uint64)
    want_crc = host_crc.crc32c_batch(crc_in, crc_lens)
    unzstd = zstandard.ZstdDecompressor()
    kernels: dict[str, dict] = {}

    def timed(name: str, fn, check) -> None:
        # one call each: on a v5e the codec kernels run for tens of
        # seconds at this bucket (PERF.md), so there is no second,
        # warm reading — the call's compile share is split out instead
        before = compiles.compile_s
        t0 = time.perf_counter()
        got = fn()
        wall = time.perf_counter() - t0
        check(got)
        compile_s = compiles.compile_s - before
        kernels[name] = {
            "smoke_compile_s": round(compile_s, 3),
            "smoke_run_s": round(wall - compile_s, 3),
        }
        _log(f"bytes: {name} ok {kernels[name]}")

    def check_crc(got) -> None:
        assert np.array_equal(np.asarray(got, np.uint32), want_crc), (
            "device CRC-32C != native rp_crc32c_batch"
        )

    def check_lz4(blocks) -> None:
        for b, blk in zip(bodies, blocks):
            assert lz4_codec.decompress_block(blk, len(b)) == b, (
                "liblz4 did not decode the device block to the original"
            )

    def check_snappy(blocks) -> None:
        for b, blk in zip(bodies, blocks):
            assert snappy_codec.decompress_raw(blk) == b, (
                "libsnappy did not decode the device block to the original"
            )

    def check_zstd(frames) -> None:
        for b, f in zip(bodies, frames):
            assert unzstd.decompress(f, max_output_size=len(b)) == b, (
                "zstandard did not decode the device frame to the original"
            )

    def fused_check(codec_check):
        def check(got) -> None:
            crcs, blocks = got
            check_crc(crcs)
            codec_check(blocks)

        return check

    timed("crc32c", lambda: crc32c_batch_device(crc_in, crc_lens), check_crc)
    timed("lz4", lambda: lz4.compress_chunks(bodies), check_lz4)
    timed("snappy", lambda: snappy.compress_chunks(bodies), check_snappy)
    timed(
        "zstd_encode",
        lambda: tpu_backend.compress_many_zstd(bodies),
        check_zstd,
    )
    # decode: the kernel's rows are huff0 streams, four to a block, so
    # one frame holding rows/4 bodies as blocks decodes `rows` streams
    # in ONE batched program; _decompress_device raises rather than
    # punting to the host codec
    whole = b"".join(bodies[: max(1, rows // 4)])
    frame = tpu_backend.compress_many_zstd([whole])[0]
    assert unzstd.decompress(frame, max_output_size=len(whole)) == whole

    def check_decode(got) -> None:
        assert got == whole, "device zstd decode != original bytes"

    try:
        timed(
            "zstd_decode",
            lambda: tpu_backend._decompress_device(frame),
            check_decode,
        )
    except zstd_frame.ZstdFormatError as e:
        raise AssertionError(f"device zstd decode punted to the host: {e}")
    timed(
        "fused_crc_lz4",
        lambda: fused.crc_lz4_fused(prefixes, bodies),
        fused_check(check_lz4),
    )
    timed(
        "fused_crc_snappy",
        lambda: fused.crc_snappy_fused(prefixes, bodies),
        fused_check(check_snappy),
    )
    timed(
        "fused_crc_zstd",
        lambda: fused.crc_zstd_fused(prefixes, bodies),
        fused_check(check_zstd),
    )
    stats = jax.devices()[0].memory_stats() or {}
    out = {
        "rows": rows,
        "body_bytes": size,
        "kernels": kernels,
        "parity": "CRC == native rp_crc32c_batch; liblz4 / libsnappy / "
        "zstandard decode every device output to the original bytes; "
        "device zstd decode == original",
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }
    if rows < FULL["rows"] or size < FULL["body"]:
        out["reduced"] = [
            f"rows {FULL['rows']} -> {rows}", f"body {FULL['body']} -> {size}"
        ]
    return out


# ------------------------------------------------- produce/fetch ledger
class _Templates:
    """A few seeded produce batches, encoded once (real producers
    encode on their own machine), with the records each must read back
    as."""

    def __init__(self, seed: int, n: int, records: int, text: bool) -> None:
        import numpy as np

        from redpanda_tpu.models.record import RecordBatchBuilder

        rng = np.random.default_rng(seed)
        self.records: list[list[tuple[bytes, bytes]]] = []
        self.wire: list[bytes] = []
        vlen = RECORD_BYTES - 16
        for t in range(n):
            if text:  # compressible values (the lz4 topic)
                body = _record_like_bodies(seed + t, records, RECORD_BYTES)
                values = [body[i, 16:].tobytes() for i in range(records)]
            else:
                raw = rng.integers(0, 256, (records, vlen), dtype=np.uint8)
                values = [raw[i].tobytes() for i in range(records)]
            recs = [
                (b"k%03d.%011d" % (t, i), values[i]) for i in range(records)
            ]
            b = RecordBatchBuilder()
            for k, v in recs:
                b.add(v, key=k)
            self.records.append(recs)
            self.wire.append(b.build().to_kafka_wire())


class _Ledger:
    """What was acknowledged: per (topic, partition), base offset ->
    template index, in ack order."""

    def __init__(self) -> None:
        self.acked: dict[tuple[str, int], dict[int, int]] = {}
        self.unacked = 0

    def ack(self, topic: str, p: int, base: int, t: int) -> None:
        self.acked.setdefault((topic, p), {})[base] = t

    def records_acked(self, tpl: dict) -> int:
        return sum(
            len(tpl[topic].records[t])
            for (topic, _p), m in self.acked.items()
            for t in m.values()
        )


async def _fetch_and_compare(client, ledger: _Ledger, tpl: dict) -> int:
    """Fetch every partition from offset 0 to its end and hold it to
    the ledger: every acknowledged batch is there, at its offset, with
    identical keys and values, and nothing else is (beyond batches
    whose produce call failed without an answer). Returns records
    read back."""
    read = 0
    extra = 0
    for (topic, p), acked in sorted(ledger.acked.items()):
        t = tpl[topic]
        end = await _end_offset(client, topic, p)
        rows = []
        off = 0
        while off < end:
            # at most seven full batches a response: one row bucket,
            # so one verify-on-read CRC program
            got = await client.fetch(topic, p, off, max_bytes=512 << 10)
            assert got, f"{topic}/{p}: empty fetch at {off} below end {end}"
            rows.extend(got)
            off = rows[-1][0] + 1
        assert [r[0] for r in rows] == list(range(end)), (
            f"{topic}/{p}: fetched offsets are not 0..{end - 1}"
        )
        pos = 0
        while pos < end:
            ti = acked.get(pos)
            if ti is None:
                # an unanswered produce may still have landed: it must
                # be one of our batches, whole
                ti = next(
                    (
                        i for i, recs in enumerate(t.records)
                        if rows[pos][1] == recs[0][0]
                    ),
                    None,
                )
                assert ti is not None, f"{topic}/{p}: foreign data at {pos}"
                extra += 1
            want = t.records[ti]
            got = [(k, v) for _o, k, v in rows[pos : pos + len(want)]]
            assert got == want, (
                f"{topic}/{p}: records at {pos} differ from what was acked"
            )
            pos += len(want)
        read += end
    assert extra <= ledger.unacked, (
        f"{extra} unacknowledged batches in the logs, {ledger.unacked} "
        "produce calls went unanswered"
    )
    return read


async def _end_offset(client, topic: str, p: int) -> int:
    """The partition's end offset from its current leader (the
    client's list_offset asks whoever it last saw leading)."""

    async def ask():
        try:
            return await client.list_offset(topic, p, -1)
        except Exception:
            await client.metadata([topic])  # leadership moved: re-learn
            raise

    return await _retry(ask, time.monotonic() + 30, f"list_offset {topic}/{p}")


async def _retry(fn, deadline: float, what: str):
    while True:
        try:
            return await fn()
        except Exception as e:
            if time.monotonic() > deadline:
                raise AssertionError(f"{what}: still failing: {e!r}") from e
            await asyncio.sleep(0.25)


# ----------------------------------------------------------- standalone
def _free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _http_json(port: int, path: str) -> dict:
    import urllib.request

    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return json.loads(r.read())


async def _standalone_client(sz: dict, args, kafka_port: int) -> dict:
    from redpanda_tpu.kafka.client import KafkaClient

    tpl = {
        "smoke": _Templates(args.seed, 4, BATCH_RECORDS, text=False),
        "smoke-lz4": _Templates(
            args.seed + 50, 2, LZ4_BATCH_RECORDS, text=True
        ),
    }
    ledger = _Ledger()
    client = KafkaClient([("127.0.0.1", kafka_port)])
    try:
        deadline = time.monotonic() + 60
        parts = sz["standalone_partitions"]
        await _retry(
            lambda: client.create_topic("smoke", partitions=parts),
            deadline, "create_topic smoke",
        )
        await _retry(
            lambda: client.create_topic(
                "smoke-lz4", partitions=1,
                configs={"compression.type": "lz4"},
            ),
            deadline, "create_topic smoke-lz4",
        )
        for topic, n_parts, n_batches in (
            ("smoke", parts, sz["standalone_batches"]),
            ("smoke-lz4", 1, 4),
        ):
            t = tpl[topic]
            for p in range(n_parts):
                for i in range(n_batches):
                    ti = (p + i) % len(t.wire)

                    def send():
                        return client.produce_wire(
                            topic, p, t.wire[ti], acks=-1
                        )

                    if i == 0:  # a fresh partition may still be electing
                        base = await _retry(
                            send, time.monotonic() + 30,
                            f"first produce {topic}/{p}",
                        )
                    else:
                        base = await send()
                    ledger.ack(topic, p, base, ti)
        read = await _fetch_and_compare(client, ledger, tpl)
    finally:
        await client.close()
    acked = ledger.records_acked(tpl)
    assert read == acked, f"acked {acked} records, read back {read}"
    return {"records_acked": acked, "records_read_back": read}


def leg_standalone(sz: dict, args) -> dict:
    """BASELINE config #1 through `python -m redpanda_tpu`. This leg's
    process never starts a JAX backend: the broker child is the one
    process on the chip, and what it runs on is read from outside."""
    _require_native()  # the broker child loads the same library
    data_dir = os.path.join(args.data_dir, "standalone")
    kafka_port, rpc_port, admin_port = _free_ports(3)
    log_path = os.path.join(args.out_dir, "standalone_broker.log")
    cmd = [
        sys.executable, "-m", "redpanda_tpu", "--node-id", "0",
        "--data-dir", data_dir, "--shards", "1",
        "--kafka-host", "127.0.0.1", "--kafka-port", str(kafka_port),
        "--rpc-port", str(rpc_port), "--admin-port", str(admin_port),
    ]
    t0 = time.perf_counter()
    with open(log_path, "wb") as log:
        broker = subprocess.Popen(
            cmd, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            env={**os.environ, **DEVICE_ENV},
        )
    try:
        # serving once the kafka port accepts (prewarm compiles first)
        deadline = time.monotonic() + 240
        while True:
            if broker.poll() is not None:
                raise AssertionError(
                    f"broker exited {broker.returncode} before serving: "
                    + _tail(log_path)
                )
            try:
                socket.create_connection(
                    ("127.0.0.1", kafka_port), timeout=1
                ).close()
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise AssertionError(
                        "broker not serving after 240 s: " + _tail(log_path)
                    )
                time.sleep(0.25)
        start_s = time.perf_counter() - t0
        counts = asyncio.run(_standalone_client(sz, args, kafka_port))
        st = _http_json(admin_port, "/v1/devplane")
        want = "cpu" if args.cpu_dry_run else "tpu"
        dev = st.get("device")
        assert st.get("enabled") and dev, f"/v1/devplane reports {st}"
        assert dev["platform"] == want, (
            f"the broker's device plane runs on {dev['platform']!r}, "
            f"not {want!r}"
        )
        facts = _digest(st)
        _require_served_kernels(facts["dispatches"])
        broker.send_signal(signal.SIGTERM)
        rc = broker.wait(timeout=60)
        assert rc == 0, f"broker exited {rc} on SIGTERM: " + _tail(log_path)
    finally:
        if broker.poll() is None:
            broker.kill()
            broker.wait()
        shutil.rmtree(data_dir, ignore_errors=True)
    out = {
        **counts,
        # the broker's own account (instrumented kernels only; whether
        # a program came from the persistent cache is not visible from
        # outside, only that its "compile" took no time)
        "compile": {
            "programs": sum(facts["programs_by_kernel"].values()),
            "compile_s": round(sum(facts["compile_s_by_kernel"].values()), 3),
        },
        "topics": "smoke: %d partitions RF=1; smoke-lz4: 1 partition, "
        "compression.type=lz4" % sz["standalone_partitions"],
        "acks": "all",
        "record_bytes": RECORD_BYTES,
        "batch_records": BATCH_RECORDS,
        "exit_code_on_sigterm": 0,
        "smoke_start_to_serving_s": round(start_s, 2),
        "broker": facts,
        "broker_log": log_path,
    }
    if sz["standalone_batches"] < FULL["standalone_batches"]:
        out["reduced"] = [
            f"standalone batches per partition "
            f"{FULL['standalone_batches']} -> {sz['standalone_batches']}"
        ]
    return out


def _tail(path: str, n: int = 2000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")
    except OSError as e:
        return f"<{e}>"


# -------------------------------------------------------------- cluster
def _elections(brokers) -> int:
    """Leadership terms won so far across the cluster (a group's first
    leader is term 1; every later election adds one)."""
    import numpy as np

    total = 0
    for b in brokers:
        arrays = b.group_manager.arrays
        live = arrays.row_active & arrays.is_leader
        total += int(np.sum(arrays.term[live]))
    return total


class _Laps:
    """Sequential phase timer: lap(name) files the seconds since the
    previous lap as smoke_<name>_s."""

    def __init__(self) -> None:
        self.s: dict[str, float] = {}
        self._t = time.perf_counter()

    def lap(self, name: str) -> None:
        now = time.perf_counter()
        self.s[f"smoke_{name}_s"] = round(now - self._t, 2)
        self._t = now


def _warm_cluster(brokers, tpl: dict, n_groups: int) -> int:
    """Size the lane space once and compile what traffic will dispatch,
    while nothing is ticking yet: a cold compile runs on the event loop
    all three brokers share, and seconds without heartbeats are an
    election storm. Every broker holds a replica of every partition, so
    each reserves the final capacity (no doubling mid-produce); then the
    tick program at every reply bucket a window can fall in (a broker
    leading every group gets two replies per group per heartbeat), the
    verify-on-read CRC at a full batch's stride, and the fused CRC+LZ4
    at the lz4 topic's body bucket. The brokers share this process's
    compiled programs. Returns the lane capacity."""
    import numpy as np

    from redpanda_tpu.ops.crc32c import crc32c_batch_device
    from redpanda_tpu.ops.fused import PREFIX, crc_lz4_fused

    cap = 64
    while cap < n_groups + 16:
        cap *= 2
    for b in brokers:
        b.group_manager.arrays.reserve(cap)
    brokers[0].group_manager.arrays.prewarm(max_replies=2 * n_groups)
    full = max(len(w) for w in tpl["repl"].wire)
    crc32c_batch_device(
        np.zeros((1, full), np.uint8), np.array([full], np.int64)
    )
    lz4_body = len(tpl["repl-lz4"].wire[0]) - KAFKA_BATCH_HEADER
    crc_lz4_fused([bytes(PREFIX)], [bytes(lz4_body)])
    return cap


async def _check_replicas(brokers, client, ledger: _Ledger) -> None:
    """All three replicas of every partition: same high watermark as
    the leader's end offset, same batch CRCs in the same order
    (followers learn the commit index with the next heartbeat, so they
    get a few intervals); the lz4 topic's batches are stored as lz4."""
    from redpanda_tpu.compression import CompressionType
    from redpanda_tpu.models.fundamental import kafka_ntp

    for topic, p in sorted(ledger.acked):
        end = await _end_offset(client, topic, p)
        ntp = kafka_ntp(topic, p)
        parts = [b.partition_manager.get(ntp) for b in brokers]
        assert all(x is not None for x in parts), (
            f"{topic}/{p}: a broker holds no replica"
        )
        wait_until = time.monotonic() + 30
        while any(x.high_watermark() != end for x in parts):
            assert time.monotonic() < wait_until, (
                f"{topic}/{p}: replica high watermarks "
                f"{[x.high_watermark() for x in parts]} != {end}"
            )
            await asyncio.sleep(0.05)
        stored = [
            [b.header for _o, b in x.read_kafka(0, 1 << 30)] for x in parts
        ]
        crcs = [[h.crc for h in hs] for hs in stored]
        assert crcs[0] == crcs[1] == crcs[2], (
            f"{topic}/{p}: replicas hold different batches"
        )
        if topic == "repl-lz4":
            assert all(
                h.compression == CompressionType.lz4 for h in stored[0]
            ), f"{topic}/{p}: stored batches are not lz4"


async def _cluster_async(sz: dict, args) -> dict:
    from redpanda_tpu.app import Broker, BrokerConfig
    from redpanda_tpu.kafka.client import KafkaClient
    from redpanda_tpu.observability import devplane
    from redpanda_tpu.rpc.loopback import LoopbackNetwork

    n_parts, n_lz4 = sz["partitions"], sz["lz4_partitions"]
    topics = {"repl": n_parts, "repl-lz4": n_lz4}
    tpl = {
        "repl": _Templates(args.seed, 8, BATCH_RECORDS, text=False),
        "repl-lz4": _Templates(
            args.seed + 50, 4, LZ4_BATCH_RECORDS, text=True
        ),
    }
    data_dir = os.path.join(args.data_dir, "cluster")
    net = LoopbackNetwork()
    members = [0, 1, 2]
    brokers = [
        Broker(
            BrokerConfig(
                node_id=i,
                data_dir=os.path.join(data_dir, f"n{i}"),
                members=members,
                enable_admin=False,
            ),
            loopback=net,
        )
        for i in members
    ]
    ledger = _Ledger()
    clients: list = []
    laps = _Laps()
    try:
        cap = _warm_cluster(brokers, tpl, n_parts + n_lz4)
        laps.lap("warm")
        for b in brokers:
            await b.start()
        addrs = {b.node_id: b.kafka_advertised for b in brokers}
        for b in brokers:
            b.config.peer_kafka_addresses = addrs
        await brokers[0].wait_controller_leader()
        bootstrap = [b.kafka_advertised for b in brokers]
        admin = KafkaClient(bootstrap)
        clients.append(admin)
        deadline = time.monotonic() + 120
        await _retry(
            lambda: admin.create_topic(
                "repl", partitions=n_parts, replication_factor=3,
                timeout_ms=60000,
            ),
            deadline, "create_topic repl",
        )
        await _retry(
            lambda: admin.create_topic(
                "repl-lz4", partitions=n_lz4, replication_factor=3,
                configs={"compression.type": "lz4"},
            ),
            deadline, "create_topic repl-lz4",
        )
        laps.lap("boot_and_create")

        # phase 1: one acknowledged batch on every partition
        n_prod = 8
        producers = [
            KafkaClient(bootstrap, serial_reads=True) for _ in range(n_prod)
        ]
        clients.extend(producers)
        work = [(t, p) for t, n in topics.items() for p in range(n)]

        async def first(idx: int) -> None:
            c = producers[idx]
            for topic, p in work[idx::n_prod]:
                ti = p % len(tpl[topic].wire)
                base = await _retry(
                    lambda: c.produce_wire(
                        topic, p, tpl[topic].wire[ti], acks=-1
                    ),
                    time.monotonic() + 180, f"first produce {topic}/{p}",
                )
                ledger.ack(topic, p, base, ti)

        await asyncio.gather(*(first(i) for i in range(n_prod)))
        laps.lap("first_ack_everywhere")
        elections_before = _elections(brokers)

        # phase 2: sustained acks=all produce across all of them
        stop_at = time.monotonic() + sz["sustain_s"]

        async def sustain(idx: int) -> None:
            c = producers[idx]
            mine = work[idx::n_prod]
            i = 0
            while time.monotonic() < stop_at:
                topic, p = mine[i % len(mine)]
                ti = (p + 1 + i // len(mine)) % len(tpl[topic].wire)
                i += 1
                try:
                    base = await c.produce_wire(
                        topic, p, tpl[topic].wire[ti], acks=-1
                    )
                except Exception as e:  # unanswered: may have landed
                    ledger.unacked += 1
                    _log(f"cluster: produce {topic}/{p} unanswered: {e!r}")
                    continue
                ledger.ack(topic, p, base, ti)

        await asyncio.gather(*(sustain(i) for i in range(n_prod)))
        laps.lap("sustained")
        elections_during = _elections(brokers) - elections_before
        acked = ledger.records_acked(tpl)

        # read everything back from the leaders, verify-on-read armed
        read = await _fetch_and_compare(admin, ledger, tpl)
        laps.lap("fetch_back")
        await _check_replicas(brokers, admin, ledger)
        laps.lap("replica_check")
    finally:
        for c in clients:
            await c.close()
        for b in brokers:
            try:
                await b.stop()
            except Exception as e:
                _log(f"cluster: broker {b.node_id} stop: {e!r}")
        shutil.rmtree(data_dir, ignore_errors=True)

    assert read == acked, f"acked {acked} records, read back {read}"
    _require_served_kernels(_digest(devplane.status())["dispatches"])
    out = {
        "brokers": 3,
        "layout": "one process, LoopbackNetwork RPC, real Kafka TCP",
        "topics": f"repl: {n_parts} partitions RF=3; repl-lz4: {n_lz4} "
        "partitions RF=3 compression.type=lz4",
        "acks": "all",
        "record_bytes": RECORD_BYTES,
        "batch_records": BATCH_RECORDS,
        "lane_capacity": cap,
        "records_acked": acked,
        "records_read_back": read,
        "produce_unanswered": ledger.unacked,
        "parity": "every acked batch read back byte-identical from the "
        "leaders with verify-on-read; 3 replicas agree on high "
        "watermark and batch CRCs on every partition",
        "elections_until_first_ack": elections_before,
        "elections_during_sustained": elections_during,
        **laps.s,
    }
    if n_parts < FULL["partitions"]:
        out["reduced"] = [f"partitions {FULL['partitions']} -> {n_parts}"]
    return out


def leg_cluster(sz: dict, args) -> dict:
    return asyncio.run(_cluster_async(sz, args))


# ----------------------------------------------------------- child main
def _child_main(args) -> int:
    """Run one leg in this process and write its result file."""
    sys.path.insert(0, REPO)
    sz = TOY if args.cpu_dry_run else FULL
    name = args.leg
    result: dict = {"ok": False}
    compiles = None
    try:
        if name == "standalone":
            # stays off JAX: the broker child is the process on the chip
            result.update(leg_standalone(sz, args))
        else:
            compiles = _CompileLog()
            result["device"] = _require_platform(args.cpu_dry_run)
            _require_native()
            if name == "lanes":
                result.update(leg_lanes(sz, args)[0])
            elif name == "mesh":
                result.update(leg_mesh(sz, args))
            elif name == "bytes":
                result.update(leg_bytes(sz, args, compiles))
            elif name == "cluster":
                result.update(leg_cluster(sz, args))
            else:
                raise AssertionError(f"no such leg {name!r}")
            result["devplane"] = _devplane_facts(args.cpu_dry_run)
        result["ok"] = True
    except SystemExit:
        raise
    except BaseException as e:
        import traceback

        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"[:2000]
    if compiles is not None:
        import jax

        result["compile"] = compiles.summary()
        result["cache_dir"] = jax.config.jax_compilation_cache_dir
    with open(args.result_file, "w") as f:
        json.dump(result, f)
    return 0 if result["ok"] else EXIT_LEG_FAILED


# =====================================================================
# parent — imports nothing from JAX
# =====================================================================
def _fs_type(path: str) -> str:
    """Filesystem type of the mount holding `path` (/proc/mounts)."""
    best, fstype = "", "unknown"
    real = os.path.realpath(path)
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mnt, typ = line.split()[:3]
                if (real == mnt or real.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best):
                    best, fstype = mnt, typ
    except OSError:
        pass
    return fstype


def _pkg_versions() -> dict:
    from importlib import metadata

    out = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def _run_leg(name: str, args, env: dict, timeout_s: float) -> tuple[int, dict]:
    result_file = os.path.join(args.out_dir, f"leg_{name}.json")
    if os.path.exists(result_file):
        os.remove(result_file)
    cmd = [
        sys.executable, os.path.abspath(__file__), "--leg", name,
        "--result-file", result_file, "--seed", str(args.seed),
        "--data-dir", args.data_dir, "--out-dir", args.out_dir,
    ]
    if args.cpu_dry_run:
        cmd.append("--cpu-dry-run")
    _log(f"leg {name}: starting")
    t0 = time.perf_counter()
    # own session: on a timeout the whole group goes, broker included
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=env, stdout=sys.stderr, start_new_session=True
    )
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        rc = -1
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    wall = round(time.perf_counter() - t0, 2)
    try:
        with open(result_file) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {"ok": False}
    if rc == -1:
        result = {**result, "ok": False,
                  "error": f"timed out after {timeout_s:.0f} s"}
    elif rc != 0 and "error" not in result:
        result["error"] = f"leg process exited {rc}"
        result["ok"] = False
    result["smoke_leg_wall_s"] = wall
    _log(f"leg {name}: {'ok' if result.get('ok') else 'FAILED'} "
         f"in {wall} s" + (f" — {result['error']}" if "error" in result else ""))
    return rc, result


def _parent_main(args) -> int:
    if not os.path.isdir(os.path.join(REPO, "redpanda_tpu")) or not \
            os.path.isfile(os.path.join(REPO, "native", "Makefile")):
        _log(f"{REPO} is not a checkout of the repo (no redpanda_tpu/, "
             "native/): nothing to run")
        return EXIT_NOT_A_CHECKOUT
    t_start = time.perf_counter()
    os.makedirs(args.out_dir, exist_ok=True)
    os.makedirs(args.data_dir, exist_ok=True)
    fstype = _fs_type(args.data_dir)
    _log(f"data directory {args.data_dir} on {fstype}")
    if fstype in ("tmpfs", "ramfs"):
        _log("data directory is memory-backed: fsync would be free and "
             "acks=all would prove nothing — pass --data-dir on a disk")
        return EXIT_LEG_FAILED

    # the native library on this path is built from the committed
    # sources, here, every time (a copied tree keeps no useful mtimes)
    t0 = time.perf_counter()
    make = subprocess.run(
        ["make", "-B", "-C", os.path.join(REPO, "native")],
        capture_output=True, text=True,
    )
    if make.returncode != 0:
        _log("native build failed:\n" + make.stdout[-2000:] + make.stderr[-2000:])
        return EXIT_LEG_FAILED
    native_s = round(time.perf_counter() - t0, 2)

    env = {**os.environ, **DEVPLANE_ENV}
    # every device switch starts off: each leg turns on what it drives
    for k in (*DEVICE_ENV, "RP_ZSTD_BACKEND", "RP_MESH_FULL",
              "RP_MESH_DEVICES", "RP_NATIVE"):
        env.pop(k, None)
    if args.cpu_dry_run:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()

    legs = [n for n in LEG_ORDER if n in args.legs]
    results: dict[str, dict] = {}
    device = None
    for name in legs:
        if name == "mesh" and device is not None and device["count"] < 2:
            results[name] = {"skipped": "one device visible"}
            continue
        leg_env = dict(env)
        if name == "cluster":
            leg_env.update(DEVICE_ENV)
        if name == "mesh":
            leg_env["RP_MESH_FULL"] = "1"
        left = TOTAL_BUDGET_S - (time.perf_counter() - t_start)
        rc, res = _run_leg(
            name, args, leg_env, max(1.0, min(LEG_TIMEOUT_S[name], left))
        )
        if rc == EXIT_NO_ACCELERATOR:
            _log("no TPU: nothing was run on another platform, no "
                 "summary is printed")
            return EXIT_NO_ACCELERATOR
        results[name] = res
        if device is None and res.get("device"):
            device = res["device"]
        if device is None and res.get("broker", {}).get("device"):
            d = res["broker"]["device"]
            device = {"platform": d["platform"], "kind": d["device_kind"],
                      "count": d["device_count"]}
    shutil.rmtree(args.data_dir, ignore_errors=True)

    ran = [r for r in results.values() if "skipped" not in r]
    ok = bool(ran) and all(r.get("ok") for r in ran) and device is not None
    compile_legs = [r["compile"] for r in ran if "compile" in r]
    summary = {
        "ok": ok,
        "device": device,
        "dry_run": bool(args.cpu_dry_run),
        "versions": _pkg_versions(),
        # as the legs' JAX reports it (None: no cache, a dry run)
        "cache_dir": next(
            (r["cache_dir"] for r in ran if "cache_dir" in r),
            os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        ),
        "compile": {
            k: round(sum(c.get(k, 0) for c in compile_legs), 3)
            for k in ("programs", "compile_s", "cache_hits",
                      "cache_misses", "cache_saved_s")
        },
        "data_dir": {"path": args.data_dir, "fstype": fstype},
        "native_rebuild_s": native_s,
        "reduced": [x for r in ran for x in r.get("reduced", [])],
        "legs": {k: _brief(v) for k, v in results.items()},
        "smoke_wall_s": round(time.perf_counter() - t_start, 1),
        "note": "smoke times: one reading each, not benchmark numbers",
        "claim": None,
    }
    full_path = os.path.join(args.out_dir, "chip_smoke.json")
    with open(full_path, "w") as f:
        json.dump({**summary, "legs": results}, f, indent=1)
    _log(f"full per-leg detail in {full_path}")
    sys.stderr.flush()
    print(json.dumps(summary), flush=True)
    if device is not None:
        # the verdict, alone on the last line: these keys and no others
        print(json.dumps({"ok": ok, "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        }}), flush=True)
    return 0 if ok else EXIT_LEG_FAILED


def _brief(res: dict) -> dict:
    """A leg's line in the summary: verdict, counts and compile totals
    (the per-kernel detail stays in chip_smoke.json)."""
    keep = (
        "ok", "skipped", "error", "compile", "smoke_leg_wall_s", "groups",
        "folds", "advanced_rows", "chips", "rows", "body_bytes",
        "peak_bytes_in_use", "records_acked", "records_read_back",
        "produce_unanswered", "elections_until_first_ack",
        "elections_during_sustained", "exit_code_on_sigterm",
    )
    out = {k: res[k] for k in keep if k in res}
    plane = res.get("devplane") or res.get("broker")
    if plane:
        out["dispatches"] = {
            k: v for k, v in plane["dispatches"].items() if v
        }
        out["frames"] = plane["frames"]
        out["transfer_bytes"] = plane["transfer_bytes"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default=",".join(LEG_ORDER),
                    help="comma-separated subset of: " + " ".join(LEG_ORDER))
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument(
        "--cpu-dry-run", action="store_true",
        help="debugging only: the same control flow on XLA:CPU at toy "
        "sizes; the summary says dry_run",
    )
    ap.add_argument(
        "--data-dir", default=os.path.join(REPO, ".smoke_data"),
        help="broker data directories (must be on a real disk)",
    )
    ap.add_argument(
        "--out-dir", default=os.path.join(REPO, "chiprun_out"),
        help="per-leg results, broker logs, chip_smoke.json",
    )
    ap.add_argument("--leg", help=argparse.SUPPRESS)  # internal: child
    ap.add_argument("--result-file", help=argparse.SUPPRESS)
    args = ap.parse_args()
    args.data_dir = os.path.abspath(args.data_dir)
    args.out_dir = os.path.abspath(args.out_dir)
    if args.leg:
        return _child_main(args)
    args.legs = [x for x in args.legs.split(",") if x]
    unknown = [x for x in args.legs if x not in LEG_ORDER]
    if unknown:
        ap.error(f"unknown legs {unknown}")
    return _parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
