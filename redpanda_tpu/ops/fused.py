"""Fused device CRC32C + LZ4 over record-batch bodies: one dispatch.

One program takes a batch's row up once and gives back both what the
broker needs of it: the Kafka CRC of prefix||body (validated against the
wire's, in place of the host's verify pass) and the body as a standard
LZ4 block. One upload, one launch, one readback: a crossing to the
device costs about half a millisecond whatever it carries, and on the
TPU v5e they are most of a call (PERF.md section 5, PR 33: of 2.3 ms
for a 40 KB batch at [1, 66048] the program is 1.0 on the device; the
host's crc and liblz4 do the same work in 0.08 ms, which is why the
host path is the default and this one a switch).

Row layout ([B, PREFIX + n + CELL] uint8, zero-padded; B is the bodies
of the call to the next power of two):

    [ crc_prefix (40 B) | records body (n bucket) | CELL guard ]

The Kafka batch CRC covers crc_prefix||body (model/record.h:398), so
the CRC reads the row head; LZ4 compresses the body slice only.
Reference: BASELINE.md north-star #1 ("CRC32c + compress"),
src/v/compression/compression.h:21 registry gating.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import devplane, trace
from ..utils import compileguard
from .crc32c import crc32c_device
from .cellparse import CELL
from .shapes import row_bucket
from .lz4 import _compress_chunks, out_bound
from .snappy import _compress_chunks as _snappy_chunks
from .snappy import _preamble as _snappy_preamble
from .snappy import out_bound as snappy_out_bound
from .zstd import _encode_one as _zstd_encode_one

PREFIX = 40  # models/record.py _CRC_PREFIX packed size


def _one_readback(crc: jax.Array, out: jax.Array, out_len: jax.Array):
    """[B, m + 8] uint8: each row's block, then its length and its CRC
    as 4 little-endian bytes each. Every crossing to the device costs
    0.4-0.5 ms whatever it carries (PERF.md section 5), so the three
    results come back as one array."""
    tail = jnp.stack([out_len.astype(jnp.uint32), crc], axis=1)  # [B, 2]
    shifts = jnp.arange(0, 32, 8, dtype=jnp.uint32)
    tail_bytes = ((tail[:, :, None] >> shifts) & 255).astype(jnp.uint8)
    return jnp.concatenate([out, tail_bytes.reshape(out.shape[0], 8)], axis=1)


@functools.partial(jax.jit, static_argnums=(2,))
def _fused(data: jax.Array, body_len: jax.Array, n: int):
    """data [B, PREFIX + n + CELL] uint8; body_len int32[B].
    Returns the lz4 blocks, their lengths and the crc over prefix||body
    of every row as `_one_readback` lays them out."""
    # CRC slice: width PREFIX+n rounded up to the 512-byte CRC chunk —
    # the matrix is allocated with that slack, zero-padded
    crc_w = ((PREFIX + n + 511) // 512) * 512
    crc = crc32c_device(
        data[:, :crc_w], (body_len + PREFIX).astype(jnp.int64)
    )
    # no optimization_barrier between the two: with the CRC taking all
    # chunks at once it decides nothing (TPU v5e, PERF.md section 5:
    # 2.900 ms with it, 2.865 without, same device time, same blocks)
    body = data[:, PREFIX : PREFIX + n + CELL]
    return _one_readback(crc, *_compress_chunks(body, body_len, n))


_fused = devplane.instrument(
    compileguard.instrument(_fused, "fused.crc_lz4"), "fused.crc_lz4"
)


@functools.partial(jax.jit, static_argnums=(2,))
def _fused_snappy(data: jax.Array, body_len: jax.Array, n: int):
    """Same layout as _fused, snappy emission instead of LZ4."""
    crc_w = ((PREFIX + n + 511) // 512) * 512
    crc = crc32c_device(
        data[:, :crc_w], (body_len + PREFIX).astype(jnp.int64)
    )
    body = data[:, PREFIX : PREFIX + n + CELL]
    return _one_readback(crc, *_snappy_chunks(body, body_len, n))


_fused_snappy = devplane.instrument(
    compileguard.instrument(_fused_snappy, "fused.crc_snappy"),
    "fused.crc_snappy",
)


@functools.partial(jax.jit, static_argnums=(2,))
def _fused_zstd(data: jax.Array, body_len: jax.Array, n: int):
    """Same layout as _fused, zstd entropy stage instead of LZ4
    (different output shape: code lengths + 4 huff0 streams per row;
    frame scaffolding is host work). Its optimization_barrier dates
    from the CRC that was a scan over chunks and was never timed since
    (no cell runs this program)."""
    crc_w = ((PREFIX + n + 511) // 512) * 512
    crc = crc32c_device(
        data[:, :crc_w], (body_len + PREFIX).astype(jnp.int64)
    )
    body = jax.lax.optimization_barrier(data[:, PREFIX : PREFIX + n])
    nbits, streams, bits = jax.vmap(
        lambda d, v: _zstd_encode_one(d, v, n)
    )(body, body_len)
    return crc, nbits, streams, bits


_fused_zstd = devplane.instrument(
    compileguard.instrument(_fused_zstd, "fused.crc_zstd"),
    "fused.crc_zstd",
)


def crc_zstd_fused(
    prefixes: "list[bytes]", bodies: "list[bytes | np.ndarray]"
) -> tuple[np.ndarray, list[bytes]]:
    """One device pass: per-row Kafka CRC (over prefix||body) and the
    body's zstd entropy stage; each body comes back as a complete
    single-block zstd frame (raw/RLE/compressed, stock-decodable).
    Bodies must be <= 64 KiB like the LZ4 leg; larger buffers go
    through compression.tpu_backend.compress_many_zstd."""
    from ..compression import zstd_frame as zf

    assert len(prefixes) == len(bodies)
    if not bodies:
        return np.empty(0, np.uint32), []
    arrs = [
        np.frombuffer(b, np.uint8) if isinstance(b, (bytes, memoryview)) else b
        for b in bodies
    ]
    longest = max(a.size for a in arrs)
    if longest > 65536:
        raise ValueError("fused codec bodies must be <= 64 KiB")
    n = 512  # floor keeps the crc fold width 512-aligned
    while n < longest:
        n *= 2
    width = ((PREFIX + n + 511) // 512) * 512
    rows = row_bucket(len(arrs))
    batch = np.zeros((rows, width), np.uint8)
    body_len = np.zeros(rows, np.int32)
    for i, (p, a) in enumerate(zip(prefixes, arrs)):
        assert len(p) == PREFIX, f"prefix must be {PREFIX} bytes"
        batch[i, :PREFIX] = np.frombuffer(p, np.uint8)
        batch[i, PREFIX : PREFIX + a.size] = a
        body_len[i] = a.size
    devplane.count_transfer(batch.nbytes + body_len.nbytes, "h2d")
    crc, nbits, streams, bits = _fused_zstd(
        jnp.asarray(batch), jnp.asarray(body_len), n
    )
    crc = np.asarray(crc)[: len(arrs)]
    nbits = np.asarray(nbits)
    streams = np.asarray(streams)
    bits = np.asarray(bits)
    devplane.count_transfer(
        crc.nbytes + nbits.nbytes + streams.nbytes + bits.nbytes, "d2h"
    )
    frames = []
    for i, a in enumerate(arrs):
        if a.size == 0:
            frames.append(zf.frame_header(0) + zf.raw_block(b"", True))
            continue
        sl = [
            streams[i, s, : bits[i, s] // 8 + 1].tobytes() for s in range(4)
        ]
        blk = zf.build_block(
            a.tobytes(), nbits[i].astype(np.int64), sl, True
        )
        frames.append(zf.frame_header(a.size) + blk)
    return crc, frames


def crc_snappy_fused(
    prefixes: "list[bytes]", bodies: "list[bytes | np.ndarray]"
) -> tuple[np.ndarray, list[bytes]]:
    """One device pass: per-row Kafka CRC + raw snappy blocks (the
    snappy leg of the north-star codec trio; preamble host-side)."""
    return _fused_entry(prefixes, bodies, _fused_snappy, snappy_out_bound,
                        _snappy_preamble)


def crc_lz4_fused(
    prefixes: "list[bytes]", bodies: "list[bytes | np.ndarray]"
) -> tuple[np.ndarray, list[bytes]]:
    """One device pass: per-row Kafka CRC (over prefix||body) and the
    body compressed into standard LZ4 blocks. Bodies must be <= 64 KiB
    (the device parser's cell-grid bound); callers chunk larger bodies
    and assemble multi-block frames host-side."""
    return _fused_entry(prefixes, bodies, _fused, out_bound, None)


def _fused_entry(prefixes, bodies, kernel, bound_fn, preamble_fn):
    assert len(prefixes) == len(bodies)
    if not bodies:
        return np.empty(0, np.uint32), []
    arrs = [
        np.frombuffer(b, np.uint8) if isinstance(b, (bytes, memoryview)) else b
        for b in bodies
    ]
    longest = max(a.size for a in arrs)
    if longest > 65536:
        raise ValueError("fused codec bodies must be <= 64 KiB")
    n = 512  # floor keeps the crc fold width 512-aligned
    while n < longest:
        n *= 2
    crc_w = ((PREFIX + n + 511) // 512) * 512
    width = max(PREFIX + n + CELL, crc_w)
    # the rows the call holds, to the next power of two: the program's
    # time is its row count's (PERF.md section 5), and the served caller
    # (RecordBatch.recompressed) holds one body. The fetch verify keeps
    # row_bucket's default floor; its shapes are the benchmark's to move
    rows = row_bucket(len(arrs), floor=1)
    batch = np.zeros((rows, width), np.uint8)
    body_len = np.zeros(rows, np.int32)
    for i, (p, a) in enumerate(zip(prefixes, arrs)):
        assert len(p) == PREFIX, f"prefix must be {PREFIX} bytes"
        batch[i, :PREFIX] = np.frombuffer(p, np.uint8)
        batch[i, PREFIX : PREFIX + a.size] = a
        body_len[i] = a.size
    # which program ran, on the span the caller has open (the served
    # path's `produce.recompress`)
    trace.tag_current(rows=rows, n=n)
    devplane.count_transfer(batch.nbytes + body_len.nbytes, "h2d")
    # the dispatch takes both numpy arrays up with it (a jnp.asarray
    # each is a crossing of its own), and one array comes back
    got = np.asarray(kernel(batch, body_len, n))
    devplane.count_transfer(got.nbytes, "d2h")
    out = got[:, :-8]
    tail = np.ascontiguousarray(got[: len(arrs), -8:]).view("<u4")
    out_len, crc = tail[:, 0].astype(np.int64), tail[:, 1]
    assert int(out_len.max()) <= bound_fn(n)
    blocks = []
    for i in range(len(arrs)):
        blk = out[i, : out_len[i]].tobytes()
        if preamble_fn is not None:
            blk = preamble_fn(int(body_len[i])) + blk
        blocks.append(blk)
    return crc, blocks
