"""Fused device CRC32C + LZ4 over record-batch bodies: ONE upload.

The round-2 lesson (BENCH_r02): each kernel alone wins device-resident
but loses end-to-end because the host->device copy dominates. Fusing
validation and compression into one program amortizes that single
upload across BOTH ops — the host must otherwise run two full passes
(crc ~8 GB/s native + lz4 ~1.6 GB/s liblz4), so the combined host
throughput is ~1.3 GB/s while the fused device path pays one transfer.

Row layout ([B, PREFIX + n + CELL] uint8, zero-padded):

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

from ..observability import devplane
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


@functools.partial(jax.jit, static_argnums=(2,))
def _fused(data: jax.Array, body_len: jax.Array, n: int):
    """data [B, PREFIX + n + CELL] uint8; body_len int32[B].
    Returns (crc uint32[B] over prefix||body, lz4 blocks + lengths)."""
    # CRC slice: width PREFIX+n rounded up to the 512-byte CRC chunk —
    # the matrix is allocated with that slack, zero-padded
    crc_w = ((PREFIX + n + 511) // 512) * 512
    crc = crc32c_device(
        data[:, :crc_w], (body_len + PREFIX).astype(jnp.int64)
    )
    # barrier: without it XLA is free to fuse the crc path's view of
    # the row (its [rows * chunks, 512] reshape) into the lz4 slice's
    # consumers. Measured when the CRC was a scan over chunks, that
    # fusion ran the combined program ~1000x slower (8.5 s vs ~1 ms for
    # this shape). Timed again on the TPU v5e at PR 32, [8, 66048],
    # since the CRC takes all chunks at once: 401.8 ms with the
    # barrier, 399.8 without, 399.7 for the LZ4 alone, same results
    # (PERF.md section 5): it no longer decides anything, and stays
    # until a change to this program's speed takes it out with a
    # measurement. It materializes the body slice once; both kernels
    # then run at their standalone speeds off the single upload.
    body = jax.lax.optimization_barrier(
        data[:, PREFIX : PREFIX + n + CELL]
    )
    out, out_len = _compress_chunks(body, body_len, n)
    return crc, out, out_len


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
    body = jax.lax.optimization_barrier(
        data[:, PREFIX : PREFIX + n + CELL]
    )
    out, out_len = _snappy_chunks(body, body_len, n)
    return crc, out, out_len


_fused_snappy = devplane.instrument(
    compileguard.instrument(_fused_snappy, "fused.crc_snappy"),
    "fused.crc_snappy",
)


@functools.partial(jax.jit, static_argnums=(2,))
def _fused_zstd(data: jax.Array, body_len: jax.Array, n: int):
    """Same layout/barrier recipe as _fused, zstd entropy stage instead
    of LZ4 (different output shape: code lengths + 4 huff0 streams per
    row; frame scaffolding is host work)."""
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
    rows = row_bucket(len(arrs))
    batch = np.zeros((rows, width), np.uint8)
    body_len = np.zeros(rows, np.int32)
    for i, (p, a) in enumerate(zip(prefixes, arrs)):
        assert len(p) == PREFIX, f"prefix must be {PREFIX} bytes"
        batch[i, :PREFIX] = np.frombuffer(p, np.uint8)
        batch[i, PREFIX : PREFIX + a.size] = a
        body_len[i] = a.size
    devplane.count_transfer(batch.nbytes + body_len.nbytes, "h2d")
    crc, out, out_len = kernel(
        jnp.asarray(batch), jnp.asarray(body_len), n
    )
    crc = np.asarray(crc)[: len(arrs)]
    out = np.asarray(out)
    out_len = np.asarray(out_len)
    devplane.count_transfer(
        crc.nbytes + out.nbytes + out_len.nbytes, "d2h"
    )
    assert int(out_len.max()) <= bound_fn(n)
    blocks = []
    for i in range(len(arrs)):
        blk = out[i, : out_len[i]].tobytes()
        if preamble_fn is not None:
            blk = preamble_fn(int(body_len[i])) + blk
        blocks.append(blk)
    return crc, blocks
