"""Batched CRC-32C on device — validate many record batches per call.

The device-side record-batch validator (north star: BASELINE.md —
record-batch CRC as a batched kernel; host analog
model/record_utils.h:23-31 + the native rp_crc32c_batch).

CRC-32C is GF(2)-LINEAR in the message bits: the register after one
byte is s' = Z(s) xor C(b) with Z, C fixed linear maps (T0[x] is
linear because CRC tables satisfy T0[a^b] = T0[a]^T0[b]). So the
whole checksum is a bit-matrix product — which on a TPU belongs on
the MXU, not in byte-table gathers (gathers are the one thing the
VPU does badly; the first-cut slice-by-8 port ran at 0.02 GB/s):

  1. Rows are padded to a uniform stride and every row is viewed as
     stride/512 chunks of 512 bytes, so the whole [rows, stride] matrix
     is one [rows * chunks, 512] matrix of chunk rows. A precomputed
     [4096, 32] GF(2) matrix M0 maps a chunk's bits to its CRC-register
     contribution, and nothing orders the chunks: ALL of them are taken
     at once, as eight int8 matmuls over the byte matrix's bit planes,
        counts = sum_k ((bytes >> k) & 1) @ M0[k::8]
     (exact int32 accumulation, then mod 2); the same bytes as 32-bit
     words are 32 planes of [rows * chunks, 128] against M0[k::32]. The
     minor dimension stays what it is throughout, so no byte is ever
     moved to make room for its bits. A row's chunk contributions are
     then combined in log depth: chunk i of n is still n-1-i chunks
     from the row's end, so level l applies Z^(512 * 2^l) to the chunks
     whose distance has bit l set (32 select/xors a level, ceil(log2 n)
     levels, any n), and one xor over the chunks gives the register.
     The init register rides through the whole row untouched by the
     data: Z^stride(0xFFFFFFFF), a constant of the stride, xored in at
     the end. No loop, no data-dependent control flow; a matrix of more
     than _TILE chunk rows walks tiles of _TILE chunk rows (not chunks),
     which bounds the live bit planes by shape.
  2. Per-row lengths are then fixed up *after* the combine: padding zeros
     are algebraically removed by multiplying the raw CRC register by
     Z^-k over GF(2), where Z is the one-zero-byte extension operator
     and k = stride - len. Z^-(2^j) matrices are precomputed host-side;
     the fixup is ~32 xor/select ops per set bit of k. This turns
     "variable-length rows" — the thing that usually kills batched CRC
     — into a constant-depth epilogue.

Differentially tested against the native host implementation
(tests/test_ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import devplane
from ..utils import compileguard
from ..utils.crc import _TABLE as _BYTE_TABLE
from .shapes import row_bucket

_MAX_LOG_PAD = 30  # supports strides up to 2^30


def _make_tables() -> np.ndarray:
    """Slice-by-8 tables: row 0 is the shared byte table from utils.crc
    (same polynomial by construction); rows 1..7 are derived."""
    t = np.zeros((8, 256), dtype=np.uint32)
    t[0] = _BYTE_TABLE
    for n in range(256):
        c = t[0, n]
        for k in range(1, 8):
            c = t[0, c & 0xFF] ^ (c >> np.uint32(8))
            t[k, n] = c
    return t


_TABLES = _make_tables()

_CHUNK = 512  # bytes per chunk row (a 4096-bit contraction)
# chunk rows whose bit planes are live at once: 4 MB of bytes, 32 MB of
# planes. The shape a live fetch dispatches ([8, 65536], 1,024 chunk
# rows) is well inside one tile; a larger matrix loops over tiles.
_TILE = 8192


# -- GF(2) linear-algebra helpers (host-side, numpy) -----------------
def _apply_cols(cols: np.ndarray, vecs: np.ndarray) -> np.ndarray:
    """Apply a 32x32 GF(2) matrix (given as its 32 uint32 columns) to
    an array of uint32 vectors."""
    out = np.zeros_like(vecs, dtype=np.uint32)
    for k in range(32):
        out ^= np.where((vecs >> np.uint32(k)) & 1, cols[k], np.uint32(0))
    return out


@functools.cache
def _z_cols() -> np.ndarray:
    """Columns of Z, the one-zero-byte register extension:
    Z(s) = T0[s & 0xff] ^ (s >> 8)."""
    t0 = _TABLES[0]
    return np.array(
        [t0[(1 << k) & 0xFF] ^ (np.uint32(1 << k) >> np.uint32(8)) for k in range(32)],
        dtype=np.uint32,
    )


@functools.cache
def _z_pow_cols(nbytes: int) -> np.ndarray:
    """Columns of Z^nbytes (the register shift over `nbytes` zero
    bytes), by square and multiply."""
    acc = np.array([np.uint32(1 << k) for k in range(32)], dtype=np.uint32)
    sq = _z_cols()
    while nbytes:
        if nbytes & 1:
            acc = _apply_cols(sq, acc)
        sq = _apply_cols(sq, sq)
        nbytes >>= 1
    return acc


@functools.cache
def _chunk_matrix() -> np.ndarray:
    """M0: [CHUNK*8, 32] int8 GF(2) matrix mapping a chunk's bits
    (byte-major, LSB-first within each byte) to the chunk's CRC
    register contribution Σ_p Z^(CHUNK-1-p) C(byte_p)."""
    t0 = _TABLES[0]
    c_vec = np.array([t0[1 << k] for k in range(8)], dtype=np.uint32)
    z = _z_cols()
    w = np.array([np.uint32(1 << k) for k in range(32)], dtype=np.uint32)  # I
    rows = np.zeros(_CHUNK * 8, dtype=np.uint32)
    for p in range(_CHUNK - 1, -1, -1):
        rows[p * 8 : (p + 1) * 8] = _apply_cols(w, c_vec)
        w = _apply_cols(z, w)
    bits = ((rows[:, None] >> np.arange(32, dtype=np.uint32)) & 1).astype(np.int8)
    return bits  # [4096, 32]


@functools.cache
def _zero_unextend_matrices() -> np.ndarray:
    """Columns of Z^-(2^j) for j in [0, _MAX_LOG_PAD): [J, 32] uint32.

    Z is the linear map one zero byte applies to the raw CRC register:
    s' = T0[s & 0xff] ^ (s >> 8). CRC tables are GF(2)-linear, so Z is
    a 32x32 bit-matrix; its inverse un-extends padding zeros."""
    z_cols = _z_cols()

    def mat_to_bits(cols: np.ndarray) -> np.ndarray:
        m = np.zeros((32, 32), dtype=np.uint8)
        for c in range(32):
            for r in range(32):
                m[r, c] = (int(cols[c]) >> r) & 1
        return m

    def bits_to_cols(m: np.ndarray) -> np.ndarray:
        cols = np.zeros(32, dtype=np.uint32)
        for c in range(32):
            v = 0
            for r in range(32):
                if m[r, c]:
                    v |= 1 << r
            cols[c] = v
        return cols

    def gf2_inv(m: np.ndarray) -> np.ndarray:
        n = m.shape[0]
        aug = np.concatenate([m.copy(), np.eye(n, dtype=np.uint8)], axis=1)
        for col in range(n):
            pivot = next(r for r in range(col, n) if aug[r, col])
            if pivot != col:
                aug[[col, pivot]] = aug[[pivot, col]]
            for r in range(n):
                if r != col and aug[r, col]:
                    aug[r] ^= aug[col]
        return aug[:, n:]

    def gf2_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
        return (a.astype(np.int32) @ b.astype(np.int32) % 2).astype(np.uint8)

    z_bits = mat_to_bits(z_cols)
    zinv = gf2_inv(z_bits)
    pows = []
    cur = zinv
    for _ in range(_MAX_LOG_PAD):
        pows.append(bits_to_cols(cur))
        cur = gf2_matmul(cur, cur)
    return np.stack(pows)  # [J, 32]


def _chunk_contribs(chunks: jax.Array) -> jax.Array:
    """CRC-register contribution of every 512-byte chunk at once.

    chunks: [R, 512] uint8, or the same bytes as [R, 128] little-endian
    uint32 words. Returns [R] uint32. Bit plane k of the lanes times
    the rows of M0 that belong to bit k of every lane (M0 is byte-major
    and LSB first, which is also word-major and LSB first): 8 (32)
    [R, 512] x [512, 32] ([R, 128] x [128, 32]) int8 matmuls into one
    exact int32 count, mod 2 = the GF(2) contribution."""
    m0 = _chunk_matrix()  # [4096, 32] int8
    planes = 8 * chunks.dtype.itemsize
    counts = sum(
        jax.lax.dot_general(
            ((chunks >> k) & 1).astype(jnp.int8),
            jnp.asarray(m0[k::planes]),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        for k in range(planes)
    )  # [R, 32]
    bits = (counts & 1).astype(jnp.uint32)
    shift = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bits << shift[None, :], axis=1, dtype=jnp.uint32)


def _combine_chunks(contribs: jax.Array) -> jax.Array:
    """sum_i Z^(_CHUNK * (n-1-i)) contribs[:, i] over GF(2), in
    ceil(log2 n) levels: level l shifts, by Z^(_CHUNK * 2^l), the
    chunks whose distance n-1-i from the row's end has bit l set.

    contribs: [B, n] uint32. Returns [B] uint32."""
    n = contribs.shape[1]
    dist = np.arange(n - 1, -1, -1)
    out = contribs
    for level in range((n - 1).bit_length()):
        cols = jnp.asarray(_z_pow_cols(_CHUNK << level))
        far = jnp.asarray(((dist >> level) & 1).astype(bool))[None, :]
        out = jnp.where(far, _gf2_matvec(cols, out), out)
    return jax.lax.reduce(out, jnp.uint32(0), jax.lax.bitwise_xor, (1,))


def _crc32c_raw(data: jax.Array, stride: int) -> jax.Array:
    """Raw (non-finalized) CRC register after every full row of
    `stride` bytes (stride % _CHUNK == 0).

    data: [B, stride] uint8 or [B, stride / 4] uint32 words. Returns
    [B] uint32."""
    b = data.shape[0]
    n_chunks = stride // _CHUNK
    total = b * n_chunks
    chunks = data.reshape(total, data.shape[1] // n_chunks)
    if total <= _TILE:
        contribs = _chunk_contribs(chunks)
    else:
        # zero chunk rows appended to fill the last tile contribute
        # nothing and are cut off again
        tiles = -(-total // _TILE)
        chunks = jnp.pad(chunks, ((0, tiles * _TILE - total), (0, 0)))
        contribs = jax.lax.map(
            _chunk_contribs, chunks.reshape(tiles, _TILE, chunks.shape[1])
        ).reshape(tiles * _TILE)[:total]
    raw = _combine_chunks(contribs.reshape(b, n_chunks))
    init = _apply_cols(_z_pow_cols(stride), np.array([0xFFFFFFFF], np.uint32))[0]
    return raw ^ jnp.uint32(init)


def _gf2_matvec(cols: jax.Array, v: jax.Array) -> jax.Array:
    """cols: [32] uint32 (matrix columns); v: [B] uint32."""
    out = jnp.zeros_like(v)
    for k in range(32):
        bit = ((v >> k) & 1).astype(bool)
        out = out ^ jnp.where(bit, cols[k], jnp.uint32(0))
    return out


def _unextend_zeros(raw: jax.Array, pad: jax.Array, stride: int) -> jax.Array:
    """Remove `pad` trailing zero bytes (at most `stride`, so only that
    many of pad's bits can be set) from each row's raw register."""
    mats = jnp.asarray(_zero_unextend_matrices())  # [J, 32]
    out = raw
    for j in range(min(stride.bit_length(), _MAX_LOG_PAD)):
        apply = ((pad >> j) & 1).astype(bool)
        out = jnp.where(apply, _gf2_matvec(mats[j], out), out)
    return out


@jax.jit
def crc32c_device(data: jax.Array, lens: jax.Array) -> jax.Array:
    """CRC-32C of each row: data [B, S] uint8 (S % _CHUNK == 0), or the
    same bytes as [B, S / 4] little-endian uint32 words (what the chip
    takes up fastest); lens [B], in bytes.

    Returns [B] uint32 finalized checksums. Rows must be zero-padded
    beyond their length (the fixup assumes padding bytes are 0)."""
    stride = data.shape[1] * data.dtype.itemsize
    raw = _crc32c_raw(data, stride)
    pad = (stride - lens).astype(jnp.uint32)
    fixed = _unextend_zeros(raw, pad, stride)
    return fixed ^ jnp.uint32(0xFFFFFFFF)


crc32c_device = devplane.instrument(
    compileguard.instrument(crc32c_device, "crc32c.device"),
    "crc32c.device",
)


def crc32c_batch_device(bufs: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Drop-in device counterpart of utils.crc.crc32c_batch (same padded
    [n, stride] layout produced by models.record.batch_crcs)."""
    bufs = np.ascontiguousarray(bufs, dtype=np.uint8)
    lens = np.asarray(lens, dtype=np.int64)
    if lens.size and int(lens.max()) > bufs.shape[1]:
        raise ValueError(
            f"lens.max()={int(lens.max())} exceeds stride={bufs.shape[1]}"
        )
    # bucket BOTH dims so the kernel signature set stays bounded: stride
    # doubles from the chunk, rows take the shared pow2 bucket. The
    # zero-pad is algebraically removed by the length fixup (Z^-k), so
    # the extra columns/rows never change real checksums; padded rows
    # (len 0) are sliced off below.
    n = bufs.shape[0]
    stride = _CHUNK
    while stride < bufs.shape[1]:
        stride *= 2
    rows = row_bucket(n)
    padded = np.zeros((rows, stride), np.uint8)
    padded[:n, : bufs.shape[1]] = bufs
    plens = np.zeros(rows, np.int64)
    plens[:n] = lens
    devplane.count_transfer(padded.nbytes + plens.nbytes, "h2d")
    # one dispatch takes both up as the numpy arrays they are (a
    # jnp.asarray each is a transfer of its own and ~0.1 ms dearer on
    # the v5e), the bytes as 32-bit words, which the chip lays out as
    # they come where bytes have to be re-tiled (another ~0.1 ms)
    out = np.asarray(crc32c_device(padded.view("<u4"), plens))
    devplane.count_transfer(out.nbytes, "d2h")
    return out[:n]
