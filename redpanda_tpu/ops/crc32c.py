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

  1. Rows are padded to a uniform stride and split into 512-byte
     chunks. A precomputed [4096, 32] GF(2) matrix M0 maps a chunk's
     bits to its CRC-register contribution; the per-chunk fold is
        s <- (Z^512)(s) xor M0^T bits(chunk)
     i.e. ONE int8 matmul per chunk (exact int32 accumulation, then
     mod 2) plus 32 select/xors for the Z^512 application — a
     lax.scan of MXU matmuls over lanes of record batches, no
     data-dependent control flow anywhere.
  2. Per-row lengths are then fixed up *after* the scan: padding zeros
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

_CHUNK = 512  # bytes folded per MXU matmul (4096-bit contraction)


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
def _zk_cols() -> np.ndarray:
    """Columns of Z^_CHUNK (the per-chunk register shift)."""
    cols = _z_cols()
    acc = np.array([np.uint32(1 << k) for k in range(32)], dtype=np.uint32)
    for _ in range(_CHUNK):
        acc = _apply_cols(cols, acc)
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


def _crc32c_padded_scan(data: jax.Array) -> jax.Array:
    """Raw (non-finalized) CRC register after scanning every full row.

    data: [B, S] uint8 with S % _CHUNK == 0. Returns [B] uint32.
    The fold is a lax.scan whose body is one MXU matmul: bits of the
    chunk [B, 4096] int8 x M0 [4096, 32] -> exact int32 counts, mod 2
    = the GF(2) contribution; plus the Z^CHUNK register shift."""
    b, s = data.shape
    n_chunks = s // _CHUNK
    m0 = jnp.asarray(_chunk_matrix())  # [4096, 32] int8
    zk = jnp.asarray(_zk_cols())  # [32] uint32
    pack_shift = jnp.arange(32, dtype=jnp.uint32)
    bit_idx = jnp.arange(8, dtype=jnp.uint8)

    # scan consumes [n_chunks, B, CHUNK] BYTES; the 8x bit expansion
    # happens inside the step so only one chunk's bits are ever live
    chunks = data.reshape(b, n_chunks, _CHUNK).transpose(1, 0, 2)

    def step(s_reg, chunk_bytes):
        chunk_bits = (
            ((chunk_bytes[:, :, None] >> bit_idx) & 1)
            .astype(jnp.int8)
            .reshape(chunk_bytes.shape[0], _CHUNK * 8)
        )
        shifted = _gf2_matvec(zk, s_reg)
        counts = jax.lax.dot_general(
            chunk_bits,
            m0,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [B, 32]
        contrib_bits = (counts & 1).astype(jnp.uint32)
        contrib = jnp.sum(contrib_bits << pack_shift[None, :], axis=1, dtype=jnp.uint32)
        return shifted ^ contrib, None

    init = jnp.full((b,), 0xFFFFFFFF, jnp.uint32)
    raw, _ = jax.lax.scan(step, init, chunks)
    return raw


def _gf2_matvec(cols: jax.Array, v: jax.Array) -> jax.Array:
    """cols: [32] uint32 (matrix columns); v: [B] uint32."""
    out = jnp.zeros_like(v)
    for k in range(32):
        bit = ((v >> k) & 1).astype(bool)
        out = out ^ jnp.where(bit, cols[k], jnp.uint32(0))
    return out


def _unextend_zeros(raw: jax.Array, pad: jax.Array) -> jax.Array:
    """Remove `pad` trailing zero bytes from each row's raw register."""
    mats = jnp.asarray(_zero_unextend_matrices())  # [J, 32]
    out = raw
    for j in range(_MAX_LOG_PAD):
        apply = ((pad >> j) & 1).astype(bool)
        out = jnp.where(apply, _gf2_matvec(mats[j], out), out)
    return out


@functools.partial(jax.jit, static_argnums=())
def crc32c_device(data: jax.Array, lens: jax.Array) -> jax.Array:
    """CRC-32C of each row: data [B, S] uint8 (S % _CHUNK == 0),
    lens [B].

    Returns [B] uint32 finalized checksums. Rows must be zero-padded
    beyond their length (the scan assumes padding bytes are 0)."""
    raw = _crc32c_padded_scan(data)
    pad = (data.shape[1] - lens).astype(jnp.uint32)
    fixed = _unextend_zeros(raw, pad)
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
    # doubles from the fold chunk, rows take the shared pow2 bucket. The
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
    out = np.asarray(crc32c_device(jnp.asarray(padded), jnp.asarray(plens)))
    devplane.count_transfer(out.nbytes, "d2h")
    return out[:n]
