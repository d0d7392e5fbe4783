"""Batched raw-snappy compression on device (north-star codec trio).

Reference: src/v/compression/internal/snappy_java_compressor.{h,cc}
compresses on the CPU via libsnappy one buffer at a time; here many
independent chunks run in one XLA program, each producing a standard
raw snappy block (decodable by snappy_uncompress / any snappy
implementation). The snappy-java ("xerial") stream framing the Kafka
wire uses stays host-side, exactly like the LZ4 frame wrap.

The parse is the shared cell grid of ops/cellparse.py (one sequence
decision per 16-byte cell, sort-based hash chain, run absorption).
Emission maps each sequence to snappy elements:

  [literal element]  tag (len-1)<<2 | 0, +1/+2 length bytes past 60
  [copy elements]    2-byte-offset copies (tag&3 == 2), length <= 64
                     each — a merged multi-cell match emits
                     ceil(mlen/64) consecutive copies of the same
                     offset, which is byte-valid snappy.

The uncompressed-length preamble varint is prepended host-side (the
device emits elements only). Offsets fit 16 bits because chunks are
<= 64 KiB, mirroring the LZ4 kernel's constraint.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import devplane
from ..utils import compileguard
from .cellparse import CELL, cell_of_output, cell_parse, take_rows
from .shapes import row_bucket


def out_bound(n: int) -> int:
    """Worst-case device output for an n-byte chunk: all-literal cells
    plus per-sequence overhead (3-byte literal header + 3 bytes per
    64-byte copy span per cell)."""
    return n + (n // CELL + 1) * 6 + 64


def _lit_extra(length):
    """Extra length bytes after the literal tag (0 for len<=60; else
    1 or 2 little-endian bytes of len-1; chunks <= 64 KiB need <= 2)."""
    return jnp.where(length <= 60, 0, jnp.where(length <= 256, 1, 2))


@functools.partial(jax.jit, static_argnums=(2,))
def _compress_chunks(data: jax.Array, valid: jax.Array, n: int):
    """data: uint8[B, n + CELL] (zero-padded), valid: int32[B].
    Returns (out: uint8[B, out_bound(n)] WITHOUT the length preamble,
    out_len: int32[B])."""
    m = out_bound(n)

    def one(d: jax.Array, v: jax.Array):
        has, mstart, offs, mlen, lit_start, lit_len, last_end = cell_parse(
            d, v, n
        )

        lit_ex = _lit_extra(lit_len)
        litsz = jnp.where(lit_len > 0, 1 + lit_ex + lit_len, 0)
        ncop = jnp.where(has, (mlen + 63) // 64, 0)
        size = jnp.where(has, litsz + 3 * ncop, 0)
        starts = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(size)[:-1].astype(jnp.int32)]
        )
        total = starts[-1] + size[-1]

        f_lit_start = last_end
        f_lit_len = jnp.maximum(v - last_end, 0)
        f_ex = _lit_extra(f_lit_len)
        f_size = jnp.where(f_lit_len > 0, 1 + f_ex + f_lit_len, 0)
        out_len = total + f_size

        def lit_byte_val(length, ex, data_b, r):
            # r == 0 → tag; r-1 < ex → length byte i; else literal data
            tag = jnp.where(
                ex == 0,
                (length - 1) << 2,
                jnp.where(ex == 1, 60 << 2, 61 << 2),
            )
            len_b = ((length - 1) >> (8 * jnp.maximum(r - 1, 0))) & 255
            return jnp.where(
                r == 0, tag, jnp.where(r - 1 < ex, len_b, data_b)
            )

        # ---- emission: every output byte finds its (cell, role) ----
        # what a byte needs of its cell comes as one gathered row, and
        # the literal header's size is computed again, not fetched
        # (cellparse.take_rows: a gather costs the chip by the index)
        o = jnp.arange(m, dtype=jnp.int32)
        start_s, lit_len_s, lit_start_s, mlen_s, off_s = take_rows(
            [starts, lit_len, lit_start, mlen, offs], cell_of_output(starts, m)
        )
        r = o - start_s
        lit_ex_s = _lit_extra(lit_len_s)
        litsz_s = jnp.where(lit_len_s > 0, 1 + lit_ex_s + lit_len_s, 0)
        in_lit = r < litsz_s
        (lit_data,) = take_rows(
            [d[:n]], jnp.clip(lit_start_s + r - 1 - lit_ex_s, 0, n - 1)
        )
        lit_v = lit_byte_val(lit_len_s, lit_ex_s, lit_data, r)
        c = r - litsz_s
        ci = c // 3
        role = c % 3
        clen = jnp.clip(mlen_s - 64 * ci, 1, 64)
        copy_v = jnp.where(
            role == 0,
            2 | ((clen - 1) << 2),
            jnp.where(role == 1, off_s & 255, off_s >> 8),
        )
        val = jnp.where(in_lit, lit_v, copy_v)

        # the last literals are one run of the input: a slice at a
        # computed offset, not a gather
        fo = o - total
        f_data = jax.lax.dynamic_slice(
            jnp.pad(d, (m, m)), (m + f_lit_start - total - 1 - f_ex,), (m,)
        )
        f_val = lit_byte_val(f_lit_len, f_ex, f_data, fo)

        out = jnp.where(
            o < total, val, jnp.where(o < out_len, f_val, 0)
        ).astype(jnp.uint8)
        return out, out_len

    return jax.vmap(one)(data, valid)


_compress_chunks = devplane.instrument(
    compileguard.instrument(_compress_chunks, "snappy.compress_chunks"),
    "snappy.compress_chunks",
)


def _preamble(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def compress_chunks(chunks: list[bytes | np.ndarray]) -> list[bytes]:
    """Compress each <= 64 KiB chunk into a standard raw snappy block
    on device (preamble prepended host-side). Padded-bucket recipe of
    ops/crc32c.py: one compiled program serves many sizes."""
    if not chunks:
        return []
    arrs = [
        np.frombuffer(c, np.uint8) if isinstance(c, bytes) else c
        for c in chunks
    ]
    longest = max(a.size for a in arrs)
    if longest > 65536:
        raise ValueError("device snappy chunks must be <= 64 KiB")
    n = 256
    while n < longest:
        n *= 2
    rows = row_bucket(len(arrs))
    batch = np.zeros((rows, n + CELL), np.uint8)
    valid = np.zeros(rows, np.int32)
    for i, a in enumerate(arrs):
        batch[i, : a.size] = a
        valid[i] = a.size
    devplane.count_transfer(batch.nbytes + valid.nbytes, "h2d")
    out, out_len = _compress_chunks(jnp.asarray(batch), jnp.asarray(valid), n)
    out = np.asarray(out)
    out_len = np.asarray(out_len)
    devplane.count_transfer(out.nbytes + out_len.nbytes, "d2h")
    assert int(out_len.max()) <= out_bound(n), "snappy out_bound violated"
    return [
        _preamble(int(valid[i])) + out[i, : out_len[i]].tobytes()
        for i in range(len(arrs))
    ]
