"""Batched LZ4 block compression on device — the `backend=tpu` codec.

North-star #1 (BASELINE.md): record-batch CRC + compression as batched
device kernels. The reference compresses on the CPU one buffer at a
time (src/v/compression/internal/lz4_frame_compressor.cc over liblz4);
here MANY independent chunks are compressed in one XLA program, each
producing a standard LZ4 *block* (decodable by any liblz4 /
LZ4_decompress_safe) that the host wraps into an LZ4 *frame*.

LZ4's greedy parse is inherently sequential, so a TPU port cannot be a
transliteration. Instead the parse is re-shaped into fixed C-byte
"cells" with one decision per cell — everything becomes dense
vector/matrix work over [N]-shaped tensors:

  1. match discovery: hash every 4-gram, sort (hash, pos) keys; the
     position k places back in sort order with the same hash is the
     k-th most recent earlier occurrence of the same gram (an exact
     hash chain, walked 3 deep).
  2. verification: the sort carries each position's 16 bytes along as
     four words, so a candidate's bytes are a shift in sort order, and
     a masked word compare keeps a match only if it runs from its
     in-cell start to the cell end, so every cell emits AT MOST ONE
     sequence: (literals | match to cell end). Cells without a match
     contribute their bytes to the next sequence's literal run (an
     exclusive cummax gives each sequence its literal-run start
     without any sequential pass).
  3. emission: per-cell sequence sizes (token + extended literal
     lengths + literals + offset + extended match length) prefix-sum
     into output positions; each output byte then finds its sequence
     by a count (ones scattered at the sequences' starts, prefix-
     summed), fetches what it needs of it as one gathered row, and
     computes its value. The byte-granular "copy" is one gather of
     the input; the last literal run is a slice.

On the TPU a gather or scatter of single elements is what costs (8.6 ns
an element on the v5e, where a sort of 65,536 keys with four payload
words takes 0.1 ms and a prefix sum of 87,109 takes 4 us: PERF.md
section 5), so steps 1-3 are written to sort, shift and scan, and to
gather only rows (ops/cellparse.py).

The resulting blocks trade ratio for parallelism (matches cannot cross
cell boundaries) but are bit-valid LZ4; ratio on redpanda-like payloads
is within ~10-25% of liblz4's greedy parse.

Spec constraints honored: last sequence is literals-only, no match
starts within the final 12 bytes, offsets ≤ 65535 (chunks ≤ 64 KiB).
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
    """Worst-case device output bytes for an n-byte chunk (all-literal
    cells plus per-cell sequence overhead plus 255-run length bytes)."""
    return n + (n // CELL + 1) * 5 + n // 64 + 64


@functools.partial(jax.jit, static_argnums=(2,))
def _compress_chunks(data: jax.Array, valid: jax.Array, n: int):
    """data: uint8[B, n + CELL] (zero-padded), valid: int32[B].
    Returns (out: uint8[B, out_bound(n)], out_len: int32[B])."""
    m = out_bound(n)

    def one(d: jax.Array, v: jax.Array):
        has, mstart, offs, mlen, lit_start, lit_len, last_end = cell_parse(
            d, v, n
        )

        def n_extra(length):
            return jnp.where(length >= 15, (length - 15) // 255 + 1, 0)

        def extra_byte(length, i):
            # i-th byte of the 255-run encoding of (length - 15)
            return jnp.clip(length - 15 - 255 * i, 0, 255)

        nk = n_extra(lit_len)
        mex = jnp.where(has, n_extra(mlen - 4), 0)
        size = jnp.where(has, 1 + nk + lit_len + 2 + mex, 0)
        starts = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(size)[:-1].astype(jnp.int32)]
        )
        total = starts[-1] + size[-1]

        f_lit_start = last_end
        f_lit_len = jnp.maximum(v - last_end, 0)
        f_nk = n_extra(f_lit_len)
        f_size = 1 + f_nk + f_lit_len
        out_len = total + f_size

        # ---- emission: every output byte finds its (cell, role) ----
        # what a byte needs of its cell comes as one gathered row, and
        # the extra-length count is computed again, not fetched
        # (cellparse.take_rows: a gather costs the chip by the index)
        o = jnp.arange(m, dtype=jnp.int32)
        start_s, lit_len_s, lit_start_s, mlen_s, offs_s = take_rows(
            [starts, lit_len, lit_start, mlen, offs], cell_of_output(starts, m)
        )
        r = o - start_s
        token = (
            (jnp.minimum(lit_len_s, 15) << 4)
            | jnp.minimum(jnp.maximum(mlen_s - 4, 0), 15)
        )
        a1 = 1 + n_extra(lit_len_s)
        a2 = a1 + lit_len_s
        (lit_byte,) = take_rows(
            [d[:n]], jnp.clip(lit_start_s + (r - a1), 0, n - 1)
        )
        val = jnp.where(
            r == 0,
            token,
            jnp.where(
                r < a1,
                extra_byte(lit_len_s, r - 1),
                jnp.where(
                    r < a2,
                    lit_byte,
                    jnp.where(
                        r == a2,
                        offs_s & 255,
                        jnp.where(
                            r == a2 + 1,
                            offs_s >> 8,
                            extra_byte(mlen_s - 4, r - (a2 + 2)),
                        ),
                    ),
                ),
            ),
        )

        # the last literals are one run of the input, f_a1 bytes on from
        # `total`: a slice at a computed offset, not a gather
        fo = o - total
        f_token = jnp.minimum(f_lit_len, 15) << 4
        f_a1 = 1 + f_nk
        f_lit_byte = jax.lax.dynamic_slice(
            jnp.pad(d, (m, m)), (m + f_lit_start - total - f_a1,), (m,)
        )
        f_val = jnp.where(
            fo == 0,
            f_token,
            jnp.where(fo < f_a1, extra_byte(f_lit_len, fo - 1), f_lit_byte),
        )

        out = jnp.where(
            o < total, val, jnp.where(o < out_len, f_val, 0)
        ).astype(jnp.uint8)
        return out, out_len

    return jax.vmap(one)(data, valid)


_compress_chunks = devplane.instrument(
    compileguard.instrument(_compress_chunks, "lz4.compress_chunks"),
    "lz4.compress_chunks",
)


def compress_chunks(chunks: list[bytes | np.ndarray]) -> list[bytes]:
    """Compress each ≤64 KiB chunk into a standard LZ4 block on device.
    Chunks are padded to a shared bucket size so one compiled program
    serves many shapes (the padded-lane recipe of ops/crc32c.py)."""
    if not chunks:
        return []
    arrs = [np.frombuffer(c, np.uint8) if isinstance(c, bytes) else c for c in chunks]
    longest = max(a.size for a in arrs)
    if longest > 65536:
        raise ValueError("device lz4 chunks must be <= 64 KiB")
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
    assert int(out_len.max()) <= out_bound(n), "lz4 out_bound violated"
    return [out[i, : out_len[i]].tobytes() for i in range(len(arrs))]
