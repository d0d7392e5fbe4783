"""Batched zstd entropy stage on device — the codec the tiered path uses.

North-star #1 (BASELINE.md) names CRC32c + lz4/zstd/snappy device
kernels; LZ4 and snappy already run as fused cell-parallel programs
(ops/lz4.py, ops/snappy.py). zstd's sequential match+FSE pipeline does
not transliterate, but SplitZip (arxiv 2605.01708) shows the split
that does: keep the entropy stage, drop the sequential parse. This
kernel emits the literals-only profile — each <=64 KiB chunk becomes a
raw/RLE/compressed zstd block whose compressed form is a 4-stream huff0
literals section (single-stage Huffman encoder, arxiv 2601.10673) with
zero sequences. Frame/block scaffolding is host-side
(compression/zstd_frame.py); this module is the O(n) device work:

  encode — per chunk, ONE program computes (1) the byte histogram,
  (2) an exactly-Kraft code-length assignment over the fixed 2^11 huff0
  slot space (power-of-two slot counts repaired by halving/doubling
  loops whose termination follows from all slot counts being powers of
  two: the deficit is always a multiple of the smallest live slot), (3)
  canonical huff0 code values (longer codes in the low table regions,
  symbols ascending within a length class), and (4) the four reversed
  bitstreams: every output byte finds its covering symbol with a
  searchsorted over the bit-position prefix sum — the same
  per-output-byte emission recipe as ops/lz4.py.

  decode — huff0 streams are sequential (each symbol's position depends
  on every previous length), so hydration decode uses pointer jumping:
  a transition table f[p] = p - nbits(peek(p)) over all 8*S bit
  positions, then log2(regen) doubling rounds (P <- concat(P, J[P]),
  J <- J[J]) enumerate all symbol positions at once — the SplitZip
  parallel-decode shape.

Code lengths are capped at TABLELOG=11 and the Kraft sum is EXACT
(sum 2^(11-len) == 2^11), which is what makes huff0's implied-weight
tree description and table-region code assignment well defined.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import devplane
from ..utils import compileguard
from .shapes import row_bucket

TABLELOG = 11
TSIZE = 1 << TABLELOG


def stream_cap(n: int) -> int:
    """Max symbols one of the 4 literal streams can carry for an
    n-byte chunk (streams 1-3 take ceil(len/4), stream 4 the rest)."""
    return n // 4 + 1


def stream_byte_bound(n: int) -> int:
    """Worst-case bytes of one emitted stream (11 bits/symbol + the
    end-marker bit, rounded up)."""
    return (TABLELOG * stream_cap(n)) // 8 + 2


def _floor_log2(x: jax.Array, hi: int) -> jax.Array:
    """Integer floor(log2(x)) for x in [1, 2^hi] — bit probes, no
    float log2 (whose boundary rounding would corrupt slot counts)."""
    j = jnp.arange(1, hi + 1, dtype=jnp.int32)
    return jnp.sum((x[..., None] >> j) > 0, axis=-1).astype(jnp.int32)


def _kraft_nbits(counts: jax.Array, v: jax.Array):
    """Exactly-Kraft code lengths over the 2^11 slot space.

    Each present symbol gets a power-of-two slot count u (code length
    11 - log2(u)), seeded from its ideal share floor-rounded to a power
    of two, then repaired: halve the smallest-count symbol while over
    budget, double the largest feasible one while under. Feasibility of
    the up-phase: every u is a power of two, so the deficit D = 2048 -
    sum(u) is a multiple of min(u); whenever D > 0 the smallest-u
    symbol satisfies u <= D (and u < 1024 unless fewer than 2 symbols
    are present, which callers route to RLE)."""
    present = counts > 0
    c64 = counts.astype(jnp.int64)
    v64 = jnp.maximum(v.astype(jnp.int64), 1)
    q = jnp.clip((c64 * TSIZE + v64 - 1) // v64, 1, TSIZE)
    u = jnp.where(
        present,
        jnp.clip(
            (1 << _floor_log2(q, TABLELOG + 1).astype(jnp.int64)), 1, 1024
        ),
        0,
    ).astype(jnp.int32)

    def down_cond(u):
        cand = present & (u >= 2)
        return (jnp.sum(u) > TSIZE) & jnp.any(cand)

    def down_body(u):
        key = jnp.where(present & (u >= 2), counts, jnp.int32(1 << 30))
        i = jnp.argmin(key)
        return u.at[i].set(u[i] >> 1)

    u = jax.lax.while_loop(down_cond, down_body, u)

    def up_cond(u):
        d = TSIZE - jnp.sum(u)
        cand = present & (u <= d) & (u < 1024)
        return (d > 0) & jnp.any(cand)

    def up_body(u):
        d = TSIZE - jnp.sum(u)
        key = jnp.where(present & (u <= d) & (u < 1024), u, -1)
        i = jnp.argmax(key)
        return u.at[i].set(u[i] * 2)

    u = jax.lax.while_loop(up_cond, up_body, u)
    nbits = jnp.where(
        present, TABLELOG - _floor_log2(jnp.maximum(u, 1), TABLELOG), 0
    )
    return nbits.astype(jnp.int32)


def _huff_codes(nbits: jax.Array) -> jax.Array:
    """Canonical huff0 code values from lengths (see
    zstd_frame.huffman_codes for the host twin and the region math)."""
    present = nbits > 0
    b = jnp.arange(TABLELOG + 1, dtype=jnp.int32)
    rc = (
        jnp.zeros(TABLELOG + 1, jnp.int32)
        .at[nbits]
        .add(present.astype(jnp.int32))
    )
    slots = jnp.where(b > 0, rc << (TABLELOG - b), 0)
    tail = jnp.cumsum(slots[::-1])[::-1]  # tail[b] = sum_{j>=b} slots[j]
    base = jnp.concatenate([tail[1:], jnp.zeros(1, tail.dtype)])
    onehot = (nbits[:, None] == b[None, :]) & present[:, None]
    order = (jnp.cumsum(onehot, axis=0) - onehot)[
        jnp.arange(256), nbits
    ].astype(jnp.int32)
    codes = (base[nbits] >> jnp.maximum(TABLELOG - nbits, 0)).astype(
        jnp.int32
    ) + order
    return jnp.where(present, codes, 0)


def _encode_one(d: jax.Array, v: jax.Array, n: int):
    """One chunk -> (nbits[256], stream bytes [4, SB], stream bits [4])."""
    mcap = stream_cap(n)
    sb = stream_byte_bound(n)
    pos_valid = jnp.arange(n, dtype=jnp.int32) < v
    counts = (
        jnp.zeros(256, jnp.int32)
        .at[d.astype(jnp.int32)]
        .add(pos_valid.astype(jnp.int32))
    )
    nbits = _kraft_nbits(counts, v)
    codes = _huff_codes(nbits)

    m4 = (v + 3) // 4
    starts = jnp.stack([0 * m4, m4, 2 * m4, 3 * m4])
    slens = jnp.stack([m4, m4, m4, jnp.maximum(v - 3 * m4, 0)])

    def emit(start, slen):
        i = jnp.arange(mcap, dtype=jnp.int32)
        sym = d[jnp.clip(start + i, 0, n - 1)].astype(jnp.int32)
        nb = jnp.where(i < slen, nbits[sym], 0)
        csum = jnp.cumsum(nb)
        tb = csum[mcap - 1]
        # symbols are written in REVERSE order (huff0 reads backward):
        # symbol i occupies bits [tb - csum[i], tb - csum[i] + nb[i])
        bitpos = tb - csum
        rev = bitpos[::-1]  # ascending
        j = jnp.arange(8 * sb, dtype=jnp.int32)
        k = jnp.searchsorted(rev, j, side="right").astype(jnp.int32) - 1
        idx = jnp.clip(mcap - 1 - k, 0, mcap - 1)
        shift = jnp.clip(j - bitpos[idx], 0, 31)
        bit = (codes[sym[idx]] >> shift) & 1
        bit = jnp.where(j < tb, bit, jnp.where(j == tb, 1, 0))
        byts = jnp.sum(
            bit.reshape(sb, 8) << jnp.arange(8, dtype=jnp.int32)[None, :],
            axis=1,
        ).astype(jnp.uint8)
        return byts, tb

    streams, tbs = jax.vmap(emit)(starts, slens)
    return nbits.astype(jnp.uint8), streams, tbs.astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(2,))
def _encode_chunks(data: jax.Array, valid: jax.Array, n: int):
    """data: uint8[B, n] (zero-padded), valid: int32[B]. Returns
    (nbits uint8[B, 256], streams uint8[B, 4, SB], bits int32[B, 4])."""
    return jax.vmap(lambda d, v: _encode_one(d, v, n))(data, valid)


_encode_chunks = devplane.instrument(
    compileguard.instrument(_encode_chunks, "zstd.encode_chunks"),
    "zstd.encode_chunks",
)


def encode_chunks(
    chunks: "list[bytes | np.ndarray]",
) -> "list[tuple[np.ndarray, list[bytes]]]":
    """Device-encode each <=64 KiB chunk: (code lengths, 4 huff0
    streams) per chunk, one compiled program per padded bucket (the
    ops/crc32c.py padded-lane recipe). Frame/block assembly from these
    is zstd_frame.build_block's job."""
    if not chunks:
        return []
    arrs = [
        np.frombuffer(c, np.uint8) if isinstance(c, bytes) else c
        for c in chunks
    ]
    longest = max(a.size for a in arrs)
    if longest > 65536:
        raise ValueError("device zstd chunks must be <= 64 KiB")
    n = 256
    while n < longest:
        n *= 2
    rows = row_bucket(len(arrs))
    batch = np.zeros((rows, n), np.uint8)
    valid = np.zeros(rows, np.int32)
    for i, a in enumerate(arrs):
        batch[i, : a.size] = a
        valid[i] = a.size
    devplane.count_transfer(batch.nbytes + valid.nbytes, "h2d")
    nbits, streams, bits = _encode_chunks(
        jnp.asarray(batch), jnp.asarray(valid), n
    )
    nbits = np.asarray(nbits)
    streams = np.asarray(streams)
    bits = np.asarray(bits)
    devplane.count_transfer(
        nbits.nbytes + streams.nbytes + bits.nbytes, "d2h"
    )
    out = []
    for i in range(len(arrs)):
        sl = [
            streams[i, s, : bits[i, s] // 8 + 1].tobytes() for s in range(4)
        ]
        out.append((nbits[i].astype(np.int64), sl))
    return out


# ------------------------------------------------------------------ decode
def _decode_one(buf, tb, rg, sym, nb, sbytes: int, rmax: int):
    """One huff0 stream decoded by pointer jumping over bit positions."""
    # padded by 2 zero bytes so every 11-bit window read is in-bounds
    padded = jnp.concatenate([jnp.zeros(2, jnp.uint8), buf])
    p = jnp.arange(8 * sbytes + 1, dtype=jnp.int32)
    lo = p + 16 - TABLELOG  # window start bit in padded space (>= 0)
    q = lo >> 3
    w = (
        padded[q].astype(jnp.int32)
        | (padded[q + 1].astype(jnp.int32) << 8)
        | (padded[jnp.clip(q + 2, 0, sbytes + 1)].astype(jnp.int32) << 16)
    )
    peek = (w >> (lo - (q << 3))) & (TSIZE - 1)
    s_at = sym[peek].astype(jnp.uint8)
    f = jnp.maximum(p - nb[peek], 0).at[0].set(0).astype(jnp.int32)
    rounds = max(1, (rmax - 1).bit_length())
    pos = jnp.zeros(rmax, jnp.int32).at[0].set(tb)
    jtab = f
    size = 1
    ar = jnp.arange(rmax, dtype=jnp.int32)
    for _ in range(rounds):
        hop = jtab[pos[jnp.clip(ar - size, 0, rmax - 1)]]
        pos = jnp.where((ar >= size) & (ar < 2 * size), hop, pos)
        jtab = jtab[jtab]
        size *= 2
    out = jnp.where(ar < rg, s_at[pos], 0).astype(jnp.uint8)
    end = f[pos[jnp.clip(rg - 1, 0, rmax - 1)]]
    return out, end


@functools.partial(jax.jit, static_argnums=(5, 6))
def _decode_streams(bufs, tbits, regen, tsym, tnb, sbytes: int, rmax: int):
    """bufs uint8[S, sbytes]; tbits/regen int32[S]; tsym uint8[S, 2048],
    tnb int32[S, 2048]. Returns (out uint8[S, rmax], end int32[S]) —
    `end` must be 0 for every valid stream (exact consumption)."""
    return jax.vmap(
        lambda b, t, r, s, n: _decode_one(b, t, r, s, n, sbytes, rmax)
    )(bufs, tbits, regen, tsym, tnb)


_decode_streams = devplane.instrument(
    compileguard.instrument(_decode_streams, "zstd.decode_streams"),
    "zstd.decode_streams",
)


def decode_streams(
    streams: "list[bytes]",
    regens: "list[int]",
    tables: "list[tuple[np.ndarray, np.ndarray]]",
) -> "list[bytes]":
    """Batch-decode huff0 streams on device. streams[i] regenerates
    regens[i] bytes using decode table tables[i] (sym[2048], nb[2048]
    from zstd_frame.decode_table). Raises ValueError on any stream that
    does not consume its bits exactly (corrupt frame)."""
    if not streams:
        return []
    smax = max(len(s) for s in streams)
    rmax_need = max(regens)
    sbytes = 64
    while sbytes < smax:
        sbytes *= 2
    rmax = 64
    while rmax < rmax_need:
        rmax *= 2
    # padded rows (zero buf/table, tbits=regen=0) decode to end==0 and
    # are sliced off below — inert under the vmap by construction
    rows = row_bucket(len(streams))
    bufs = np.zeros((rows, sbytes), np.uint8)
    tbits = np.zeros(rows, np.int32)
    for i, s in enumerate(streams):
        if not s or s[-1] == 0:
            raise ValueError("huffman stream missing its end marker")
        bufs[i, : len(s)] = np.frombuffer(s, np.uint8)
        tbits[i] = 8 * (len(s) - 1) + s[-1].bit_length() - 1
    regen_v = np.zeros(rows, np.int32)
    regen_v[: len(streams)] = regens
    tsym = np.zeros((rows, TSIZE), np.uint8)
    tnb = np.zeros((rows, TSIZE), np.int32)
    for i, t in enumerate(tables):
        tsym[i] = t[0]
        tnb[i] = t[1]
    devplane.count_transfer(
        bufs.nbytes + tbits.nbytes + regen_v.nbytes + tsym.nbytes
        + tnb.nbytes,
        "h2d",
    )
    out, end = _decode_streams(
        jnp.asarray(bufs),
        jnp.asarray(tbits),
        jnp.asarray(regen_v),
        jnp.asarray(tsym),
        jnp.asarray(tnb),
        sbytes,
        rmax,
    )
    out = np.asarray(out)
    end = np.asarray(end)
    devplane.count_transfer(out.nbytes + end.nbytes, "d2h")
    if int(np.abs(end).max(initial=0)) != 0:
        bad = int(np.flatnonzero(end)[0])
        raise ValueError(
            f"huffman stream {bad} did not consume its bits exactly "
            f"({int(end[bad])} left)"
        )
    return [out[i, : regens[i]].tobytes() for i in range(len(streams))]
