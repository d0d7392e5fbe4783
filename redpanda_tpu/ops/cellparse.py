"""Shared cell-grid LZ77 parse for the device codecs (lz4, snappy).

The parse reshapes the inherently-sequential greedy LZ77 scan into one
decision per fixed CELL-byte cell, all dense vector work (see
ops/lz4.py module docstring for the full derivation):

  1. nearest earlier 4-gram occurrences via sort-based hash chain,
     walked 3 deep to recover periodic matches;
  2. window verification, in sort order (a candidate is a shift there,
     its bytes ride the sort): a candidate is kept only if it matches
     from its in-cell start to the cell end;
  3. run merging: fully-matched cells continuing the previous cell's
     match at the same offset are absorbed, so periodic data emits one
     long sequence;
  4. literal-run attribution via exclusive cummax.

Both codecs emit (literal run | match to cell end) sequences from the
returned per-cell vectors; only the byte-level emission differs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CELL = 16  # parse grid: one sequence decision per CELL bytes
_HASH_BITS = 16
_TAIL_GUARD = 12  # no match may start near the end (LZ4 spec; safe for snappy)


@jax.custom_batching.custom_vmap
def _sort_by_first(*operands: jax.Array) -> tuple:
    """The vectors sorted by the first of them, whose values are
    distinct (so the sort need not be stable)."""
    return tuple(jax.lax.sort(operands, num_keys=1, is_stable=False))


@_sort_by_first.def_vmap
def _sort_rows_flat(axis_size, in_batched, *operands):
    """A batch of such sorts (the codecs vmap the parse over their
    rows) as ONE flat sort by (row, key). The chip sorts along the rows
    of a [1, n] array 8 x slower than the same n keys without the batch
    dimension (65,536 keys with four payload words on the v5e: 0.85 ms
    against 0.10; 1.07 for 8 rows), and the served path dispatches one
    row."""
    wide = [
        x if batched else jnp.broadcast_to(x, (axis_size, *x.shape))
        for x, batched in zip(operands, in_batched)
    ]
    n = wide[0].shape[1]
    row = jnp.repeat(jnp.arange(axis_size, dtype=jnp.int32), n)
    flat = jax.lax.sort(
        (row, *(x.reshape(-1) for x in wide)), num_keys=2, is_stable=False
    )
    return (
        tuple(x.reshape(axis_size, n) for x in flat[1:]),
        (True,) * len(operands),
    )


def cell_parse(d: jax.Array, v: jax.Array, n: int):
    """d: uint8[n + CELL] zero-padded input, v: scalar valid length.
    Returns per-cell vectors (nc = n // CELL):
      has[nc]       — cell emits a sequence (literal run + match)
      mstart[nc]    — match start position
      offs[nc]      — match backward offset (>= 1)
      mlen[nc]      — match length (covers absorbed following cells)
      lit_start[nc] — literal-run start for this sequence
      lit_len[nc]   — literal-run length
      last_end      — scalar: end of the last match run (final-literal
                      start)
    """
    assert n <= 1 << _HASH_BITS, "positions must fit the sort key's low half"
    nc = n // CELL
    pos = jnp.arange(n, dtype=jnp.uint32)
    d32 = d.astype(jnp.uint32)
    # the little-endian word at every position, CELL - 4 past the end so
    # that the word 4, 8 and 12 bytes on from any position is there too
    # (d carries CELL zeros of guard): static slices, no gather
    ng = n + CELL - 4
    gram = (
        d32[:ng]
        | (d32[1 : ng + 1] << 8)
        | (d32[2 : ng + 2] << 16)
        | (d32[3 : ng + 3] << 24)
    )
    h = (gram[:n] * jnp.uint32(2654435761)) >> (32 - _HASH_BITS)
    # sorted by (hash, position), hash and position 16 bits each in one
    # 32-bit key (64-bit integers are emulated on the TPU): the k-th
    # candidate of a position, its k-th most recent earlier occurrence
    # of the same gram, is k places before it in sort order. The sort
    # carries each position's CELL // 4 words along, so a candidate's
    # bytes are a shift in sort order and never a gather
    words = range(CELL // 4)
    sk, *sw = _sort_by_first(
        h << _HASH_BITS | pos, *(gram[4 * i : 4 * i + n] for i in words)
    )
    sh = sk >> _HASH_BITS
    sp = (sk & ((1 << _HASH_BITS) - 1)).astype(jnp.int32)

    cell_end = (sp // CELL + 1) * CELL
    cap = jnp.minimum(cell_end, v) - sp
    eligible = (cap >= 4) & (cell_end <= v - _TAIL_GUARD)
    # a candidate matches to the cell's end iff its words equal the
    # position's own under the mask of the bytes that lie before the
    # end: clip(cap - 4i, 0, 4) low bytes of word i
    keep = [
        jnp.where(
            cap > 4 * i,
            jnp.uint32(0xFFFFFFFF)
            >> (8 * jnp.clip(4 * (i + 1) - cap, 0, 3)).astype(jnp.uint32),
            jnp.uint32(0),
        )
        for i in words
    ]

    def back(x, k, fill):
        return jnp.concatenate([jnp.full(k, fill, x.dtype), x[:-k]])

    chain = []  # (candidate or -1, verified) for k = 1, 2, 3
    for k in (1, 2, 3):
        # sorted by hash first: the same hash k places back means the
        # same hash all the way
        there = back(sh, k, 1 << _HASH_BITS) == sh
        differ = jnp.zeros(n, jnp.uint32)
        for i in words:
            differ |= (sw[i] ^ back(sw[i], k, 0)) & keep[i]
        chain.append(
            (jnp.where(there, back(sp, k, 0), -1), (differ == 0) & eligible & there)
        )
    (cand1, g1), (cand2, g2), (cand3, g3) = chain
    good = g1 | g2 | g3
    cand = jnp.where(g1, cand1, jnp.where(g2, cand2, cand3))
    # back to position order, both in one word: the positions are a
    # permutation, so a sort by position is the scatter, at a fifth of
    # its time on the chip
    _, placed = _sort_by_first(
        sp, (cand + 1) | (good.astype(jnp.int32) << (_HASH_BITS + 1))
    )
    good = (placed >> (_HASH_BITS + 1)) > 0
    cand = (placed & ((2 << _HASH_BITS) - 1)) - 1

    # one sequence per cell: first in-cell position whose match runs
    # to the cell end
    goodc = good.reshape(nc, CELL)
    has = goodc.any(axis=1)
    j = jnp.argmax(goodc, axis=1).astype(jnp.int32)
    cstart = jnp.arange(nc, dtype=jnp.int32) * CELL
    mstart = cstart + j
    offs = mstart - cand[mstart]

    # merge runs (absorption): see module docstring
    absorb = jnp.concatenate(
        [
            jnp.zeros(1, bool),
            has[1:] & has[:-1] & (j[1:] == 0) & (offs[1:] == offs[:-1]),
        ]
    )
    head = has & ~absorb
    cell_idx = jnp.arange(nc, dtype=jnp.int32)
    boundary = jnp.where(~absorb, cell_idx, nc)
    next_boundary = jnp.concatenate(
        [
            jax.lax.cummin(boundary[::-1])[::-1][1:],
            jnp.full(1, nc, jnp.int32),
        ]
    )
    run_end = jnp.where(head, next_boundary, 0)
    has = head
    mlen = jnp.where(has, (run_end - cell_idx) * CELL - j, 0)

    # literal-run starts: end of the previous match run
    contrib = jnp.where(has, run_end * CELL, 0)
    cmax = jax.lax.cummax(contrib)
    prev_end = jnp.concatenate([jnp.zeros(1, jnp.int32), cmax[:-1]])
    lit_start = prev_end
    lit_len = jnp.where(has, mstart - prev_end, 0)
    last_end = jnp.maximum(cmax[-1], 0)
    return has, mstart, offs, mlen, lit_start, lit_len, last_end


def take_rows(columns, idx: jax.Array) -> list[jax.Array]:
    """[c[idx] for c in columns], vectors of one length, as ONE gather
    of rows. On the TPU a gather costs by the index and hardly by the
    row: 87,109 rows of four words take 0.15 ms where as many single
    elements take 0.65 (v5e, PERF.md section 5), so what one index
    fetches comes as one row, and a single column is doubled so that
    it is a row at all."""
    wide = columns if len(columns) > 1 else [columns[0], columns[0]]
    rows = jnp.stack(wide, axis=1)[idx]
    return [rows[:, k] for k in range(len(columns))]


def cell_of_output(starts: jax.Array, m: int) -> jax.Array:
    """For each of `m` output bytes, the cell whose sequence holds it:
    the last cell whose `starts` (non-decreasing, the prefix sum of the
    sequences' sizes) is at or before the byte. (number of starts <= o)
    - 1, as a count: ones scattered at the starts, then a prefix sum;
    `searchsorted` gives the same by a binary search, which on the TPU
    is a loop of log2(cells) dependent gathers over all `m` bytes."""
    nc = starts.shape[0]
    at_or_before = jnp.cumsum(
        jnp.zeros(m, jnp.int32).at[starts].add(1, mode="drop")
    )
    return jnp.clip(at_or_before - 1, 0, nc - 1)
