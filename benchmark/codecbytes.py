"""Bytes a recompression has to move, from the work and not from any
program's shapes. Kept with the benchmark, beside opsbytes.py, so that no
later PR can change what `lz4_roofline` is measured against: a rewrite
that batches rows, pads less or splits the program is read against the
same bytes."""

from __future__ import annotations


def recompress_bytes(bytes_in: int, bytes_out: int) -> int:
    """Least bytes one broker-side recompression moves: the records
    section as it was sent (`bytes_in`, the `produce.recompress` span's
    tag) read once, and the section as it is stored (`bytes_out`: the
    codec's frame) written once. The 40-byte CRC prefix, the padding to
    a power of two, the cell guard and the block-size bound the fused
    program uploads and reads back are the program's own cost; bytes
    bound it, the match search is compares over what was read."""
    return int(bytes_in) + int(bytes_out)
