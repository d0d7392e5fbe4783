"""The seeded corpus behind tests/corpus/cellparse_golden.json.

Every case is a byte string made from fixed seeds; `digests(bucket)`
runs the cases of one width bucket through `cellparse.cell_parse`,
`lz4.compress_chunks` and `snappy.compress_chunks` and gives, for each
case, the sha256 and length of both blocks and of each of the parse's
seven vectors. The golden file holds what the tree before ISSUE 33's
rewrite of the parse gave (commit 07cb742); tests/test_cellparse_golden
holds every later tree to it, case by case.

    python tests/cellparse_corpus.py     # print the digests as JSON
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import sys

import numpy as np

GOLDEN = os.path.join(os.path.dirname(__file__), "corpus", "cellparse_golden.json")
VECTORS = ("has", "mstart", "offs", "mlen", "lit_start", "lit_len", "last_end")
BUCKETS = tuple(256 << i for i in range(9))  # 256 .. 65,536


def _mixed(rng, length: int) -> np.ndarray:
    """Runs of random bytes, of zeros and of a short phrase repeated, in
    seeded order and of seeded lengths: matches of every kind, near and
    far, that begin and end anywhere in a cell."""
    out = np.zeros(length, np.uint8)
    at = 0
    while at < length:
        run = int(rng.integers(1, 200))
        kind = int(rng.integers(0, 4))
        end = min(length, at + run)
        if kind == 0:
            out[at:end] = rng.integers(0, 256, end - at, dtype=np.uint8)
        elif kind == 1:
            phrase = rng.integers(97, 123, int(rng.integers(2, 24)), dtype=np.uint8)
            out[at:end] = np.resize(phrase, end - at)
        elif kind == 2 and at > 64:
            back = int(rng.integers(1, min(at, 4096)))
            for i in range(at, end):  # an overlapping copy, as a decoder makes it
                out[i] = out[i - back]
        at = end
    return out


def _periodic(period: int, length: int) -> np.ndarray:
    return np.resize(np.arange(1, period + 1, dtype=np.uint8), length)


def _cell_templates() -> list[np.ndarray]:
    """The records sections of `omb_100_lz4.half_random_0p8`'s batches:
    39 records of 1 KB, half of every value random, a pool of 8."""
    from benchmark.templates import compressible
    from redpanda_tpu.models.record import RecordBatch

    tpl = compressible.random_share(
        2**31 + 33,
        {"templates": {"count": 8, "random_share": 0.5}, "batch_records": 39},
        {"topics": [{"name": "t", "configs": {"compression.type": "lz4"}}],
         "record_bytes": 1024},
    )
    return [
        np.frombuffer(bytes(RecordBatch.from_kafka_wire(t.wire).body), np.uint8)
        for t in tpl
    ]


@functools.lru_cache(maxsize=1)
def cases() -> dict[str, np.ndarray]:
    rng = np.random.default_rng(33)
    out: dict[str, np.ndarray] = {}
    for i, body in enumerate(_cell_templates()):
        out[f"cell_template_{i}"] = body
    for length in (4096, 65536):
        out[f"zeros_{length}"] = np.zeros(length, np.uint8)
    for period in (3, 17):
        for length in (1000, 65536):
            out[f"period{period}_{length}"] = _periodic(period, length)
    out["random_4096"] = rng.integers(0, 256, 4096, dtype=np.uint8)
    out["random_65536"] = rng.integers(0, 256, 65536, dtype=np.uint8)
    for length in (0, 1, 15, 16, 17, 4095, 4096, 4097, 65536):
        out[f"mixed_len{length}"] = _mixed(rng, length)
        out[f"period3_len{length}"] = _periodic(3, length)
    # a repeat that runs to the last byte: its match has to stop short
    # of the 12-byte tail guard, at every phase of the cell grid
    for tail in range(0, 33, 3):
        head = rng.integers(0, 256, 40, dtype=np.uint8)
        out[f"tail_guard_{tail}"] = np.concatenate(
            [head, np.resize(head[:20], 200 + tail)])
    for n in BUCKETS[1:]:  # every width the fused program takes, 512 up
        out[f"bucket_full_{n}"] = _mixed(rng, n)
        out[f"bucket_short_{n}"] = _mixed(rng, n - 5)
    return out


def bucket_of(length: int) -> int:
    """The width `compress_chunks` pads a chunk of `length` bytes to."""
    n = BUCKETS[0]
    while n < length:
        n *= 2
    return n


def names_of(bucket: int) -> list[str]:
    return [k for k, v in cases().items() if bucket_of(v.size) == bucket]


def _sha(a: np.ndarray) -> dict:
    raw = np.ascontiguousarray(a).tobytes()
    return {"sha256": hashlib.sha256(raw).hexdigest(), "len": len(raw)}


@functools.lru_cache(maxsize=None)
def digests(bucket: int) -> dict[str, dict]:
    """{case: {"lz4": .., "snappy": .., "<vector>": ..}} for the cases
    of one bucket, each {"sha256", "len"}: one call of each program."""
    import jax
    import jax.numpy as jnp

    from redpanda_tpu.ops import cellparse, lz4, snappy

    names = names_of(bucket)
    if not names:
        return {}
    chunks = [cases()[k] for k in names]
    out = {k: {} for k in names}
    for k, blk in zip(names, lz4.compress_chunks(chunks)):
        out[k]["lz4"] = _sha(np.frombuffer(blk, np.uint8))
    for k, blk in zip(names, snappy.compress_chunks(chunks)):
        out[k]["snappy"] = _sha(np.frombuffer(blk, np.uint8))
    batch = np.zeros((len(chunks), bucket + cellparse.CELL), np.uint8)
    for i, c in enumerate(chunks):
        batch[i, : c.size] = c
    valid = np.array([c.size for c in chunks], np.int32)
    parse = jax.jit(jax.vmap(lambda d, v: cellparse.cell_parse(d, v, bucket)))
    vectors = [np.asarray(v) for v in parse(jnp.asarray(batch), jnp.asarray(valid))]
    for i, k in enumerate(names):
        for name, vec in zip(VECTORS, vectors):
            row = vec[i]
            out[k][name] = _sha(row.astype(np.uint8 if row.dtype == bool else np.int32))
    return out


def all_digests() -> dict[str, dict]:
    out: dict[str, dict] = {}
    for bucket in BUCKETS:
        out.update(digests(bucket))
    return {k: out[k] for k in cases()}


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    json.dump(all_digests(), sys.stdout, indent=1, sort_keys=True)
    sys.stdout.write("\n")
