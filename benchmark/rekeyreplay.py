"""The plain reference of a re-keying exactly-once pipeline: what a
`read_committed` consumer of the output must be handed, and what the
group must have committed, written out from the semantics of a Kafka
Streams topology `stream(source).selectKey(...).to(sink)` under
`processing.guarantee=exactly_once_v2` (KIP-98, KIP-447) and from the
logs alone. It imports the log's replay and the consumer's filter
(benchmark/txreplay.py) and nothing of the program or the generator.

The pipeline reads every record of the source, gives it as its key its
value's key field (the value's first 16 bytes) and writes the record,
value unchanged, to the sink partition that Kafka's default partitioner
names for that key: murmur2(key) & 0x7fffffff modulo the sink's
partitions. It commits the group's offsets past what it read inside the
same transaction; a transaction that aborts leaves neither its records
nor its offsets, and the application reads those records again. A
record is known by its value: every value carries its own event id, so
no two source records are alike. After the drain that is:

  exactly_once  every source record appears once, and once only, among
                the records a `read_committed` consumer of the sink sees
                (the visible batches of each sink partition's
                `txreplay.replay`); no sink record is one the source
                does not hold; the group's committed offset of every
                source partition is its end;
  partitioning  each copy's key is its value's key field, and it lies in
                the partition that key hashes to;
  order         of the copies of one source partition in one sink
                partition, each is of a later source record than the
                one before;
  atomicity     no transaction is left open in either log, and what the
                consumer's filter (`txreplay.Filter`, given the aborted
                transactions the log holds) hands on of each sink
                partition is that replay's visible sequence; a consumer
                that was handed anything else broke it (`departures`);
  idempotence   every producer's sequences are continuous in both logs.

Each departure is named, its rule first ("order: ..."), with the source
records it touches (`wrong`), or, where it touches none, the sink
partition (`wrong_sinks`). `placed` says in which sink batch each
source record's copy lies, so that a generator can say when a source
batch was whole in its consumers' hands.
"""

from __future__ import annotations

import struct
from typing import NamedTuple

from benchmark.txreplay import Filter, _varint, head_of, replay

KEY_FIELD = 16          # bytes of the value that are the new key
_RECORDS_AT = 61
_COUNT_AT = 57


def murmur2(data: bytes) -> int:
    """Kafka's Utils.murmur2 (seed 0x9747b28c), as its default
    partitioner hashes a key."""
    m, mask = 0x5BD1E995, 0xFFFFFFFF
    n = len(data)
    h = (0x9747B28C ^ n) & mask
    for i in range(0, n - n % 4, 4):
        k = int.from_bytes(data[i:i + 4], "little")
        k = (k * m) & mask
        k ^= k >> 24
        k = (k * m) & mask
        h = ((h * m) & mask) ^ k
    tail = data[n - n % 4:]
    signed = [b - 256 if b >= 128 else b for b in tail]   # Java bytes
    if len(tail) == 3:
        h ^= (signed[2] << 16) & mask
    if len(tail) >= 2:
        h ^= (signed[1] << 8) & mask
    if len(tail) >= 1:
        h ^= signed[0] & mask
        h = (h * m) & mask
    h ^= h >> 13
    h = (h * m) & mask
    return h ^ (h >> 15)


def partition_for(key: bytes, partitions: int) -> int:
    """Kafka's default partitioner for a keyed record."""
    return (murmur2(key) & 0x7FFFFFFF) % partitions


def records_of(batch: bytes) -> list[tuple[int, bytes | None, bytes | None]]:
    """(offset, key, value) of every record of a whole uncompressed v2
    batch, from Kafka's record format."""
    base = head_of(batch).base
    (count,) = struct.unpack_from(">i", batch, _COUNT_AT)
    out, pos = [], _RECORDS_AT
    for _ in range(count):
        length, pos = _varint(batch, pos)
        end = pos + length
        pos += 1                                   # attributes
        _ts, pos = _varint(batch, pos)
        delta, pos = _varint(batch, pos)
        fields = []
        for _field in range(2):                    # key, value
            n, pos = _varint(batch, pos)
            fields.append(None if n < 0 else batch[pos:pos + n])
            pos += max(0, n)
        out.append((base + delta, fields[0], fields[1]))
        pos = end
    return out


class Expected(NamedTuple):
    handed: dict[int, list[int]]    # sink partition -> bases a read_committed reader is handed
    aborted: dict[int, list[int]]   # sink partition -> bases an ABORT marker closed
    placed: dict[tuple[int, int], tuple[int, int]]  # (source p, offset) -> (sink q, base)
    ends: dict[int, int]            # source partition -> its end offset
    breaks: list[str]               # each departure, its rule first
    wrong: set[tuple[int, int]]     # source records a departure touches
    wrong_sinks: set[int]           # sink partitions of a departure no source record carries


def expected(sources: dict[int, list[bytes]], source_starts: dict[int, int],
             sinks: dict[int, list[bytes]], committed: dict[int, int | None],
             partitions: int) -> Expected:
    """`sources[p]` is source partition p's log from `source_starts[p]`
    and `sinks[q]` sink partition q's from where the copies begin, each
    whole, in offset order and read `read_uncommitted`; `committed[p]`
    is the group's committed offset for p (None: none); `partitions`
    is the sink's partition count."""
    breaks: list[str] = []
    wrong: set[tuple[int, int]] = set()
    wrong_sinks: set[int] = set()
    by_value: dict[bytes, tuple[int, int]] = {}
    ends: dict[int, int] = {}
    for p, log in sorted(sources.items()):
        out = replay(log)
        _log_breaks(out, f"source {p}", breaks)
        data = [b for b in log if not head_of(b).control]
        ends[p] = head_of(data[-1]).last + 1 if data else source_starts[p]
        if committed.get(p) != ends[p]:
            breaks.append(f"exactly_once: the group committed {committed.get(p)} for "
                          f"source {p}, which ends at {ends[p]}")
            wrong.update((p, o) for o in range(source_starts[p], ends[p]))
        visible = set(out.visible)
        for batch in data:
            if head_of(batch).base not in visible:
                continue
            for offset, _key, value in records_of(batch):
                if value in by_value:
                    breaks.append(f"exactly_once: source {p} at {offset} holds the value "
                                  f"of source {by_value[value][0]} at {by_value[value][1]}")
                    wrong.update(((p, offset), by_value[value]))
                    continue
                by_value[value] = (p, offset)

    seen: dict[tuple[int, int], list[tuple[int, int]]] = {}
    handed: dict[int, list[int]] = {}
    aborted: dict[int, list[int]] = {}
    for q, log in sorted(sinks.items()):
        out = replay(log)
        if _log_breaks(out, f"sink {q}", breaks):
            wrong_sinks.add(q)
        handed[q], aborted[q] = out.visible, out.aborted
        if _consumer_view(log, out) != out.visible:
            breaks.append(f"atomicity: sink {q}: the consumer's filter hands on another "
                          "sequence than the log's replay")
            wrong_sinks.add(q)
        last: dict[int, int] = {}   # source partition -> offset of its last copy here
        by_base = {head_of(b).base: b for b in log}
        for base in out.visible:
            for _offset, key, value in records_of(by_base[base]):
                src = by_value.get(value)
                if src is None:
                    breaks.append(f"exactly_once: sink {q} at {base} holds a record no "
                                  "source holds")
                    wrong_sinks.add(q)
                    continue
                seen.setdefault(src, []).append((q, base))
                p, offset = src
                want = value[:KEY_FIELD]
                if key != want:
                    breaks.append(f"partitioning: sink {q} at {base}: the copy of source "
                                  f"{p} at {offset} is keyed {key!r}, its key field is {want!r}")
                    wrong.add(src)
                elif partition_for(want, partitions) != q:
                    breaks.append(f"partitioning: sink {q} at {base}: the copy of source "
                                  f"{p} at {offset} belongs in partition "
                                  f"{partition_for(want, partitions)}")
                    wrong.add(src)
                if p in last and offset < last[p]:
                    breaks.append(f"order: sink {q} at {base}: source {p}'s record at "
                                  f"{offset} after its record at {last[p]}")
                    wrong.update((src, (p, last[p])))
                last[p] = max(offset, last.get(p, offset))

    placed: dict[tuple[int, int], tuple[int, int]] = {}
    for src in sorted(by_value.values()):
        copies = seen.get(src, [])
        if not copies:
            breaks.append(f"exactly_once: source {src[0]} at {src[1]} has no committed copy")
            wrong.add(src)
            continue
        placed[src] = copies[0]
        if len(copies) > 1:
            breaks.append(f"exactly_once: source {src[0]} at {src[1]} has {len(copies)} "
                          f"committed copies (sink, base): {copies[:4]}: a second copy")
            wrong.add(src)
    return Expected(handed, aborted, placed, ends, breaks, wrong, wrong_sinks)


def _log_breaks(out, which: str, breaks: list[str]) -> bool:
    """The idempotence and atomicity rules one log's replay breaks."""
    n = len(breaks)
    for producer_id, base, want, found in out.sequence_breaks:
        breaks.append(f"idempotence: {which} at {base}: producer {producer_id} "
                      f"stored sequence {found} where {want} follows")
    for base in out.open:
        breaks.append(f"atomicity: {which} at {base}: no marker closed it")
    return len(breaks) > n


def _consumer_view(log: list[bytes], out) -> list[int]:
    """What `Filter` hands on of a whole log, given the aborted
    transactions the log holds, each at its first offset, as a broker
    names them in a `read_committed` fetch response: data of a
    transaction still open is past the LSO and never handed on."""
    first: dict[int, int] = {}   # aborted base -> its transaction's first offset
    opened: dict[int, int] = {}  # producer id -> first offset of its open transaction
    named: list[tuple[int, int]] = []
    aborted, still_open = set(out.aborted), set(out.open)
    for batch in log:
        h = head_of(batch)
        if h.control:
            if h.producer_id in opened:
                begun = opened.pop(h.producer_id)
                if first.get(begun):
                    named.append((h.producer_id, begun))
            continue
        if h.transactional and h.producer_id not in opened:
            opened[h.producer_id] = h.base
            first[h.base] = h.base in aborted
    flt = Filter(named)
    return [head_of(b).base for b in log
            if flt.take(b) == "deliver" and head_of(b).base not in still_open]


def departures(want: Expected, q: int, handed: list[int]) -> list[str]:
    """What a `read_committed` consumer of sink partition `q` was handed
    (base offsets, in order), against what it had to be."""
    if handed == want.handed.get(q, []):
        return []
    aborted = set(want.aborted.get(q, [])) & set(handed)
    if aborted:
        return [f"atomicity: sink {q}: the aborted batch at {min(aborted)} was handed on"]
    return [f"exactly_once: sink {q}: consumers were handed {handed[:8]}..., the "
            f"replay's visible sequence is {want.handed.get(q, [])[:8]}..."]
