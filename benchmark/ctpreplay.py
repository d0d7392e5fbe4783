"""The plain reference of a consume-transform-produce pipeline: what a
`read_committed` consumer of the output must be handed, and what the
group must have committed, written out from the pipeline's semantics
(Kafka's TransactionalMessageCopier, KIP-98, KIP-447) and from the logs
alone. It imports nothing of the program and nothing of the generator.

The pipeline copies every batch of source partition p, in offset order,
into sink partition p, each copy under a transaction that also commits
the group's offset past the batches it copied; a transaction that
aborts leaves neither its copies nor its offset, and the member copies
those batches again. After the drain that is, for each partition:

  the sink's visible sequence (txreplay.replay of the whole log, read
  `read_uncommitted`) holds exactly one copy of every source batch, in
  the source's order: the i-th visible copy has the i-th source batch's
  records section, byte for byte, and there are as many of the one as of
  the other (no copy lost, none twice);

  every producer's sequences are continuous in both logs, and no
  transaction of the sink is left open;

  the group's committed offset is the source's end: not short of it
  (the member would copy again what it copied) and not past it (an
  offset committed past its copy);

  a `read_committed` consumer was handed that visible sequence and no
  other: no copy of an aborted transaction (`departures`).

`expected` gives, besides the rules a partition breaks, the base offset
in the sink of each source batch's copy for as long as the two sequences
agree, so that a generator can say when each source batch's copy
reached a consumer.
"""

from __future__ import annotations

from typing import NamedTuple

from benchmark.reference import RECORDS_AT
from benchmark.txreplay import head_of, replay


class Expected(NamedTuple):
    sources: list[int]     # the source's base offsets, in order
    handed: list[int]      # sink base offsets a read_committed reader is handed, in order
    aborted: list[int]     # sink base offsets of copies an ABORT marker closed
    copies: list[int]      # the sink base offset of the i-th source batch's copy
    end: int               # the source's end offset
    breaks: list[str]      # each rule broken, named first ("exactly_once: ...")


def expected(source: list[bytes], source_start: int, sink: list[bytes],
             committed: int | None) -> Expected:
    """One partition. `source` is the source partition's log from
    `source_start` and `sink` the sink partition's from where the copies
    begin, each whole and in offset order; `committed` is the group's
    committed offset for the source partition (None: none)."""
    breaks: list[str] = []
    src = [b for b in source if not head_of(b).control]
    end = head_of(src[-1]).last + 1 if src else source_start
    if committed != end:
        breaks.append(f"exactly_once: the group committed {committed}, "
                      f"the source ends at {end}")
    for log, which in ((replay(source), "source"), (replay(sink), "sink")):
        for producer_id, base, want, found in log.sequence_breaks:
            breaks.append(f"idempotence: {which} at {base}: producer {producer_id} "
                          f"stored sequence {found} where {want} follows")
        for base in log.open:
            breaks.append(f"atomicity: {which} at {base}: no marker closed it")
    out = replay(sink)
    by_base = {head_of(b).base: b for b in sink}
    copies: list[int] = []
    for base, batch in zip(out.visible, src):
        if by_base[base][RECORDS_AT:] != batch[RECORDS_AT:]:
            breaks.append(f"exactly_once: the copy at {base} is not of the source "
                          f"batch at {head_of(batch).base}")
            break
        copies.append(base)
    n, m = len(out.visible), len(src)
    if n > m:
        breaks.append(f"exactly_once: {n} committed copies of {m} source batches: "
                      "a second copy")
    elif n < m:
        breaks.append(f"exactly_once: {n} committed copies of {m} source batches: the "
                      f"source batch at {head_of(src[n]).base} has no committed copy")
    return Expected([head_of(b).base for b in src], out.visible, out.aborted, copies,
                    end, breaks)


def departures(want: Expected, handed: list[int]) -> list[str]:
    """What a `read_committed` consumer was handed (sink base offsets,
    in order), against what it had to be."""
    if handed == want.handed:
        return []
    aborted = set(want.aborted) & set(handed)
    if aborted:
        return [f"atomicity: the aborted copy at {min(aborted)} was handed on"]
    return [f"exactly_once: consumers were handed {handed[:8]}..., the replay's "
            f"visible sequence is {want.handed[:8]}..."]
