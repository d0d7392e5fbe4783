"""Per-layer metrics read from the tags of the program's spans: the raw
records `[name, kind, start_ns, dur_ns, id, parent, trace_id, tags]` a
traced run keeps (readers/hostspans.py says where they come from).

A tag is what its site knew at no cost when the span was recorded: how
many rows a fold carried (`tick.upload`: `rows`, `replies`, `bucket`),
how many produce requests were open when one arrived (`kafka.produce`:
`open`). A program that records the span without the tag (the parent of
the PR that added it), a run that kept no raw records or dropped some:
every reader here then returns None, never 0."""

from __future__ import annotations

import statistics

from benchmark.readers.hostspans import NAME, TAGS, _raw


def _tagged(ctx: dict, params: dict) -> list:
    """The values of `params["tag"]` on the window's records of
    `params["span"]` that carry it."""
    span, tag = params["span"], params["tag"]
    return [
        s[TAGS][tag] for s in _raw(ctx)
        if s[NAME] == span and s[TAGS] and tag in s[TAGS]
    ]


def tag_mean(ctx: dict, params: dict):
    """Mean of the tag over the window's spans. Not the median: these
    tags are small whole numbers, and the median of such stays on one
    of them until more than half the spans have moved."""
    values = _tagged(ctx, params)
    return float(statistics.fmean(values)) if values else None


def spans_per_acked_batch(ctx: dict, params: dict):
    """Spans carrying the tag, over the batches acknowledged in the
    window: the acknowledged payload bytes over one template's (every
    template of a traffic mix has the same records)."""
    n = len(_tagged(ctx, params))
    templates = ctx.get("templates")
    acked = ctx.get("acked_payload_bytes")
    if not n or not templates or not acked:
        return None
    return n / (acked / templates[0].payload_bytes)
