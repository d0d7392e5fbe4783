"""Per-layer metrics that say what a batch costs the one event loop the
brokers share: read from the span store's aggregates alone,
`ctx["devplane"]["host"]` (per span name: `kind`, `count`, `self_s`),
which the store keeps whatever the window's span count. So they read
where the raw records overflowed (`spans_dropped` > 0), as a window over
saturation can make them.

A span's self time is its duration less what its children cover
(redpanda_tpu/observability/trace.py, `Span.finish`), so a sum of self
times over spans of kind `run` counts no instant of the loop twice, as
long as no such span's extent holds a wait; `wait` spans hold no loop
and count for nothing here. One `run` span of the program does hold a
wait: `produce.decode` is recorded from the frame's arrival to the end
of the decode (kafka/server.py), so over saturation it holds the time a
request waited for the loop (a timed window at 800 batches/s read 160 ms
a span under it, 22 ms a batch, where every other `run` span summed to
1.0 ms a batch; PR 34). The metrics name such spans under
`params["awaiting"]` and they count for nothing either, until the
program stamps the wait as a `wait` span. `produce.dispatch` is open
across `await partition.replicate_in_stages`, which can wait for the
replicate batcher's byte budget; the windows of PR 34 read 0.7-1.7 ms a
request under it, an enqueue's worth, and it counts. So the decode of
a produce request is in no share and not in the whole: it is inside
`unspanned_pct`, with the selector and the scheduler (under the knee
`produce.decode` reads 0.2 ms a request of one batch, so at saturation
between 0.01 and 0.2 ms a batch), and the front end's share is
understated by as much.

The numerator is the loop's work inside the window, the denominator the
batches the clients saw acknowledged inside it. They part at the
window's edges: a batch appended in the window and acknowledged after
its close, or sent twice after a not-leader answer, is in the first and
not in the second. The timed runs of PR 34 appended 12 % more batches
than they saw acknowledged (`raft.coalesce`'s count against the acked),
and under a busy-wait of 1 ms a batch 27 % more, which is why the front
end read 1.49 ms a batch there and not 1.1: 22.98 s over 15,450
acknowledged where 19,595 were appended, 1.17 ms each (the wait, the
span's own 0.1 and the waits of calls answered not-leader). No instant
is counted twice. The numbers are the traced run's,
in which the probe waits for every dispatch; `detail.span_self_s` of a
timed run holds the same aggregates without that wait.

None, never 0, where there is no aggregate or no acknowledged batch."""

from __future__ import annotations


def _run_self_s(ctx: dict, params: dict):
    """Seconds of loop held by the `run` spans `params["spans"]` names
    (all of them where it names none) less those `params["awaiting"]`
    names, or None where the store kept no `run` aggregate."""
    host = (ctx.get("devplane") or {}).get("host")
    if not host:
        return None
    names, awaiting = params.get("spans"), params.get("awaiting", ())
    runs = {n: a for n, a in host.items()
            if a.get("kind") == "run" and a.get("count") and n not in awaiting}
    if not runs:
        return None
    return sum(a["self_s"] for n, a in runs.items() if names is None or n in names)


def _acked_batches(ctx: dict) -> float:
    """Batches acknowledged inside the window: the acknowledged payload
    bytes over one template's (every template of a mix has the same)."""
    templates, acked = ctx.get("templates"), ctx.get("acked_payload_bytes")
    if not templates or not acked:
        return 0.0
    return acked / templates[0].payload_bytes


def run_ms_per_acked_batch(ctx: dict, params: dict):
    """Milliseconds of loop a batch acknowledged in the window cost,
    under the `run` spans `params["spans"]` names (every `run` span
    where it names none)."""
    held = _run_self_s(ctx, params)
    batches = _acked_batches(ctx)
    if not held or not batches:
        return None
    return 1e3 * held / batches


def unspanned_pct(ctx: dict, params: dict):
    """Share of the window's seconds in which no `run` span held the
    loop: the loop asleep, or at work no span covers (the selector, the
    protocol codec outside the sites, the scheduler's own time)."""
    held = _run_self_s(ctx, params)
    seconds = ctx.get("devplane_s")
    if not held or not seconds:
        return None
    return 100.0 * (1.0 - held / seconds)
