"""Per-layer metrics of the one event loop the brokers share, read from
what the loop's own selector saw (`ctx["devplane"]["loop"]`;
redpanda_tpu/observability/trace.py, `LoopLagProbe._hook`): `passes`,
`awake_s` (outside a sleeping `select`), `asleep_s` (inside one),
`wake_late_*` (how far past the timeout asyncio asked for a timer's
wake-up returned, epoll's rounding to whole milliseconds included) and,
in a traced run, `sleeps`, every sleep as two stamps `[start_ns,
end_ns, ...]` on the spans' monotonic clock, with `sleeps_dropped`.
The sleeps are not span records: `idle_attributed_pct` reads the spans
and does not see them.

And the `storage.fsync` span, the flush's syscall where it runs: on the
loop (`path=inline`, kind `run`) or on an executor thread (`path=
executor`, kind `wait`, one span a round of `fds` syscalls).

A program without the probe or the span (the parent of the PR that
added them) has none of these keys or records: every reader then
returns None and says nothing.

Five metrics, one file each under `benchmark/metrics/`, read in every
cell: `loop_busy_pct`, `loop_unspanned_busy_pct`,
`loop_wake_late_p50_ms`, `fsync_ms`, `idle_loop_asleep_pct`. Their
cases are `benchmark/tests/test_looptime.py`, collected into tier-1 by
`tests/test_benchmark_looptime.py`. `benchmark/README.md` does not name
this file yet: it is a file the benchmark already had, and a
`benchmark` PR brings it up to date."""

from __future__ import annotations

import statistics

from benchmark import log
from benchmark import trace as tr
from benchmark.readers import hostspans as hs


def _loop(ctx: dict) -> dict:
    loop = (ctx.get("devplane") or {}).get("loop") or {}
    return loop if loop.get("passes") else {}


def loop_busy_pct(ctx: dict, params: dict):
    """100 x awake / (awake + asleep): how busy the one processor the
    brokers share was over the window. Writes to standard error how much
    of the store's seconds the two cover and the passes a second."""
    loop = _loop(ctx)
    if not loop:
        return None
    awake, asleep = loop["awake_s"], loop["asleep_s"]
    if awake + asleep <= 0:
        return None
    seconds = ctx.get("devplane_s")
    if seconds:
        log(f"looptime: awake {awake:.4f} s + asleep {asleep:.4f} s = "
            f"{100 * (awake + asleep) / seconds:.2f} % of the store's "
            f"{seconds:.4f} s; {loop['passes'] / seconds:.1f} passes a second")
    return 100.0 * awake / (awake + asleep)


def loop_unspanned_busy_pct(ctx: dict, params: dict):
    """100 x (awake - the self time of every `run` span) / awake: the
    loop's work that no span names (the selector's callbacks, the
    scheduler, the protocol codec outside the sites). Negative where a
    `run` span holds a wait. Writes the largest `run` spans to standard
    error."""
    loop = _loop(ctx)
    host = (ctx.get("devplane") or {}).get("host")
    if not loop or not host or loop["awake_s"] <= 0:
        return None
    runs = {n: a["self_s"] for n, a in host.items() if a.get("kind") == "run"}
    if not runs:
        return None
    held = sum(runs.values())
    top = sorted(runs.items(), key=lambda kv: -kv[1])[:6]
    log("looptime: awake {:.4f} s, under run spans {:.4f} s: {}".format(
        loop["awake_s"], held, ", ".join(f"{n} {s:.4f}" for n, s in top)))
    return 100.0 * (loop["awake_s"] - held) / loop["awake_s"]


def loop_wake_late_p50_ms(ctx: dict, params: dict):
    """Median of how far past its requested timeout a sleeping loop
    resumed, over the window's timer wake-ups. Writes the p99, the count
    and the median past the timeout rounded up to whole milliseconds
    (what epoll's rounding does not explain) to standard error."""
    loop = _loop(ctx)
    if not loop.get("wake_late_count"):
        return None
    log(f"looptime: {loop['wake_late_count']} timer wake-ups, late p50 "
        f"{loop['wake_late_p50_ms']:.4f} ms, p99 {loop['wake_late_p99_ms']:.4f} ms; "
        f"past the timeout rounded up to whole ms: p50 "
        f"{loop.get('wake_late_rest_p50_ms', float('nan')):.4f} ms")
    return float(loop["wake_late_p50_ms"])


def fsync_ms(ctx: dict, params: dict):
    """p50 of `storage.fsync` (`hostspans.span_p50_ms`: exact from the
    raw records, else the histogram). Writes how many took each path and
    each path's median to standard error."""
    got = hs.span_p50_ms(ctx, {"span": "storage.fsync"})
    if got is None:
        return None
    by_path: dict = {}
    for s in hs._raw(ctx):
        if s[hs.NAME] == "storage.fsync":
            tags = s[hs.TAGS] or {}
            by_path.setdefault(tags.get("path"), []).append(
                (s[hs.DUR] / 1e6, tags.get("fds", 1)))
    for path, rows in sorted(by_path.items(), key=lambda kv: str(kv[0])):
        log(f"looptime: storage.fsync path={path}: {len(rows)} spans, "
            f"{sum(f for _, f in rows)} syscalls, p50 "
            f"{statistics.median(d for d, _ in rows):.4f} ms")
    return got


# ------------------------------------------------ intervals on one clock
def _pairs(flat: list) -> list:
    return tr.union(list(zip(flat[0::2], flat[1::2])))


def _intersect(a: list, b: list) -> list:
    """Intersection of two sorted, merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def idle_loop_asleep_pct(ctx: dict, params: dict):
    """Of the device's idle time in the traced seconds, the share during
    which the loop sat in `select`: `idle_attributed_pct`'s idle time and
    clock check, against the loop's sleep intervals. None where the
    check fails or the window dropped sleeps. Writes the ten longest
    idle gaps to standard error, each split into asleep, awake under a
    `run` span and awake under none."""
    loop, trace, spans = _loop(ctx), ctx.get("trace"), hs._raw(ctx)
    if not loop.get("sleeps") or loop.get("sleeps_dropped") or trace is None \
            or not spans:
        return None
    fit = hs.align(hs.executions(trace, params["kernels"]),
                   hs.dispatches(spans, params["kernels"]))
    if fit is None:
        return None
    offset = fit[0]
    idle = [(s + offset, e + offset) for s, e in hs.idle_intervals(trace)]
    total = _length(idle)
    if total <= 0:
        return None
    sleeps = _pairs(loop["sleeps"])
    runs = tr.union([(s[hs.START], s[hs.START] + s[hs.DUR])
                     for s in spans if s[hs.KIND] == "run"])
    asleep = _length(_intersect(idle, sleeps))
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
        gap = [(a, b)]
        slept = _length(_intersect(gap, sleeps))
        ran = _length(_intersect(gap, runs))
        ran -= _length(_intersect(_intersect(gap, runs), sleeps))
        log(f"looptime: gap {(b - a) / 1e6:.2f} ms: asleep {slept / 1e6:.2f}, "
            f"awake under a run span {ran / 1e6:.2f}, awake under none "
            f"{(b - a - slept - ran) / 1e6:.2f}")
    return 100.0 * asleep / total
