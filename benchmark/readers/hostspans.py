"""Per-layer metrics read from the program's own spans over the window:
`ctx["devplane"]["host"]` (per span name: kind, count, total and self
seconds, p50 and p99), `["loop"]` (how late a 10 ms timer ran on the
brokers' event loop) and, in a traced run, `["spans"]`, the raw records
`[name, kind, start_ns, dur_ns, id, parent, trace_id, tags]` on the
monotonic clock (redpanda_tpu/observability/trace.py, WindowStore).

A program without the store (the parent of the PR that added it) has
none of these keys: every reader then returns None and says nothing.

The raw records and the profiler's device events are on two clocks.
`align` finds the one offset between them from what both saw: every
execution of an instrumented kernel lies inside the `device.dispatch`
span (dispatch to ready) of its call. Under 99 % contained, nothing
that needs the clock is reported."""

from __future__ import annotations

import re
import statistics

from benchmark import log, trace as tr

NAME, KIND, START, DUR, ID, PARENT, TRACE_ID, TAGS = range(8)
NO_SPAN = "no span open"
CONTAINED_AT_LEAST = 0.99


def _raw(ctx: dict) -> list:
    """The raw span records, if every one of the window was kept."""
    dev = ctx.get("devplane") or {}
    if dev.get("spans_dropped"):
        return []
    return dev.get("spans") or []


def span_p50_ms(ctx: dict, params: dict):
    """Median duration of the spans named `params["span"]`: exact from
    the raw records where the window kept them, else the store's
    histogram (bucket bounds, 6 % apart)."""
    ms = [s[DUR] / 1e6 for s in _raw(ctx) if s[NAME] == params["span"]]
    if ms:
        return statistics.median(ms)
    agg = ((ctx.get("devplane") or {}).get("host") or {}).get(params["span"])
    if not agg or not agg.get("count"):
        return None
    return float(agg["p50_ms"])


def frontend_ms(ctx: dict, params: dict):
    """Median, over the produce requests of the window, of the root
    span less its wait for the ack: frame arrival to handed to raft,
    and ack to response written."""
    waits = {s[PARENT]: s[DUR] for s in _raw(ctx) if s[NAME] == params["wait"]}
    ms = [
        (s[DUR] - waits[s[ID]]) / 1e6
        for s in _raw(ctx)
        if s[NAME] == params["root"] and s[ID] in waits
    ]
    return statistics.median(ms) if ms else None


def loop_lag_p99_ms(ctx: dict, params: dict):
    loop = (ctx.get("devplane") or {}).get("loop")
    if not loop or not loop.get("samples"):
        return None
    return float(loop["lag_p99_ms"])


def compiles_in_window(ctx: dict, params: dict):
    """XLA compiles the program attributed to its kernels since the
    window opened, warm-up and steady phase both: should read 0."""
    compiles = (ctx.get("devplane") or {}).get("compiles")
    if compiles is None:
        return None
    return float(sum(c.get("warmup", 0) + c.get("steady", 0)
                     for c in compiles.values()))


# ------------------------------------------------------ the shared clock
def executions(trace: dict, kernels: dict) -> dict:
    """{kernel: [(start_ns, dur_ns)]} of the compiled programs whose
    name matches a pattern of `kernels` ({pattern: kernel}), on the
    trace's clock, first device."""
    devs = trace["devices"]
    out: dict = {k: [] for k in kernels.values()}
    if not devs:
        return out
    rx = [(re.compile(p), k) for p, k in kernels.items()]
    for name, s, d in devs[sorted(devs)[0]].get(tr.MODULES_LINE, []):
        for r, k in rx:
            if r.search(name):
                out[k].append((s, d))
    return out


def dispatches(spans: list, kernels: dict) -> dict:
    """{kernel: [(start_ns, end_ns)]} of the `device.dispatch` spans,
    on the monotonic clock."""
    out: dict = {k: [] for k in kernels.values()}
    for s in spans:
        if s[NAME] == "device.dispatch":
            k = (s[TAGS] or {}).get("kernel")
            if k in out:
                out[k].append((s[START], s[START] + s[DUR]))
    return out


def align(execs: dict, disp: dict):
    """(offset_ns, contained share, executions) with monotonic = trace
    + offset, or None when no offset lays 99 % of the executions inside
    a dispatch span of their kernel. Every pair of an execution [x,
    x+d) and a span [s, e] of its kernel admits the offsets [s - x,
    e - d - x]; the offset that most pairs admit is the answer (a
    kernel's spans do not overlap, so an execution counts once)."""
    import numpy as np

    lo_all, hi_all, n = [], [], 0
    for k, ex in execs.items():
        if not ex or not disp.get(k):
            continue
        n += len(ex)
        x = np.array([e[0] for e in ex], np.float64)[:, None]
        d = np.array([e[1] for e in ex], np.float64)[:, None]
        s = np.array([p[0] for p in disp[k]], np.float64)[None, :]
        e = np.array([p[1] for p in disp[k]], np.float64)[None, :]
        lo, hi = s - x, e - d - x
        ok = hi >= lo
        lo_all.append(lo[ok])
        hi_all.append(hi[ok])
    if not n or not lo_all:
        return None
    lo = np.sort(np.concatenate(lo_all))
    hi = np.sort(np.concatenate(hi_all))
    if not len(lo):
        return None
    # intervals open at each lo: those begun less those ended before it
    open_at = np.arange(1, len(lo) + 1) - np.searchsorted(hi, lo, "left")
    i = int(np.argmax(open_at))
    share = float(open_at[i]) / n
    if share < CONTAINED_AT_LEAST:
        log(f"hostspans: clock check failed: at best {100 * share:.2f} % of "
            f"{n} executions inside their dispatch spans")
        return None
    # the admitted region runs from this lo to the first hi at or after it
    end = hi[np.searchsorted(hi, lo[i], "left")]
    return (float(lo[i]) + float(end)) / 2.0, share, n


# ----------------------------------------------------- idle attribution
def idle_intervals(trace: dict) -> list:
    """The complement of the `XLA Ops` union over the traced window,
    first device: what `device_idle_pct` calls idle, as intervals on
    the trace's clock."""
    devs = trace["devices"]
    first, last = trace["span_ns"]
    if not devs or last <= first:
        return []
    out, at = [], first
    for s, e in tr._busy(devs[sorted(devs)[0]]):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if last > at:
        out.append((at, last))
    return out


def attribute(idle: list, spans: list) -> tuple[dict, list]:
    """({name: idle ns}, [(gap ns, {name: ns})] a gap) with each idle
    instant given to the innermost span open at it: the open `run` span
    that started last if one is open (it holds the event loop), else
    the open `wait` span that started last, else `no span open`.
    `idle` and `spans` ([name, kind, start, dur, ...]) share a clock."""
    if not idle:
        return {}, []
    lo, hi = idle[0][0], idle[-1][1]
    live = [s for s in spans if s[START] < hi and s[START] + s[DUR] > lo]
    cuts = sorted({lo, hi, *(t for iv in idle for t in iv),
                   *(min(max(s[START], lo), hi) for s in live),
                   *(min(max(s[START] + s[DUR], lo), hi) for s in live)})
    opens = sorted(live, key=lambda s: s[START])
    by_name: dict = {}
    gaps = [(e - s, {}) for s, e in idle]
    open_now: list = []
    oi = gi = 0
    for a, b in zip(cuts, cuts[1:]):
        while oi < len(opens) and opens[oi][START] <= a:
            open_now.append(opens[oi])
            oi += 1
        open_now = [s for s in open_now if s[START] + s[DUR] > a]
        while gi < len(idle) and idle[gi][1] <= a:
            gi += 1
        if gi == len(idle) or idle[gi][0] > a:
            continue  # the device is busy
        best = None
        for s in open_now:  # sorted by start: the last of a kind wins
            if best is None or s[KIND] == "run" or best[KIND] != "run":
                best = s
        name = best[NAME] if best is not None else NO_SPAN
        by_name[name] = by_name.get(name, 0.0) + (b - a)
        g = gaps[gi][1]
        g[name] = g.get(name, 0.0) + (b - a)
    return by_name, gaps


def idle_attributed_pct(ctx: dict, params: dict):
    """Of the device's idle time in the traced seconds, the share
    during which at least one program span was open, after the clock
    check. Writes the idle seconds by innermost open span and the ten
    longest gaps to standard error."""
    trace, spans = ctx.get("trace"), _raw(ctx)
    if trace is None or not spans:
        return None
    fit = align(executions(trace, params["kernels"]),
                dispatches(spans, params["kernels"]))
    if fit is None:
        return None
    offset, share, n = fit
    log(f"hostspans: clock offset {offset:.0f} ns lays {100 * share:.2f} % of "
        f"{n} executions inside their dispatch spans")
    idle = [(s + offset, e + offset) for s, e in idle_intervals(trace)]
    by_name, gaps = attribute(idle, spans)
    total = sum(by_name.values())
    if total <= 0:
        return None
    for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1]):
        log(f"hostspans: idle {ns / 1e9:.4f} s ({100 * ns / total:.1f} %) "
            f"under {name}")
    for ns, names in sorted(gaps, key=lambda g: -g[0])[:10]:
        parts = ", ".join(f"{k} {v / 1e6:.2f}" for k, v in
                          sorted(names.items(), key=lambda kv: -kv[1])[:4])
        log(f"hostspans: gap {ns / 1e6:.2f} ms: {parts}")
    return 100.0 * (total - by_name.get(NO_SPAN, 0.0)) / total
