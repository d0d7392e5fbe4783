"""Per-layer metrics read from the profiler trace of the traced run."""

from __future__ import annotations

from benchmark import opsbytes, trace as tr


def _peak(ctx: dict) -> float:
    return float(ctx["peaks"]["hbm_bytes_per_s"])


def device_idle_pct(ctx: dict, params: dict):
    if ctx.get("trace") is None:
        return None
    busy, window = tr.busy_and_window(ctx["trace"])
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)


def tick_roofline(ctx: dict, params: dict):
    """Bytes bound it: least time is the bytes one fold must read and
    write (opsbytes.tick_bytes at the lane capacity and slots in use,
    the reply window at its smallest bucket) over the peak HBM rate."""
    if ctx.get("trace") is None:
        return None
    secs, n = tr.module_seconds(ctx["trace"], params["module"])
    if n == 0 or secs <= 0:
        return None
    lanes = ctx["lanes"]
    least = n * opsbytes.tick_bytes(lanes["capacity"], lanes["slots"]) / _peak(ctx)
    return 100.0 * least / secs


def crc_roofline(ctx: dict, params: dict):
    """Bytes bound it: least time is every crc-covered byte of the
    batches that were fetched in the traced seconds, as long as they
    were stored (what a broker compressed is shorter than what was
    sent), read once (opsbytes.crc_bytes of one row of that length a
    batch; the padding the program adds is its own cost), over the peak
    HBM rate."""
    if ctx.get("trace") is None or not ctx.get("fetched_in_trace"):
        return None
    secs, n = tr.module_seconds(ctx["trace"], params["module"])
    if n == 0 or secs <= 0:
        return None
    least = sum(
        opsbytes.crc_bytes(1, stored - opsbytes.BODY_AT)
        for stored in ctx["fetched_in_trace"]
    ) / _peak(ctx)
    return 100.0 * least / secs
