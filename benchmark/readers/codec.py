"""Per-layer metrics of a topic whose broker recompresses, read from the
`produce.recompress` spans (kafka/server.py: one a batch the broker
rewrote; tags `codec`, `path` = `device` where the fused CRC and codec
program ran and `host` where the registry's codec did, `bytes_in` the
records section as sent, `bytes_out` as stored) and, for the roofline
share, from the device trace.

A program without the span (the parent of the PR that added it), a topic
that passes batches through, a run that kept no raw records: every
reader here returns None, never 0."""

from __future__ import annotations

from benchmark import codecbytes, log
from benchmark.readers import hostspans as hs


def _recompressed(ctx: dict, params: dict) -> list:
    """The window's records of `params["span"]` that carry both byte
    tags, and `params["path"]` as their `path` if it is given."""
    path = params.get("path")
    return [
        s for s in hs._raw(ctx)
        if s[hs.NAME] == params["span"] and s[hs.TAGS]
        and s[hs.TAGS].get("bytes_in") and s[hs.TAGS].get("bytes_out")
        and (path is None or s[hs.TAGS].get("path") == path)
    ]


def stored_bytes_per_sent_byte(ctx: dict, params: dict):
    """Bytes of records section stored for every byte sent, over the
    window's recompressions: all the `bytes_out` over all the
    `bytes_in`. What `correct` cannot see: a frame of stored blocks
    alone passes the reference's decoder."""
    spans = _recompressed(ctx, params)
    if not spans:
        return None
    return (sum(s[hs.TAGS]["bytes_out"] for s in spans)
            / sum(s[hs.TAGS]["bytes_in"] for s in spans))


def lz4_roofline(ctx: dict, params: dict):
    """Bytes bound it: least time is, for every batch the device
    recompressed inside the traced seconds, its records section read
    once and its stored section written once (codecbytes.
    recompress_bytes of the span's own tags), over the peak HBM rate;
    over the device time of the executions of `params["kernel"]`'s
    program that ran inside those spans. Spans and trace are on two
    clocks: `hostspans.align` finds the offset from the kernels of
    `params["clock"]` ({module pattern: kernel}), and nothing is
    reported where it finds none. A span counts if it lies wholly
    inside the traced seconds, so that its bytes and its device time
    are of the same work."""
    trace = ctx.get("trace")
    spans = _recompressed(ctx, params)
    if trace is None or not spans or not ctx.get("peaks"):
        return None
    execs = hs.executions(trace, params["clock"])
    fit = hs.align(execs, hs.dispatches(hs._raw(ctx), params["clock"]))
    if fit is None:
        return None
    offset = fit[0]
    ran = [(s + offset, s + offset + d) for s, d in execs[params["kernel"]]]
    first, last = (t + offset for t in trace["span_ns"])
    moved = batches = 0
    device_ns = 0.0
    for s in spans:
        lo, hi = s[hs.START], s[hs.START] + s[hs.DUR]
        inside = [e - b for b, e in ran if lo <= b and e <= hi]
        if lo < first or hi > last or not inside:
            continue
        batches += 1
        device_ns += sum(inside)
        moved += codecbytes.recompress_bytes(
            s[hs.TAGS]["bytes_in"], s[hs.TAGS]["bytes_out"])
    if not batches or device_ns <= 0:
        return None
    log(f"codec: {batches} batches recompressed on the device inside the "
        f"traced seconds, {moved} B to move, {device_ns / 1e9:.6f} s of "
        f"{params['kernel']} on the device")
    least_s = moved / float(ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (device_ns / 1e9)
