"""From a profiler trace to numbers: device busy and idle time, time per
compiled program, the operations that took most time and the longest
idle gaps. Works on plain lists of events so that a small recorded trace
(tests/trace_sample.json) checks it without a chip.

An event is [name, start_ns, duration_ns] on the trace's own clock. A
TPU's plane is named `/device:TPU:<n>`; its line `XLA Ops` holds one
event per operation that ran on the device, and `XLA Modules` one per
execution of a compiled program, named after the jitted function
(`jit_heartbeat_tick(...)`)."""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(trace_dir: str) -> dict:
    """The device planes of the newest `.xplane.pb` under `trace_dir`,
    as {"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "span_ns": [first, last], "planes": {plane: {line: events}}} where
    the span covers every plane's events, host threads included, and
    `planes` counts the events of every line, for reading by hand."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: dict = {}
    names: dict = {}
    first, last = None, None
    for plane in data.planes:
        keep = DEVICE_PLANE.match(plane.name) is not None
        lines: dict = {}
        for line in plane.lines:
            evs = []
            names.setdefault(plane.name, {})[line.name] = 0
            for e in line.events:
                names[plane.name][line.name] += 1
                s, d = float(e.start_ns), float(e.duration_ns)
                if first is None or s < first:
                    first = s
                if last is None or s + d > last:
                    last = s + d
                if keep:
                    evs.append([short(e.name), s, d])
            if keep:
                lines[line.name] = evs
        if keep:
            devices[plane.name] = lines
    return {"devices": devices, "span_ns": [first or 0.0, last or 0.0],
            "planes": names}


def short(name: str) -> str:
    """An operation's name without its HLO text: the trace names an
    operation `%fusion.3 = u32[8]{...} fusion(...)`; keep `%fusion.3`."""
    return name.split(" = ", 1)[0][:80]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _busy(lines: dict) -> list[tuple[float, float]]:
    return union([(s, s + d) for _n, s, d in lines.get(OPS_LINE, []) if d > 0])


def busy_and_window(trace: dict) -> tuple[float, float]:
    """(busy_s, window_s): seconds in which an operation ran on a
    device, averaged over the devices in the trace, and the length of
    the traced window."""
    devs = trace["devices"]
    first, last = trace["span_ns"]
    window = (last - first) / 1e9
    if not devs:
        return 0.0, window
    busy = [sum(e - s for s, e in _busy(lines)) for lines in devs.values()]
    return sum(busy) / len(busy) / 1e9, window


def module_seconds(trace: dict, pattern: str) -> tuple[float, int]:
    """(device seconds, executions) of the compiled programs whose name
    matches `pattern`, summed over devices."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for lines in trace["devices"].values():
        for name, _s, d in lines.get(MODULES_LINE, []):
            if rx.search(name):
                total += d
                n += 1
    return total / 1e9, n


def top_ops(trace: dict, k: int = 10) -> list[list]:
    """The device operations that took most time: [[name, seconds]],
    each named `<program> / <operation>` where the program running at
    that time is known."""
    acc: dict[str, float] = {}
    for lines in trace["devices"].values():
        mods = sorted(
            (s, s + d, n) for n, s, d in lines.get(MODULES_LINE, [])
        )
        starts = [m[0] for m in mods]
        for name, s, d in lines.get(OPS_LINE, []):
            i = bisect.bisect_right(starts, s) - 1
            prog = ""
            if i >= 0 and s < mods[i][1]:
                prog = mods[i][2].split("(")[0] + " / "
            key = prog + name
            acc[key] = acc.get(key, 0.0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in top]


def idle_gaps(trace: dict, k: int = 10) -> list[list]:
    """The longest idle gaps on the first device: [[name, seconds]].
    No host span is on the trace's clock yet, so what the host was
    doing in a gap is not known: each is named `unattributed`, with
    the programs that ran before and after it."""
    devs = trace["devices"]
    if not devs:
        return []
    lines = devs[sorted(devs)[0]]
    busy = _busy(lines)
    mods = sorted((s, s + d, n) for n, s, d in lines.get(MODULES_LINE, []))

    def near(t: float, before: bool) -> str:
        best = None
        for s, e, n in mods:
            if before and e <= t + 1:
                best = n
            elif not before and s >= t - 1:
                return n.split("(")[0]
        if not before:
            return "the trace's edge"
        return best.split("(")[0] if best else "the trace's edge"

    gaps = [
        (busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
        for i in range(len(busy) - 1)
    ]
    gaps.sort(reverse=True)
    return [
        [f"unattributed (after {near(a, True)}, before {near(b, False)})",
         g / 1e9]
        for g, a, b in gaps[:k]
    ]


def sample(trace: dict, seconds: float = 0.25) -> dict:
    """The first `seconds` of a trace's device events, with what they
    reduce to, small enough to keep beside the tests."""
    first = trace["span_ns"][0]
    last = first + seconds * 1e9
    devices = {
        plane: {
            line: [e for e in evs if e[1] >= first and e[1] + e[2] <= last]
            for line, evs in lines.items()
        }
        for plane, lines in trace["devices"].items()
    }
    cut = {"devices": devices, "span_ns": [first, last],
           "planes": trace.get("planes", {})}
    busy, window = busy_and_window(cut)
    patterns = ("^jit_heartbeat_tick", "^jit_crc32c_device")
    cut["expected"] = {
        "busy_s": busy, "window_s": window,
        "modules": {p: list(module_seconds(cut, p)) for p in patterns},
    }
    return cut
