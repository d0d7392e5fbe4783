"""The readers of the event loop's own counters and of `storage.fsync`
(readers/looptime.py), on hand-made contexts; collected into tier-1 by
tests/test_benchmark_looptime.py."""

import json
import os

import pytest

from benchmark import run
from benchmark.readers import looptime as lt
from benchmark.tests.conftest import ROOT

NEW_METRICS = ("loop_busy_pct", "loop_unspanned_busy_pct", "loop_wake_late_p50_ms",
               "fsync_ms", "idle_loop_asleep_pct")
KERNELS = {"^jit_heartbeat_tick": "quorum.heartbeat_tick",
           "^jit_crc32c_device": "crc32c.device"}
MS = 1_000_000
OFFSET = 10**12  # the spans' clock ahead of the trace's


def span(name, kind, start, dur, **tags):
    return [name, kind, start, dur, 0, 0, 0, tags or None]


def loop_digest(**over):
    return {"samples": 4, "lag_p50_ms": 1.0, "lag_p99_ms": 2.0, "lag_max_ms": 2.0,
            "passes": 10, "awake_s": 1.0, "asleep_s": 3.0, "wake_late_p50_ms": 0.75,
            "wake_late_p99_ms": 1.5, "wake_late_count": 3,
            "wake_late_rest_p50_ms": 0.05, "sleeps_dropped": 0, **over}


def by_hand():
    """benchmark/tests/trace_by_hand.json (device busy 0-2 ms and 4-4.5 ms
    of a 10 ms window, so idle 2-4 and 4.5-10) with the dispatch spans
    of its two executions on a clock OFFSET ahead, the loop asleep
    2.5-3.5 and 5-9 ms of the idle time and 0.5-1.5 ms of the busy, and
    one `run` span at 9-9.5 ms."""
    with open(os.path.join(ROOT, "benchmark", "tests", "trace_by_hand.json")) as f:
        trace = json.load(f)
    spans = [
        span("device.dispatch", "run", OFFSET - MS // 10, 22 * MS // 10,
             kernel="quorum.heartbeat_tick"),
        span("device.dispatch", "run", OFFSET + 39 * MS // 10, 7 * MS // 10,
             kernel="crc32c.device"),
        span("fetch.read", "run", OFFSET + 9 * MS, MS // 2),
        span("kafka.fetch", "wait", OFFSET, 10 * MS),
    ]
    sleeps = [OFFSET + t * MS // 10 for t in (5, 15, 25, 35, 50, 90)]
    return {"trace": trace, "devplane": {
        "spans": spans, "spans_dropped": 0, "loop": loop_digest(sleeps=sleeps)}}


def test_idle_loop_asleep_pct_on_the_trace_by_hand(capfd):
    got = lt.idle_loop_asleep_pct(by_hand(), {"kernels": KERNELS})
    # asleep 1 + 4 ms of the 7.5 ms idle; the busy time's sleep is not counted
    assert got == pytest.approx(100 * 5 / 7.5)
    err = capfd.readouterr().err
    assert err.count("looptime: gap ") == 2
    # the dispatches overhang the idle time by 0.1 ms at each edge
    assert "gap 5.50 ms: asleep 4.00, awake under a run span 0.60, " \
           "awake under none 0.90" in err
    assert "gap 2.00 ms: asleep 1.00, awake under a run span 0.20, " \
           "awake under none 0.80" in err


def test_idle_loop_asleep_pct_refuses_what_it_cannot_check():
    ctx = by_hand()
    ctx["devplane"]["loop"]["sleeps_dropped"] = 1
    assert lt.idle_loop_asleep_pct(ctx, {"kernels": KERNELS}) is None
    ctx = by_hand()
    for s in ctx["devplane"]["spans"]:  # a clock no offset fits
        if s[0] == "device.dispatch" and s[7]["kernel"] == "crc32c.device":
            s[2] += 50 * MS
    assert lt.idle_loop_asleep_pct(ctx, {"kernels": KERNELS}) is None
    ctx = by_hand()
    del ctx["devplane"]["loop"]["sleeps"]  # a timed run keeps none
    assert lt.idle_loop_asleep_pct(ctx, {"kernels": KERNELS}) is None


def test_loop_busy_and_the_loop_s_unspanned_work(capfd):
    host = {"raft.append": {"kind": "run", "count": 4, "self_s": 0.25},
            "fetch.read": {"kind": "run", "count": 2, "self_s": 0.25},
            "kafka.produce": {"kind": "wait", "count": 9, "self_s": 5.0}}
    ctx = {"devplane": {"loop": loop_digest(), "host": host}, "devplane_s": 4.0}
    assert lt.loop_busy_pct(ctx, {}) == 25.0
    assert "= 100.00 % of the store's 4.0000 s; 2.5 passes a second" in \
        capfd.readouterr().err
    assert lt.loop_unspanned_busy_pct(ctx, {}) == 50.0
    # a run span that holds a wait reads as more than the loop's work
    host["produce.decode"] = {"kind": "run", "count": 1, "self_s": 1.5}
    assert lt.loop_unspanned_busy_pct(ctx, {}) == -100.0
    assert "produce.decode 1.5000" in capfd.readouterr().err


def test_the_wake_late_median_and_the_fsync_median(capfd):
    ctx = {"devplane": {"loop": loop_digest()}}
    assert lt.loop_wake_late_p50_ms(ctx, {}) == 0.75
    assert "rounded up to whole ms: p50 0.0500 ms" in capfd.readouterr().err
    assert lt.loop_wake_late_p50_ms(
        {"devplane": {"loop": loop_digest(wake_late_count=0)}}, {}) is None
    spans = [span("storage.fsync", "run", 0, d * MS, path="inline", fds=1)
             for d in (0.1, 0.2, 0.3)]
    spans.append(span("storage.fsync", "wait", 0, 0.9 * MS, path="executor", fds=3))
    host = {"storage.fsync": {"kind": "run", "count": 4, "p50_ms": 0.25}}
    ctx = {"devplane": {"spans": spans, "spans_dropped": 0, "host": host}}
    assert lt.fsync_ms(ctx, {}) == pytest.approx(0.25)
    err = capfd.readouterr().err
    assert "path=inline: 3 spans, 3 syscalls, p50 0.2000 ms" in err
    assert "path=executor: 1 spans, 3 syscalls, p50 0.9000 ms" in err
    # raw records dropped: the histogram's median
    ctx["devplane"]["spans_dropped"] = 1
    host["storage.fsync"]["p50_ms"] = 0.3
    assert lt.fsync_ms(ctx, {}) == 0.3


@pytest.mark.parametrize("devplane", [
    {}, {"enabled": True, "kernels": {}},
    {"host": {}, "loop": {"samples": 0, "lag_p50_ms": 0.0, "lag_p99_ms": 0.0,
                          "lag_max_ms": 0.0}, "spans": [], "spans_dropped": 0}])
def test_the_readers_say_nothing_of_a_program_without_the_probe(devplane):
    """What the parent of the PR that added the probe hands them: a
    `loop` digest of the lag timer alone and no `storage.fsync`."""
    with open(os.path.join(ROOT, "benchmark", "tests", "trace_by_hand.json")) as f:
        trace = json.load(f)
    for tr in (None, trace):
        ctx = {"devplane": devplane, "trace": tr, "devplane_s": 40.0}
        for name in NEW_METRICS:
            spec = run.load_json(run.HERE, "metrics", name + ".json")
            assert run.resolve(spec["reader"], "readers")(ctx, spec["params"]) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_five_are_appended_and_every_cell_reads_them(name):
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-5:] == list(NEW_METRICS)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert "workloads" not in entry
    spec = run.load_json(run.HERE, "metrics", name + ".json")
    assert spec["name"] == name and spec["reader"] == f"looptime.{name}"
    for cell in manifest["workloads"]:
        assert name in {m["name"] for m in run.load_cell(cell["name"])["per_layer"]}
