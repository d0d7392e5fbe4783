"""The readers of the program's spans, on hand-made span records, trace
events and dispatch lists; and a traced --cpu-dry-run of each cell,
which has to print the span metrics."""

import json
import os
import random

import pytest

from benchmark.readers import hostspans as hs
from benchmark.tests.conftest import ROOT, dry_run

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
KERNELS = {"^jit_heartbeat_tick": "quorum.heartbeat_tick",
           "^jit_crc32c_device": "crc32c.device"}
SPAN_METRICS = {"frontend_ms", "coalesce_wait_ms", "log_flush_ms",
                "quorum_wait_ms", "fetch_verify_ms", "loop_lag_p99_ms",
                "compiles_in_window"}


def span(name, kind, start, dur, sid=0, parent=0, trace_id=0, **tags):
    return [name, kind, start, dur, sid, parent, trace_id, tags or None]


def made_up(offset, n=200, stray=0):
    """A trace and the dispatch spans of the same `n` calls, the span
    clock `offset` ns ahead of the trace's: executions of 0.2 ms at
    irregular times, each inside a dispatch span of 1.0-1.9 ms that
    began 0.5 ms before it; twice as many spans as executions, since
    the spans cover the window and the trace its middle. `stray`
    executions have no span."""
    mods, ops, spans = [], [], []
    t = 5_000_000.0
    rng = random.Random(25)
    for i in range(3 * n):
        t += 7_000_000 + rng.randrange(6_000_000)
        kern, mod = (("quorum.heartbeat_tick", "jit_heartbeat_tick(1)")
                     if i % 3 else ("crc32c.device", "jit_crc32c_device(2)"))
        spans.append(span("device.dispatch", "run", t - 500_000 + offset,
                          1_000_000 + (i % 10) * 100_000, kernel=kern))
        if n <= i < 2 * n:
            mods.append([mod, t, 200_000.0])
            ops.append(["%fusion.1", t, 200_000.0])
    for j in range(stray):
        mods.append(["jit_heartbeat_tick(1)", mods[0][1] - 3_000_000 * (j + 1),
                     100_000.0])
    trace = {"devices": {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}},
             "span_ns": [mods[0][1] - 1e6, mods[n - 1][1] + 1e6]}
    return trace, spans


@pytest.mark.parametrize("offset", [0, 123_456_789_012, -98_765_432])
def test_alignment_recovers_a_planted_offset(offset):
    trace, spans = made_up(offset)
    got = hs.align(hs.executions(trace, KERNELS), hs.dispatches(spans, KERNELS))
    assert got is not None
    found, share, n = got
    assert (share, n) == (1.0, 200)
    # any offset inside the slack every pair leaves is right: the
    # execution began 0.5 ms into a span at least 1.0 ms long
    assert -500_000 <= found - offset <= 300_000


def test_alignment_refuses_under_99_percent():
    trace, spans = made_up(10**12, stray=3)  # 200 of 203 contained
    execs, disp = hs.executions(trace, KERNELS), hs.dispatches(spans, KERNELS)
    assert hs.align(execs, disp) is None
    ctx = {"trace": trace, "devplane": {"spans": spans, "spans_dropped": 0}}
    assert hs.idle_attributed_pct(ctx, {"kernels": KERNELS}) is None
    # nothing to align at all
    assert hs.align({"quorum.heartbeat_tick": []}, disp) is None
    assert hs.align(execs, {}) is None


def test_idle_is_given_to_the_innermost_open_span():
    # idle 0-100 and 150-200; a wait span 10-90 with a run span 30-50
    # nested in it and a later wait 60-80; a run span over busy time
    idle = [(0.0, 100.0), (150.0, 200.0)]
    spans = [
        span("t.wait", "wait", 10, 80, 1),
        span("t.run", "run", 30, 20, 2, 1),
        span("t.wait2", "wait", 60, 20, 3, 1),
        span("t.busy", "run", 110, 30, 4),
        span("t.tail", "wait", 190, 50, 5),
    ]
    by_name, gaps = hs.attribute(idle, spans)
    assert by_name == {
        hs.NO_SPAN: 10 + 10 + 40,   # 0-10, 90-100, 150-190
        "t.wait": 20 + 10 + 10,     # 10-30, 50-60, 80-90
        "t.run": 20,                # 30-50: a run span beats the wait
        "t.wait2": 20,              # 60-80: the wait that began last
        "t.tail": 10,               # 190-200, clipped to the idle time
    }
    assert [g[0] for g in gaps] == [100.0, 50.0]
    assert gaps[1][1] == {hs.NO_SPAN: 40, "t.tail": 10}
    assert hs.attribute([], spans) == ({}, [])


def test_idle_attributed_pct_on_a_made_up_run(capfd):
    trace, spans = made_up(5 * 10**11)
    first, last = trace["span_ns"]
    # one request span over the first half of the traced window
    spans.append(span("kafka.produce", "wait", first + 5 * 10**11,
                      (last - first) / 2, 7))
    ctx = {"trace": trace, "devplane": {"spans": spans, "spans_dropped": 0}}
    got = hs.idle_attributed_pct(ctx, {"kernels": KERNELS})
    # the dispatch spans cover 0.8 ms of idle time a call besides
    assert 50.0 < got < 65.0
    err = capfd.readouterr().err
    assert "lays 100.00 % of 200 executions" in err
    assert "under kafka.produce" in err and "under no span open" in err
    assert err.count("hostspans: gap ") == 10


def test_span_medians_and_the_front_end():
    spans = [span("raft.wire", "wait", 0, d * 1e6, batches=b)
             for d, b in ((1, 0), (2, 0), (3, 1), (5, 1), (9, 1))]
    spans += [span("kafka.produce", "wait", 0, 10e6, 1),
              span("produce.ack_wait", "wait", 1e6, 7e6, 2, 1),
              span("kafka.produce", "wait", 0, 20e6, 3),
              span("produce.ack_wait", "wait", 1e6, 15e6, 4, 3),
              span("kafka.produce", "wait", 0, 99e6, 5)]  # no wait: left out
    ctx = {"devplane": {"spans": spans, "spans_dropped": 0, "host": {
        "raft.wire": {"count": 5, "p50_ms": 3.25}}}}
    assert hs.span_p50_ms(ctx, {"span": "raft.wire"}) == 3.0
    assert hs.frontend_ms(ctx, {"root": "kafka.produce",
                                "wait": "produce.ack_wait"}) == 4.0
    # records dropped: the histogram's median, and no per-request sum
    ctx["devplane"]["spans_dropped"] = 1
    assert hs.span_p50_ms(ctx, {"span": "raft.wire"}) == 3.25
    assert hs.frontend_ms(ctx, {"root": "kafka.produce",
                                "wait": "produce.ack_wait"}) is None


@pytest.mark.parametrize("devplane", [{}, {"enabled": True, "kernels": {}},
                                      {"host": {}, "loop": {"samples": 0},
                                       "spans": [], "spans_dropped": 0}])
def test_readers_say_nothing_on_an_empty_devplane(devplane):
    """What a program without the span store hands the readers."""
    empty = {"devices": {}, "span_ns": [0.0, 1e9]}
    for trace in (None, empty):
        ctx = {"devplane": devplane, "trace": trace}
        for m in MANIFEST["per_layer"]:
            with open(os.path.join(ROOT, "benchmark", "metrics",
                                   m["name"] + ".json")) as f:
                spec = json.load(f)
            module, _, fn = spec["reader"].partition(".")
            if module != "hostspans":
                continue
            assert getattr(hs, fn)(ctx, spec["params"]) is None, m["name"]


def test_compiles_in_window_counts_both_phases():
    ctx = {"devplane": {"compiles": {
        "a": {"warmup": 2.0, "steady": 1.0, "seconds": 9.0},
        "b": {"warmup": 0.0, "steady": 0.0, "seconds": 0.0}}}}
    assert hs.compiles_in_window(ctx, {}) == 3.0
    assert hs.compiles_in_window({"devplane": {"compiles": {}}}, {}) == 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_dry_run_prints_the_span_metrics(cell):
    line = dry_run(cell, seed=2**31 + 25, trace=1)
    assert line["correct"] is True
    want = set(SPAN_METRICS)
    if "rf3" in cell:
        want.add("follower_rtt_ms")
    assert want <= set(line["metrics"]), sorted(line["metrics"])
    assert "follower_rtt_ms" in line["metrics"] or "rf3" not in cell
    for name in want - {"compiles_in_window"}:
        assert line["metrics"][name]["value"] > 0, name
    # no device plane in a CPU trace: the clock cannot be checked, so
    # the metric that needs it is left out, not guessed
    assert "idle_attributed_pct" not in line["metrics"]
