"""The reduction from a trace to numbers, on small recorded traces kept
beside this file, and the bytes functions on known shapes."""

import json
import os

import pytest

from benchmark import opsbytes, trace as tr
from benchmark.readers import trace as readers
from benchmark.reference import make_templates

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str) -> dict:
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.union([]) == []


def test_hand_made_trace_gives_hand_counted_numbers():
    # one device, a 10 ms window; ops busy 0-1, 1-2 (touching), 4-4.5 ms
    t = _load("trace_by_hand.json")
    busy, window = tr.busy_and_window(t)
    assert busy == pytest.approx(2.5e-3) and window == pytest.approx(10e-3)
    secs, n = tr.module_seconds(t, "^jit_heartbeat_tick")
    assert (secs, n) == (pytest.approx(2.0e-3), 1)
    secs, n = tr.module_seconds(t, "^jit_crc32c_device")
    assert (secs, n) == (pytest.approx(0.5e-3), 1)
    assert tr.module_seconds(t, "^jit_nothing") == (0.0, 0)
    top = tr.top_ops(t)
    assert top[0] == ["jit_heartbeat_tick / fusion.1", pytest.approx(1e-3)]
    gaps = tr.idle_gaps(t)
    assert gaps[0][1] == pytest.approx(2e-3)
    assert gaps[0][0] == ("unattributed (after jit_heartbeat_tick, "
                          "before jit_crc32c_device)")
    tpl = make_templates(1, 2, 4, 128)
    ctx = {"trace": t, "peaks": {"hbm_bytes_per_s": 819e9},
           "lanes": {"capacity": 2048, "slots": 8}, "templates": tpl,
           "fetched_in_trace": [len(tpl[0].wire), len(tpl[1].wire), 300]}
    assert readers.device_idle_pct(ctx, {}) == pytest.approx(75.0)
    want = 100 * opsbytes.tick_bytes(2048, 8) / 819e9 / 2.0e-3
    assert readers.tick_roofline(ctx, {"module": "^jit_heartbeat_tick"}) == pytest.approx(want)
    # three batches fetched in the traced seconds, one of them stored
    # shorter than it was sent: their crc-covered bytes as stored, a
    # length and a result each, whatever the program padded
    body = len(tpl[0].wire) - 21
    want = 100 * (2 * (body + 12) + (300 - 21 + 12)) / 819e9 / 0.5e-3
    assert readers.crc_roofline(ctx, {"module": "^jit_crc32c_device"}) == pytest.approx(want)


def test_readers_say_nothing_when_there_is_nothing_to_read():
    empty = {"devices": {}, "span_ns": [0.0, 1e9]}
    for ctx in ({"trace": None}, {"trace": empty, "peaks": {"hbm_bytes_per_s": 1.0},
                                  "lanes": {"capacity": 64, "slots": 8},
                                  "templates": make_templates(1, 1, 1, 64),
                                  "fetched_in_trace": [85]}):
        assert readers.device_idle_pct(ctx, {}) is None
        assert readers.tick_roofline(ctx, {"module": "x"}) is None
        assert readers.crc_roofline(ctx, {"module": "x"}) is None


def test_bytes_from_known_shapes():
    # 2,048 groups x 8 slots: 33 B a group and 26 B a slot read, the
    # window's 5 int64 columns at 8 entries, 16 B a group and 24 B a
    # slot written
    assert opsbytes.tick_bytes(2048, 8) == (
        2048 * 33 + 2048 * 8 * 26 + 5 * 8 * 8 + 2048 * 16 + 2048 * 8 * 24
    )
    assert opsbytes.tick_bytes(64, 8, replies=16) - opsbytes.tick_bytes(64, 8) == 5 * 8 * 8
    assert opsbytes.crc_shape(66_549, 1) == (8, 131072)
    assert opsbytes.crc_shape(66_549, 7) == (8, 131072)
    assert opsbytes.crc_shape(512, 9) == (16, 512)
    assert opsbytes.crc_bytes(8, 131072) == 8 * 131072 + 8 * 12
    # a consumer of 8 tails 125 of 1,000 partitions; 131,072 B a
    # partition hold three 40 KB batches: 375 rows, the 512 bucket
    tpl = make_templates(1, 1, 39, 1024)
    config = {"topics": [{"partitions": 1000}]}
    traffic = {"fetch_max_bytes": 131072, "consumers": 8}
    assert opsbytes.fetch_crc_shape(config, traffic, len(tpl[0].wire)) == (512, 65536)
    one = {"topics": [{"partitions": 1}]}
    assert opsbytes.fetch_crc_shape(one, {**traffic, "consumers": 1}, len(tpl[0].wire)) == (8, 65536)


@pytest.mark.skipif(
    not os.path.exists(os.path.join(HERE, "trace_sample.json")),
    reason="no recorded chip trace beside this file",
)
def test_recorded_chip_trace_reduces_to_its_recorded_numbers():
    t = _load("trace_sample.json")
    want = t["expected"]
    busy, window = tr.busy_and_window(t)
    assert busy == pytest.approx(want["busy_s"])
    assert window == pytest.approx(want["window_s"])
    for pattern, (secs, n) in want["modules"].items():
        got = tr.module_seconds(t, pattern)
        assert got == (pytest.approx(secs), n)
    assert 0 < busy < window
