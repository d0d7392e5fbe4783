"""The tick fold's exchange with the device: the touched rows go up as
32-bit words inside the dispatch and come back as flat words, the int64
lanes cross bit for bit both ways, and prewarm leaves no compile for a
live fold at any bucket."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from redpanda_tpu.models.consensus_state import GroupState
from redpanda_tpu.ops.quorum import heartbeat_tick_jit, i64_to_words, words_to_i64
from redpanda_tpu.raft.shard_state import ShardGroupArrays

I64 = np.iinfo(np.int64)
VALUES = {
    "i64_min": I64.min,  # what padding replies carry
    "minus_one": -1,  # NO_OFFSET
    "zero": 0,
    "two_31": 2**31,
    "two_32_less_1": 2**32 - 1,
    "two_32": 2**32,
    "two_32_plus_1": 2**32 + 1,
    "i64_max": I64.max,
}
EMPTY = np.empty(0, np.int64)


@pytest.mark.parametrize("value", list(VALUES.values()), ids=list(VALUES))
def test_words_round_trip_bit_for_bit(value):
    """int64 → the host's uint32 view → int64 in the program, and back."""
    lanes = np.array([[value, -value if value != I64.min else 7, 1]], np.int64)
    on_device = jax.jit(words_to_i64)(lanes.view(np.uint32))
    assert on_device.dtype == np.int64
    np.testing.assert_array_equal(np.asarray(on_device), lanes)
    words = np.asarray(jax.jit(i64_to_words)(on_device))
    assert words.dtype == np.uint32 and words.shape == (6,)
    np.testing.assert_array_equal(words.view(np.int64).reshape(1, 3), lanes)


@pytest.mark.parametrize("value", list(VALUES.values()), ids=list(VALUES))
def test_fold_carries_every_lane_bit_for_bit(monkeypatch, value):
    """A row that does not lead goes through a fold unchanged: every
    lane the fold reads back is the value that went up, bit for bit,
    beside a leader whose commit the same fold advances."""
    monkeypatch.setenv("RP_QUORUM_BACKEND", "device")
    a = ShardGroupArrays(capacity=16)
    still, lead = a.alloc_row(), a.alloc_row()
    a.is_voter[[still, lead], 0] = True
    a.is_leader[lead] = True
    a.voter_epoch += 1
    a.device_tick(EMPTY, EMPTY, EMPTY, EMPTY, EMPTY)  # the dirty rows
    for lane in ("commit_index", "last_visible", "term", "term_start",
                 "match_index", "flushed_index", "last_seq"):
        getattr(a, lane)[still] = value
    a.match_index[lead, 0] = a.flushed_index[lead, 0] = 2**32 + 5
    advanced = a.device_tick(
        EMPTY, EMPTY, EMPTY, EMPTY, EMPTY, force_rows=np.array([still, lead])
    )
    assert list(advanced) == [lead] and a.commit_index[lead] == 2**32 + 5
    for lane in ("commit_index", "last_visible", "match_index", "flushed_index",
                 "last_seq"):
        got = getattr(a, lane)[still]
        assert got.dtype == np.int64 and np.all(got == value), (lane, got)


@pytest.mark.parametrize("bucket", [8, 16, 32, 64, 128])
def test_prewarm_leaves_no_compile_for_a_live_fold(monkeypatch, bucket):
    """After prewarm, a live fold at any bucket it can land in (up to
    the larger of the capacity and the reply window) finds its program
    compiled."""
    monkeypatch.setenv("RP_QUORUM_BACKEND", "device")
    cache_size = getattr(heartbeat_tick_jit, "_cache_size", None)
    if cache_size is None:
        pytest.skip("jax jit cache introspection unavailable")
    a = ShardGroupArrays(capacity=64)
    rows = np.array([a.alloc_row() for _ in range(64)], np.int64)
    a.is_leader[rows] = True
    a.is_voter[rows, :3] = True
    a.voter_epoch += 1
    a.prewarm(max_replies=128)
    a.device_tick(EMPTY, EMPTY, EMPTY, EMPTY, EMPTY)  # the dirty rows
    warmed = cache_size()
    n = bucket // 2 + 1 if bucket > 8 else 1  # replies that need this bucket
    r = np.resize(np.repeat(rows, 2), n)
    s = np.resize(np.array([1, 2], np.int64), n)
    off = np.full(n, 9, np.int64)
    a.match_index[rows, 0] = a.flushed_index[rows, 0] = 9
    advanced = a.device_tick(r, s, off, off, off)
    assert sorted(advanced) == sorted(np.unique(r))
    assert cache_size() == warmed, f"a live fold at bucket {bucket} compiled"


def test_layout_matches_the_program(monkeypatch):
    """The packed widths the host writes are the program's: every
    GroupState lane, the two health flags and the window up, the five
    lanes a fold changes and the three health lanes down."""
    monkeypatch.setenv("RP_QUORUM_BACKEND", "device")
    a = ShardGroupArrays(capacity=8)
    r = a.replica_slots
    width, up, back_width, back = a._fold_layout()
    assert width == 13 + 5 * r
    assert [name for name, _, _ in up] == list(GroupState._fields)
    assert back_width == sum(w for _, _, w in back) == 5 + 3 * r
    words = a._pack_fold(EMPTY, (EMPTY,) * 5, 8)
    assert words.dtype == np.uint32 and words.shape == (8, 2 * width)


def test_measure_fold_crossings_runs(tmp_path):
    """tools/measure_fold_crossings.py at three folds a part: every
    shape timed through both exchanges, and its two checks pass."""
    out = tmp_path / "parts.json"
    tool = Path(__file__).resolve().parents[1] / "tools" / "measure_fold_crossings.py"
    proc = subprocess.run(
        [sys.executable, str(tool), "--folds", "3", "--out", str(out)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    report = json.loads(out.read_text())
    assert report["platform"] == "cpu"
    assert report["words_bit_for_bit"] == {"up": True, "down": True}
    assert report["parity_with_host"] is True
    assert set(report["shapes"]) == {"single_1p", "rf3_write", "rf3_beat"}
    for shape in report["shapes"].values():
        assert shape["live"] is not None and shape["whole"]["fold_now"] > 0
