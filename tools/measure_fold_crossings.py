#!/usr/bin/env python
"""Time one tick fold by parts: the crossings, the kernel and the host's
own work, each as the median of many folds.

A fold (`TickFrame.fold_now` → `ShardGroupArrays.frame_tick` =
`device_tick` → `_fold_on_device`) packs the rows it touches, sends
them up, runs `jit_heartbeat_tick` against the state resident on the
device, brings the lanes it changed back and writes them into the
mirrors. The shapes are the benchmark's:

  single_1p   64 lanes, one row, no replies          (bucket 8)
  rf3_write   2,048 lanes, one row, two replies      (bucket 8)
  rf3_beat    2,048 lanes, 333 rows, 666 replies     (bucket 1024)

Two exchanges are timed side by side, step by step:

  int64   ten lanes packed into a fresh int64 buffer, uploaded by
          `jnp.asarray`, a ten-array GroupState donated to the program,
          an int64 readback written lane by lane (written out here)
  live    this tree's own exchange (`ShardGroupArrays._pack_fold`,
          `ops.quorum.heartbeat_tick_jit`, `_unpack_fold`), where the
          tree has one; its upload rides the dispatch, so its `up` is
          what the words cost sent by themselves, not paid by the fold

and, through the tree's own entry points, what a fold costs whole
(`device_tick`, `fold_now`), `device_tick`'s bookkeeping (less
`_fold_on_device` and `_health_np_rows`), the health refresh, and the
frame's own work with one no-op callback a row (less `frame_tick`).
Besides: what a dispatch pays for each donated leaf (ten against one),
whether int64 lanes cross as 32-bit words bit for bit, both ways, and
whether the device fold leaves the lanes host_tick leaves.

Usage:
    python tools/measure_fold_crossings.py [--folds 200] [--out FILE]

The first line names the platform and device kind JAX reports: a table
taken with JAX_PLATFORMS=cpu says nothing about a chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

SHAPES = (("single_1p", 64, 1, 0), ("rf3_write", 2048, 1, 2), ("rf3_beat", 2048, 333, 666))
WORDS = np.array(
    [np.iinfo(np.int64).min, -1, 0, 1, 2**31, 2**32 - 1, 2**32, 2**32 + 1,
     np.iinfo(np.int64).max, -(2**40) + 3],
    np.int64,
)


def _ms(ns: list[int]) -> float:
    return statistics.median(ns) / 1e6


class _Clock:
    """Accumulates the wall time of the calls it wraps, by name."""

    def __init__(self):
        self.ns: dict[str, int] = {}

    def wrap(self, obj, name: str) -> None:
        inner = getattr(obj, name)

        def timed(*a, **kw):
            t0 = time.perf_counter_ns()
            try:
                return inner(*a, **kw)
            finally:
                self.ns[name] = self.ns.get(name, 0) + time.perf_counter_ns() - t0

        setattr(obj, name, timed)

    def take(self, name: str) -> int:
        return self.ns.pop(name, 0)


def _arrays(cap: int, n_rows: int, voters: int):
    from redpanda_tpu.raft.shard_state import ShardGroupArrays

    a = ShardGroupArrays(capacity=cap)
    rows = np.array([a.alloc_row() for _ in range(n_rows)], np.int64)
    a.is_leader[rows] = True
    a.is_voter[rows, :voters] = True
    a.voter_epoch += 1
    a.device_tick(*(np.empty(0, np.int64),) * 5)  # the dirty rows
    return a, rows


def _window(rows, n_replies: int, seq: int):
    r = np.resize(np.repeat(rows, 2), n_replies)
    s = np.resize(np.array([1, 2], np.int64), n_replies)
    off = np.full(n_replies, seq, np.int64)
    return r, s, off, off, off


def _bucket(rows: int, replies: int) -> int:
    b = 8
    while b < max(rows, replies):
        b *= 2
    return b


def _advance(a, rows, seq: int) -> None:
    a.match_index[rows, 0] = seq
    a.flushed_index[rows, 0] = seq


def _int64_program():
    """The exchange before 32-bit words: the program as it was."""
    import jax
    import jax.numpy as jnp

    from redpanda_tpu.models.consensus_state import GroupState
    from redpanda_tpu.ops.quorum import heartbeat_tick

    lanes_back = ("commit_index", "last_visible", "match_index", "flushed_index", "last_seq")

    def heartbeat_tick_int64(state, packed):
        b = packed.shape[0]
        rows = packed[:, 0]
        lanes, col = [], 1
        for lane in state:
            width = math.prod(lane.shape[1:])
            fresh = packed[:, col : col + width].reshape((b,) + lane.shape[1:])
            lanes.append(lane.at[rows].set(fresh.astype(lane.dtype), mode="drop"))
            col += width
        state = heartbeat_tick(GroupState(*lanes), *(packed[:, col + i] for i in range(5)))
        at = jnp.minimum(rows, state.num_groups - 1)
        back = [getattr(state, n)[at].reshape(b, -1) for n in lanes_back]
        return state, jnp.concatenate(back, axis=1)

    return jax.jit(heartbeat_tick_int64, donate_argnums=0), lanes_back


def _exchange_int64(a, rows, shape, folds: int) -> dict:
    import jax.numpy as jnp

    from redpanda_tpu.models.consensus_state import GroupState

    prog, lanes_back = _int64_program()
    _, cap, n_rows, n_replies = shape
    state = GroupState(*(jnp.asarray(getattr(a, f)) for f in GroupState._fields))
    bucket, r = _bucket(n_rows, n_replies), a.replica_slots
    parts = {k: [] for k in ("pack", "up", "launch_ready", "readback", "unpack")}
    for i in range(folds + 20):
        seq = 10_000 + i
        _advance(a, rows, seq)
        window = _window(rows, n_replies, seq)
        t0 = time.perf_counter_ns()
        t = len(rows)
        packed = np.zeros((bucket, 11 + 5 * r), np.int64)
        packed[:t, 0] = rows
        packed[t:, 0] = cap
        col = 1
        for name in GroupState._fields:
            lane = getattr(a, name)
            width = 1 if lane.ndim == 1 else r
            packed[:t, col : col + width] = lane[rows].reshape(t, width)
            col += width
        m = len(window[0])
        packed[m:, col + 2 :] = np.iinfo(np.int64).min
        for j, column in enumerate(window):
            packed[:m, col + j] = column
        t1 = time.perf_counter_ns()
        up = jnp.asarray(packed)
        up.block_until_ready()
        t2 = time.perf_counter_ns()
        state, back = prog(state, up)
        back.block_until_ready()
        t3 = time.perf_counter_ns()
        out = np.asarray(back)
        t4 = time.perf_counter_ns()
        col = 0
        for name in lanes_back:
            lane = getattr(a, name)
            width = 1 if lane.ndim == 1 else r
            lane[rows] = out[:t, col : col + width].reshape((t,) + lane.shape[1:])
            col += width
        t5 = time.perf_counter_ns()
        if i >= 20:
            for k, v in zip(parts, (t1 - t0, t2 - t1, t3 - t2, t4 - t3, t5 - t4)):
                parts[k].append(v)
    return {k: _ms(v) for k, v in parts.items()}


def _exchange_live(a, rows, shape, folds: int) -> dict | None:
    import jax

    from redpanda_tpu.ops.quorum import heartbeat_tick_jit

    if not hasattr(a, "_pack_fold"):
        return None
    _, _, n_rows, n_replies = shape
    bucket = _bucket(n_rows, n_replies)
    parts = {k: [] for k in ("pack", "up", "launch_ready", "readback", "unpack")}
    resident, a._resident = a._resident, None
    for i in range(folds + 20):
        seq = 20_000 + i
        _advance(a, rows, seq)
        window = _window(rows, n_replies, seq)
        t0 = time.perf_counter_ns()
        words = a._pack_fold(rows, window, bucket)
        t1 = time.perf_counter_ns()
        jax.device_put(words).block_until_ready()  # by itself: not the fold's
        t2 = time.perf_counter_ns()
        resident, back = heartbeat_tick_jit(resident, words)
        back.block_until_ready()
        t3 = time.perf_counter_ns()
        out = np.asarray(back)
        t4 = time.perf_counter_ns()
        a._unpack_fold(rows, out)
        t5 = time.perf_counter_ns()
        if i >= 20:
            for k, v in zip(parts, (t1 - t0, t2 - t1, t3 - t2, t4 - t3, t5 - t4)):
                parts[k].append(v)
    a._resident = resident
    return {k: _ms(v) for k, v in parts.items()}


def _whole(a, rows, shape, folds: int) -> dict:
    """The tree's own entry points, each part by subtraction."""
    from redpanda_tpu.raft.tick_frame import TickFrame

    _, _, n_rows, n_replies = shape
    clock = _Clock()
    for name in ("_fold_on_device", "_health_np_rows", "frame_tick"):
        clock.wrap(a, name)
    frame = TickFrame(a)
    for row in rows:
        frame.register(int(row), lambda: None)
    keys = ("fold_now", "device_tick", "fold_on_device", "health", "device_tick_bookkeeping",
            "frame_bookkeeping_and_callbacks")
    parts = {k: [] for k in keys}
    for i in range(folds + 20):
        seq = 60_000 + i
        _advance(a, rows, seq)
        for row in rows:
            frame.note_self(int(row))
        t0 = time.perf_counter_ns()
        advanced = frame.fold_now(*_window(rows, n_replies, seq))
        whole = time.perf_counter_ns() - t0
        assert len(advanced) == n_rows, (shape, len(advanced))
        tick = clock.take("frame_tick")
        fold = clock.take("_fold_on_device")
        health = clock.take("_health_np_rows")
        if i >= 20:
            for k, v in zip(keys, (whole, tick, fold, health, tick - fold - health, whole - tick)):
                parts[k].append(v)
    return {k: _ms(v) for k, v in parts.items()}


def _leaf_cost(folds: int) -> dict:
    """Launch to ready of the same arithmetic over ten donated [64]
    arrays and over one donated [64, 10] array."""
    import jax
    import jax.numpy as jnp

    ten = jax.jit(lambda s, x: tuple(v + x for v in s), donate_argnums=0)
    one = jax.jit(lambda s, x: s + x, donate_argnums=0)
    s10 = tuple(jnp.zeros(64, jnp.int64) for _ in range(10))
    s1 = jnp.zeros((64, 10), jnp.int64)
    out = {}
    for name, fn, s in (("ten_leaves", ten, s10), ("one_leaf", one, s1)):
        ns = []
        for i in range(folds + 20):
            x = np.int64(i)
            t0 = time.perf_counter_ns()
            s = fn(s, x)
            jax.block_until_ready(s)
            if i >= 20:
                ns.append(time.perf_counter_ns() - t0)
        out[name] = _ms(ns)
    out["per_leaf"] = (out["ten_leaves"] - out["one_leaf"]) / 9
    return out


def _words_bit_for_bit() -> dict:
    """int64 → 32-bit words on the host → int64 on the device, and back."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    up = jax.jit(lambda w: lax.bitcast_convert_type(w.reshape(-1, 2), jnp.int64))
    down = jax.jit(lambda v: lax.bitcast_convert_type(v, jnp.uint32).reshape(-1))
    on_device = np.asarray(up(WORDS.view(np.uint32)))
    back = np.asarray(down(jnp.asarray(WORDS))).view(np.int64)
    return {
        "up": bool(np.array_equal(on_device, WORDS)),
        "down": bool(np.array_equal(back, WORDS)),
    }


def _parity_with_host(folds: int = 12) -> bool:
    """The device fold against host_tick on one random history at 64
    and at 2,048 lanes (buckets 8 to 1,024): every lane the fold
    writes, and the health lanes, equal after every fold."""
    from redpanda_tpu.models.consensus_state import GroupState
    from redpanda_tpu.raft.shard_state import ShardGroupArrays

    lanes = GroupState._fields + ("health_max_lag", "health_under", "health_leaderless")
    same = True
    for cap in (64, 2048):
        rng = np.random.default_rng(cap)
        pair = {b: ShardGroupArrays(capacity=cap) for b in ("host", "device")}
        n = cap // 2
        for a in pair.values():
            rows = np.array([a.alloc_row() for _ in range(n)], np.int64)
        g, r = np.random.default_rng(7), pair["host"].replica_slots
        match = g.integers(-1, 1 << 33, (n, r))
        voters = g.random((n, r)) < 0.6
        voters[:, 0] = True
        for a in pair.values():
            a.match_index[rows] = match
            a.flushed_index[rows] = match - 3
            a.is_voter[rows] = voters
            a.is_leader[rows] = np.arange(n) % 5 != 0
            a.voter_epoch += 1
        for step in range(folds):
            m = int(rng.integers(0, 2 * n))
            window = (
                rng.choice(rows, m), rng.integers(1, r, m),
                rng.integers(0, 1 << 34, m), rng.integers(0, 1 << 34, m),
                rng.integers(0, 1 << 40, m),
            )
            force = rng.choice(rows, int(rng.integers(1, 4)))
            for backend, a in pair.items():
                os.environ["RP_QUORUM_BACKEND"] = backend
                a.device_tick(*window, force_rows=force)
            for lane in lanes:
                same &= bool(np.array_equal(
                    getattr(pair["host"], lane), getattr(pair["device"], lane)))
    os.environ["RP_QUORUM_BACKEND"] = "device"
    return same


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--folds", type=int, default=200)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    os.environ["RP_QUORUM_BACKEND"] = "device"
    import jax

    import redpanda_tpu  # noqa: F401  (configures JAX: 64-bit lanes)

    dev = jax.devices()[0]
    report = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "folds": args.folds,
        "words_bit_for_bit": _words_bit_for_bit(),
        "parity_with_host": _parity_with_host(),
        "leaf_cost_ms": _leaf_cost(args.folds),
        "shapes": {},
    }
    print(f"# platform: {dev.platform} device_kind: {dev.device_kind}; "
          f"medians of {args.folds} folds, ms")
    print("# words bit for bit:", report["words_bit_for_bit"],
          "; device fold equals host_tick:", report["parity_with_host"])
    print("# launch→ready, donated leaves:", report["leaf_cost_ms"])
    for shape in SHAPES:
        name = shape[0]
        a, rows = _arrays(shape[1], shape[2], 3 if shape[3] else 1)
        got = {
            "bucket": _bucket(shape[2], shape[3]),
            "int64": _exchange_int64(a, rows, shape, args.folds),
            "live": _exchange_live(a, rows, shape, args.folds),
            "whole": _whole(a, rows, shape, args.folds),
        }
        report["shapes"][name] = got
        print(f"{name} (bucket {got['bucket']})")
        for part, value in got["int64"].items():
            live = got["live"][part] if got["live"] else None
            print(f"  {part:>28} int64 {value:8.4f}   live "
                  + ("n/a" if live is None else f"{live:8.4f}"))
        for part, value in got["whole"].items():
            print(f"  {part:>28} {value:8.4f}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
