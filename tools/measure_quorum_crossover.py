#!/usr/bin/env python
"""Measure the host-vs-device crossover for the quorum sweep.

VERDICT r2 weak #5: the size from which the device fold pays was
asserted, not measured. This tool measures a FULL FOLD (every group
advancing — the worst case; steady-state ticks skip the sweep entirely
since the r3 incremental change) through shard_state.host_tick and
through the device path, at several shard sizes, using the honest
device methodology (distinct settled inputs, per-call blocking).

Usage:
    python tools/measure_quorum_crossover.py     # whatever JAX finds

The report names the platform and device kind it ran on, as JAX
reports them: a table from JAX_PLATFORMS=cpu says nothing about a
chip. Prints a table plus the measured crossover.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def make_arrays(g: int, backend: str):
    from redpanda_tpu.raft.shard_state import ShardGroupArrays

    a = ShardGroupArrays(capacity=g, replica_slots=8)
    rows = [a.alloc_row() for _ in range(g)]
    a.is_leader[:] = True
    a.is_voter[:, :3] = True
    a.term_start[:] = 0
    a.match_index[:, 0] = 0
    a.flushed_index[:, 0] = 0
    os.environ["RP_QUORUM_BACKEND"] = backend
    return a, np.array(rows, np.int64)


def one_tick(a, rows, offset: int):
    m = len(rows) * 2
    g_rows = np.repeat(rows, 2)
    slots = np.tile(np.array([1, 2], np.int64), len(rows))
    dirty = np.full(m, offset, np.int64)
    seqs = np.full(m, offset + 1, np.int64)
    # leader log advances too, so every group's commit moves (full fold)
    a.match_index[rows, 0] = offset
    a.flushed_index[rows, 0] = offset
    return a.device_tick(g_rows, slots, dirty, dirty, seqs)


def measure(g: int, backend: str, iters: int = 8) -> float:
    a, rows = make_arrays(g, backend)
    one_tick(a, rows, 0)  # warm/compile
    times = []
    for i in range(1, iters + 1):
        t0 = time.perf_counter()
        advanced = one_tick(a, rows, i)
        times.append(time.perf_counter() - t0)
        assert len(advanced) == g, (backend, g, len(advanced))
    os.environ.pop("RP_QUORUM_BACKEND", None)
    return min(times) * 1e3


def main() -> None:
    ap = argparse.ArgumentParser()
    args = ap.parse_args()
    import jax

    sizes = [1024, 4096, 16384, 65536, 131072]
    dev0 = jax.devices()[0]
    lines = [
        "# quorum sweep host-vs-device crossover "
        "(full fold, every group advancing; ms per tick, min of 8)",
        f"# platform: {dev0.platform} device_kind: {dev0.device_kind} "
        f"devices: {len(jax.devices())}",
        f"{'groups':>8} {'host_ms':>9} {'device_ms':>10} {'winner':>7}",
    ]
    crossover = None
    for g in sizes:
        host = measure(g, "host")
        dev = measure(g, "device")
        winner = "device" if dev < host else "host"
        if winner == "device" and crossover is None:
            crossover = g
        lines.append(f"{g:>8} {host:>9.3f} {dev:>10.3f} {winner:>7}")
    lines.append(
        f"# measured crossover: device wins from ~{crossover} groups"
        if crossover
        else "# measured crossover: host wins at every tested size"
    )
    report = "\n".join(lines)
    print(report)


if __name__ == "__main__":
    main()
