"""Node-level fsync coalescing across logs.

The replicate batcher coalesces fsyncs *within* one raft group, but a
broker hosting 1k groups under rotating producers issues one executor
round-trip per group per produce, and the executor hand-off (about a
millisecond of queue latency each on a CPU box; not measured on the
chip) dominated the leader flush path. The reference hits the same
wall differently and solves it in segment_appender's shared flush
queue; here one coalescer per event loop gathers every fsync request
that arrives while an executor round is in flight and settles them in
ONE `run_in_executor` call (looping os.fsync over the unique fds), so
executor trips per interval are O(1) in group count.

Error isolation is per-fd: one bad descriptor fails only its waiters.
"""

from __future__ import annotations

import asyncio
import os
import time
from typing import Optional

from ..observability import trace


def _fsync_all(
    fds: list[int],
) -> list[tuple[Optional[BaseException], int, int]]:
    """On the executor's thread: fsync each fd, with the monotonic_ns
    stamps around each syscall. The loop records them as the round's
    `storage.fsync` span once the await resumes (the span store is the
    loop's alone) and feeds the EWMA from the same pair."""
    out: list[tuple[Optional[BaseException], int, int]] = []
    for fd in fds:
        t0 = time.monotonic_ns()
        try:
            os.fsync(fd)
            out.append((None, t0, time.monotonic_ns()))
        except BaseException as e:  # per-fd isolation
            out.append((e, t0, time.monotonic_ns()))
    return out


class FlushCoalescer:
    _by_loop: dict = {}

    def __init__(self) -> None:
        self._pending: list[tuple[int, asyncio.Future]] = []
        self._running = False

    @classmethod
    def get(cls) -> "FlushCoalescer":
        loop = asyncio.get_event_loop()
        inst = cls._by_loop.get(loop)
        if inst is None:
            inst = cls()
            cls._by_loop[loop] = inst
            # don't let dead loops accumulate instances (test suites
            # create thousands of loops)
            if len(cls._by_loop) > 8:
                cls._by_loop = {
                    l: i for l, i in cls._by_loop.items() if not l.is_closed()
                }
        return inst

    # device-speed estimate: EWMA of the raw fsync syscall time. Below
    # the inline threshold (tmpfs, fast NVMe appends) the syscall runs
    # directly on the event loop — the executor hand-off costs ~1-2 ms
    # of GIL/wakeup latency on a busy loop, an order of magnitude more
    # than the fast-device syscall it wraps. Slow devices keep the
    # off-loop path. Starts optimistic; one slow fsync flips it over.
    INLINE_THRESHOLD_S = 0.0002
    _ewma_s = 0.0

    async def fsync(self, fd: int) -> None:
        if FlushCoalescer._ewma_s < self.INLINE_THRESHOLD_S:
            t0 = time.monotonic_ns()
            os.fsync(fd)
            t1 = time.monotonic_ns()
            trace.record("storage.fsync", "run", t0, t1, path="inline", fds=1)
            FlushCoalescer._ewma_s += 0.2 * ((t1 - t0) / 1e9 - FlushCoalescer._ewma_s)
            return
        loop = asyncio.get_event_loop()
        fut = loop.create_future()
        self._pending.append((fd, fut, trace.current_span()))
        if not self._running:
            self._running = True
            asyncio.ensure_future(self._run())
        await fut

    async def _run(self) -> None:
        loop = asyncio.get_event_loop()
        try:
            while self._pending:
                batch, self._pending = self._pending, []
                # dedupe: several waiters on one fd need one fsync
                order: list[int] = []
                seen: set[int] = set()
                for fd, _, _ in batch:
                    if fd not in seen:
                        seen.add(fd)
                        order.append(fd)
                try:
                    results = await loop.run_in_executor(
                        None, _fsync_all, order
                    )
                    by_fd = {fd: err for fd, (err, _, _) in zip(order, results)}
                    for _, t0, t1 in results:
                        FlushCoalescer._ewma_s += 0.2 * (
                            (t1 - t0) / 1e9 - FlushCoalescer._ewma_s
                        )
                    # back on the loop: the round's syscalls as one span,
                    # under the first waiter's span (still open: its
                    # future is settled below)
                    trace.record(
                        "storage.fsync", "wait", results[0][1], results[-1][2],
                        parent=batch[0][2], path="executor", fds=len(order),
                    )
                except asyncio.CancelledError:
                    raise  # teardown must propagate, not land in futures
                except BaseException as e:  # executor itself failed
                    by_fd = {fd: e for fd in order}
                for fd, fut, _ in batch:
                    if fut.done():
                        continue
                    err = by_fd.get(fd)
                    if err is None:
                        fut.set_result(None)
                    else:
                        fut.set_exception(err)
        finally:
            self._running = False


# RP_SAN=1: the pending/running pair is the classic coalescer handoff
# (submit appends, the drain task swaps) — NOT _ewma_s, which is
# class-level state a descriptor would be clobbered by. No-op when
# RP_SAN is unset.
from ..utils import rpsan as _rpsan  # noqa: E402

_rpsan.instrument(FlushCoalescer, ("_pending", "_running"))
