"""Flight recorder: ring-buffered trace spans with a slow-request freezer.

The reference has no distributed tracer (SURVEY §5.1 — request-level
visibility is sampled logs); this is the piece we add on top of the
probe/histogram layer. One `FlightRecorder` per broker holds:

  * a fixed-size ring of completed span *trees* (most recent first at
    dump time) — the "what just happened" tail;
  * a bounded freezer of full span trees whose root latency exceeded
    the slow threshold — the "why was that one slow" sample;
  * a small event log for out-of-band markers (NemesisNet fault
    injections land here and also tag the span they hit).

This is the broker's one span system: a module-level ENABLED flag
checked per call, a shared no-op context object when tracing is off,
and `time.monotonic_ns()` stamps. Parent linkage is a contextvar within
a task; across tasks (produce request -> batcher flush round) the
caller captures `handoff_span()` and passes it back via
`span(..., parent=...)`. A span is of one of two kinds: `run` (no
`await` inside: the span holds the event loop) or `wait` (mostly a wait
on something else: a future, an RPC, a thread's fsync).

Besides its tree, every finished span feeds the process-global
`WINDOW` store: per span name a count, total and self time and a
latency histogram; from one `LoopLagProbe` per event loop a loop-lag
histogram and what the loop's selector saw (passes, seconds awake and
asleep, how late a timer woke it); and, while the device plane runs at
full fidelity (`RP_DEVPLANE_SAMPLE=1`), the raw span records and the
loop's sleep intervals. The store rides
`devplane.reset()` / `devplane.status()` (keys `host`, `loop`, `spans`,
`spans_dropped`), which is how a benchmark window reads it.

Env knobs:
  RP_TRACE=0          kill switch — span() returns the shared no-op,
                      nothing is allocated or recorded
  RP_TRACE_SLOW_MS    slow-request freeze threshold (default 100 ms)
  RP_TRACE_RING       ring capacity in span trees (default 256)
"""

from __future__ import annotations

import asyncio
import itertools
import math
import os
import time
from array import array
from collections import deque
from contextvars import ContextVar
from typing import Optional

from ..metrics import HistogramChild

ENABLED = os.environ.get("RP_TRACE", "1") != "0"
SLOW_MS = float(os.environ.get("RP_TRACE_SLOW_MS", "100"))
RING_CAP = int(os.environ.get("RP_TRACE_RING", "256"))
FROZEN_CAP = 32
EVENTS_CAP = 256

_ids = itertools.count(1)
_current: ContextVar[Optional["Span"]] = ContextVar("rp_trace_span", default=None)
# cross-process parent adopted by the next ROOT span opened in this
# task: (trace_id, parent_span_id, origin) shipped inside the invoke_on
# envelope / TRACED_CALL rpc wrapper by the sending side
_remote: ContextVar[Optional[tuple]] = ContextVar("rp_trace_remote", default=None)


def _after_fork_child() -> None:
    """Fork hygiene: the id counter and the module-default recorder are
    copied by fork — reseed ids into a pid-disjoint range (stitched
    cross-process trees must never collide on span ids) and drop the
    parent's trees/events from the child's recorder, its window store
    and its loops' lag probes."""
    global _ids
    _ids = itertools.count(((os.getpid() & 0x3FFFFF) << 40) | 1)
    r = _default_recorder
    r._ring = [None] * len(r._ring)
    r._ring_idx = 0
    r._frozen.clear()
    r._events.clear()
    r.trees_total = 0
    r.frozen_total = 0
    WINDOW.reset()
    LoopLagProbe._by_loop = {}


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_after_fork_child)


def _covered_ns(kids: list, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) that the child intervals cover: their
    union, clipped (children may overlap, and may start before a
    parent that adopted them late)."""
    total = 0
    end = lo
    for s, e in sorted(kids):
        if e > hi:
            e = hi
        if s < end:
            s = end
        if e > s:
            total += e - s
            end = e
    return total


class Span:
    """One timed node in a trace tree. Construct via span() — the
    context manager guarantees the exit stamp and ring handoff; a bare
    Span() that never closes silently poisons its whole tree (enforced
    by rplint RPL008 outside this package)."""

    __slots__ = (
        "name",
        "kind",
        "span_id",
        "parent_id",
        "start_ns",
        "dur_ns",
        "tags",
        "trace_id",
        "origin",
        "heir",
        "_root",
        "_parent",
        "_kids",
        "_recorder",
        "_spans",
        "_token",
    )

    def __init__(
        self,
        name: str,
        kind: str = "run",
        parent: Optional["Span"] = None,
        recorder: Optional["FlightRecorder"] = None,
        tags: Optional[dict] = None,
    ):
        self.name = name
        self.kind = kind
        self.span_id = next(_ids)
        self.start_ns = 0
        self.dur_ns = -1
        self.tags = tags
        self._parent = parent
        # (start, end) of every child that finished while this span
        # was open: what finish() subtracts to get the self time
        self._kids: Optional[list] = None
        if parent is not None:
            self.parent_id = parent.span_id
            self._root = parent._root
            self._recorder = parent._recorder
        else:
            self._root = self
            # collector for every span in this tree, filled on exits
            self._spans: list[dict] = []
            # the wait span a request root pre-makes for work that
            # outlives its dispatch (see handoff_span)
            self.heir: Optional["Span"] = None
            self._recorder = recorder if recorder is not None else _default_recorder
            r = _remote.get()
            if r is not None:
                # root of a remote continuation: join the propagated
                # trace under the sender's span
                self.trace_id, self.parent_id, self.origin = r
            else:
                self.parent_id = 0
                self.trace_id = self.span_id
                self.origin = None
        self._token = None

    def tag(self, **tags) -> None:
        if self.tags is None:
            self.tags = tags
        else:
            self.tags.update(tags)

    def _to_dict(self) -> dict:
        d = {
            "name": self.name,
            "kind": self.kind,
            "id": self.span_id,
            "parent": self.parent_id,
            "start_ns": self.start_ns,
            "dur_ns": self.dur_ns,
        }
        if self.tags:
            d["tags"] = self.tags
        return d

    def begin(self, start_ns: int = 0) -> "Span":
        """Stamp the start without entering the task's contextvar
        scope — for a span whose lifetime crosses tasks, or whose
        start a probe site already read off the same clock."""
        self.start_ns = start_ns or time.monotonic_ns()
        return self

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        if not self.start_ns:
            self.start_ns = time.monotonic_ns()
        return self

    def detach(self) -> None:
        """End this span's contextvar scope without stamping its end
        time — for a root whose lifetime crosses tasks (staged produce:
        dispatch happens here, the ack lands in the response writer).
        Call finish() from wherever the request actually completes."""
        if self._token is not None:
            try:
                _current.reset(self._token)
            except ValueError:
                # token from another Context (detach after a task hop)
                _current.set(None)
            self._token = None

    def finish(self, exc_type=None, end_ns: int = 0) -> None:
        """Stamp the end time, feed the window store and hand the tree
        to the recorder. Idempotent; __exit__ is detach()+finish()."""
        if self.dur_ns >= 0:
            return
        end = end_ns or time.monotonic_ns()
        self.dur_ns = end - self.start_ns
        if exc_type is not None:
            self.tag(error=exc_type.__name__)
        root = self._root
        root._spans.append(self._to_dict())
        self_ns = self.dur_ns
        if self._kids is not None:
            self_ns -= _covered_ns(self._kids, self.start_ns, end)
        p = self._parent
        if p is not None and p.dur_ns < 0:
            if p._kids is None:
                p._kids = [(self.start_ns, end)]
            else:
                p._kids.append((self.start_ns, end))
        WINDOW.add(self, self_ns)
        if root is self:
            rec = self._recorder
            if rec is not None:
                rec._finish_tree(self)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.detach()
        self.finish(exc_type)
        return False


class _NoopSpan:
    """Shared do-nothing context when tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **tags):
        pass

    def begin(self, start_ns=0):
        return self

    def detach(self):
        pass

    def finish(self, exc_type=None, end_ns=0):
        pass

    def next(self, name, kind="run"):
        pass

    def end(self):
        pass

    span_id = 0
    dur_ns = -1


_NOOP = _NoopSpan()


def _open_current() -> Optional[Span]:
    """The task's current span, if it is still open. A task keeps the
    context it was created under (a flush loop, a peer fiber, a
    call_soon callback), so the contextvar can hold a span that
    finished long ago: that one adopts no new children."""
    s = _current.get()
    if s is not None and s.dur_ns >= 0:
        return None
    return s


def span(
    name: str,
    kind: str = "run",
    parent: Optional[Span] = None,
    recorder: Optional["FlightRecorder"] = None,
    **tags,
):
    """Open a trace span of kind `run` or `wait`. Parent defaults to
    the task's current open span; pass `parent=` explicitly to stitch
    across tasks (e.g. a batcher flush round adopting the first queued
    produce's span). Keep tag values pre-formatted plain objects —
    building f-strings in the argument list runs even when tracing is
    off (rplint RPL008)."""
    if not ENABLED:
        return _NOOP
    if parent is None:
        parent = _open_current()
    return Span(name, kind, parent, recorder, tags or None)


def record(
    name: str,
    kind: str,
    start_ns: int,
    end_ns: int,
    parent: Optional[Span] = None,
    **tags,
) -> None:
    """A span that is already over, from two stamps of the monotonic
    clock its site took anyway (a probe histogram's pair, an item's
    enqueue time): one call, no contextvar scope."""
    if not ENABLED:
        return
    if parent is None:
        parent = _open_current()
    s = Span(name, kind, parent, None, tags or None)
    s.start_ns = start_ns
    s.finish(end_ns=end_ns)


class _Phases:
    """Consecutive child spans of one long function body, without
    re-indenting it: next() closes the phase that is open and opens
    the named one, end() closes the last."""

    __slots__ = ("_open",)

    def __init__(self) -> None:
        self._open: Optional[Span] = None

    def next(self, name: str, kind: str = "run") -> None:
        self.end()
        self._open = Span(name, kind, _open_current()).__enter__()

    def tag(self, **tags) -> None:
        if self._open is not None:
            self._open.tag(**tags)

    def end(self) -> None:
        s, self._open = self._open, None
        if s is not None:
            s.__exit__(None, None, None)


def phases():
    if not ENABLED:
        return _NOOP
    return _Phases()


def current_span() -> Optional[Span]:
    """The innermost open span of this task, or None (also None when
    tracing is disabled — callers pass it straight back to span())."""
    if not ENABLED:
        return None
    return _open_current()


def handoff_span() -> Optional[Span]:
    """The span that work queued here for another task should hang
    under: the `heir` of the request's root if it pre-made one (a
    produce's `produce.ack_wait`, under which the replicate stages
    run), else the current span."""
    if not ENABLED:
        return None
    s = _open_current()
    if s is not None and s._root.heir is not None:
        return s._root.heir
    return s


def propagation_ctx() -> Optional[tuple[int, int]]:
    """(trace_id, span_id) of the innermost open span, for shipping
    across a process/rpc boundary (invoke_on envelope, TRACED_CALL
    wrapper). None when tracing is off or no span is open — callers
    skip the wrap entirely."""
    if not ENABLED:
        return None
    s = _current.get()
    if s is None:
        return None
    return s._root.trace_id, s.span_id


def set_remote_parent(trace_id: int, span_id: int, origin: str):
    """Adopt an incoming cross-process trace context: the next root
    span opened under this token joins `trace_id` as a child of the
    sender's `span_id`. Returns a reset token (None when tracing is off
    or the context is empty — pass it straight to reset_remote_parent)."""
    if not ENABLED or not trace_id:
        return None
    return _remote.set((trace_id, span_id, origin))


def reset_remote_parent(token) -> None:
    if token is not None:
        _remote.reset(token)


def tag_current(**tags) -> None:
    """Attach tags to the innermost open span, if any."""
    if not ENABLED:
        return
    s = _current.get()
    if s is not None:
        s.tag(**tags)


class FlightRecorder:
    """Per-broker store of finished span trees + fault events."""

    def __init__(
        self,
        ring_capacity: int = RING_CAP,
        slow_ms: float = SLOW_MS,
        node_id: int = -1,
        shard: int = 0,
    ):
        self.node_id = node_id
        self.shard = shard
        self.slow_ns = int(slow_ms * 1e6)
        self._ring: list[Optional[dict]] = [None] * max(1, ring_capacity)
        self._ring_idx = 0
        self._frozen: deque[dict] = deque(maxlen=FROZEN_CAP)
        self._events: deque[dict] = deque(maxlen=EVENTS_CAP)
        self.trees_total = 0
        self.frozen_total = 0

    def span(self, name: str, kind: str = "run", **tags):
        """Open a *root* span recorded into this recorder."""
        if not ENABLED:
            return _NOOP
        return Span(name, kind, None, self, tags or None)

    def _finish_tree(self, root: Span) -> None:
        tree = {
            "trace_id": root.trace_id,
            "root": root.name,
            "dur_ns": root.dur_ns,
            "spans": root._spans,
            "node": self.node_id,
            "shard": self.shard,
        }
        if root.origin is not None:
            # continuation of a remote trace: the root's parent span
            # lives in another process's dump (stitch by trace_id)
            tree["remote_parent"] = root.parent_id
            tree["origin"] = root.origin
        self.trees_total += 1
        self._ring[self._ring_idx] = tree
        self._ring_idx = (self._ring_idx + 1) % len(self._ring)
        if root.dur_ns >= self.slow_ns:
            self.frozen_total += 1
            self._frozen.append(tree)

    def record_event(self, name: str, **tags) -> None:
        """Out-of-band marker (e.g. a NemesisNet fault firing): logged
        here and tagged onto the task's current span if one is open."""
        if not ENABLED:
            return
        self._events.append(
            {"name": name, "at_ns": time.monotonic_ns(), "tags": tags}
        )
        s = _current.get()
        if s is not None:
            s.tag(**{name: tags or True})

    def ring_tail(self, n: int = 50) -> list[dict]:
        """Most recent completed trees, newest last."""
        cap = len(self._ring)
        out = []
        for i in range(cap):
            t = self._ring[(self._ring_idx + i) % cap]
            if t is not None:
                out.append(t)
        return out[-n:]

    def frozen(self) -> list[dict]:
        return list(self._frozen)

    def events(self) -> list[dict]:
        return list(self._events)

    def dump(self, tail: int = 50) -> dict:
        """JSON-ready dump for /v1/debug/traces and tools/log_viewer."""
        return {
            "node_id": self.node_id,
            "shard": self.shard,
            "enabled": ENABLED,
            "slow_threshold_ms": self.slow_ns / 1e6,
            "trees_total": self.trees_total,
            "frozen_total": self.frozen_total,
            "frozen": self.frozen(),
            "ring": self.ring_tail(tail),
            "events": self.events(),
        }


class WindowStore:
    """What the spans of this process add up to since the last
    reset(): the store a benchmark window reads through
    `devplane.status()`. Process-global like the devplane registry (the
    in-process brokers of a test or a benchmark share one event loop
    and one device)."""

    RAW_CAP = 1 << 18
    # sleep intervals a traced window keeps (two stamps each)
    SLEEPS_CAP = 1 << 19

    def __init__(self) -> None:
        # raw records are kept only at the device plane's full
        # fidelity (devplane.reset() says when); aggregates always
        self.keep_raw = False
        self.reset()

    def reset(self) -> None:
        # name -> [kind, count, total_ns, self_ns, histogram]
        self._agg: dict[str, list] = {}
        self._raw: list[list] = []
        self.dropped = 0
        self._lag = HistogramChild()
        self._lag_max = 0.0
        # the loop probe's selector hook (LoopLagProbe._hook) adds here:
        # passes, nanoseconds outside and inside a sleeping select, how
        # late a timer's wake-up came, and in a traced window every
        # sleep as [start_ns, end_ns] (never among the span records)
        self.passes = 0
        self.awake_ns = 0
        self.asleep_ns = 0
        self.wake_late = HistogramChild()
        # the same wake-ups past the timeout rounded up to a whole
        # millisecond, as epoll waits: what its rounding does not explain
        self.wake_late_rest = HistogramChild()
        self.sleeps = array("q")
        self.sleeps_dropped = 0
        self.loop_mark = time.monotonic_ns()

    def add(self, s: Span, self_ns: int) -> None:
        a = self._agg.get(s.name)
        if a is None:
            a = self._agg[s.name] = [s.kind, 0, 0, 0, HistogramChild()]
        a[1] += 1
        a[2] += s.dur_ns
        a[3] += self_ns
        a[4].observe(s.dur_ns / 1e9)
        if self.keep_raw:
            if len(self._raw) < self.RAW_CAP:
                self._raw.append([
                    s.name, s.kind, s.start_ns, s.dur_ns, s.span_id,
                    s.parent_id, s._root.trace_id, s.tags,
                ])
            else:
                self.dropped += 1

    def add_lag(self, seconds: float) -> None:
        self._lag.observe(seconds)
        if seconds > self._lag_max:
            self._lag_max = seconds

    def status(self) -> dict:
        loop = {
            "samples": self._lag._count,
            "lag_p50_ms": self._lag.quantile(0.50) * 1e3,
            "lag_p99_ms": self._lag.quantile(0.99) * 1e3,
            "lag_max_ms": self._lag_max * 1e3,
            "passes": self.passes,
            "awake_s": self.awake_ns / 1e9,
            "asleep_s": self.asleep_ns / 1e9,
            "wake_late_p50_ms": self.wake_late.quantile(0.50) * 1e3,
            "wake_late_p99_ms": self.wake_late.quantile(0.99) * 1e3,
            "wake_late_count": self.wake_late._count,
            "wake_late_rest_p50_ms": self.wake_late_rest.quantile(0.50) * 1e3,
            "sleeps_dropped": self.sleeps_dropped,
        }
        if self.keep_raw:
            loop["sleeps"] = self.sleeps.tolist()
        return {
            "host": {
                name: {
                    "kind": kind,
                    "count": n,
                    "total_s": total / 1e9,
                    "self_s": own / 1e9,
                    "p50_ms": h.quantile(0.50) * 1e3,
                    "p99_ms": h.quantile(0.99) * 1e3,
                }
                for name, (kind, n, total, own, h) in sorted(self._agg.items())
            },
            "loop": loop,
            "spans": list(self._raw),
            "spans_dropped": self.dropped,
        }


WINDOW = WindowStore()


class LoopLagProbe:
    """How long ready work waits for the one thread everything shares:
    a timer due every 10 ms records how late it ran (Seastar's reactor
    stall detector, as a histogram). And the loop traced from inside:
    its selector's `select` is shadowed so that each pass of the loop
    says whether it slept and for how long (`_hook`). One per event
    loop, refcounted across the brokers that share the loop."""

    INTERVAL_S = 0.010
    _by_loop: dict = {}

    def __init__(self, loop: asyncio.AbstractEventLoop) -> None:
        self._loop = loop
        self._refs = 0
        self._handle: Optional[asyncio.TimerHandle] = None
        self._due = 0.0
        self._selector = None

    @classmethod
    def acquire(cls) -> None:
        """Start (or share) the running loop's probe; a no-op with
        tracing off."""
        if not ENABLED:
            return
        loop = asyncio.get_running_loop()
        probe = cls._by_loop.get(loop)
        if probe is None:
            # dead loops must not pile up (suites create thousands)
            cls._by_loop = {
                l: p for l, p in cls._by_loop.items() if not l.is_closed()
            }
            probe = cls._by_loop[loop] = cls(loop)
        probe._refs += 1
        if probe._refs == 1:
            probe._arm()
            probe._hook()

    @classmethod
    def release(cls) -> None:
        if not ENABLED:
            return
        loop = asyncio.get_running_loop()
        probe = cls._by_loop.get(loop)
        if probe is None:
            return
        probe._refs -= 1
        if probe._refs <= 0:
            if probe._handle is not None:
                probe._handle.cancel()
            probe._unhook()
            del cls._by_loop[loop]

    def _hook(self) -> None:
        """Shadow the selector's bound `select` on the instance. Per
        pass: the time since the last `select` returned is awake; the
        time inside a `select` with a timeout other than 0 is asleep (a
        `select(0)` is a poll, and counts as awake); a sleep that ended
        with no event was a timer's wake-up, and how far past the
        timeout asyncio asked for (before epoll rounds it up to a whole
        millisecond) it returned goes to `wake_late`, how far past the
        timeout rounded up to a whole millisecond to `wake_late_rest`.
        The clock is the spans' own, so a reader aligns both with one
        offset."""
        sel = getattr(self._loop, "_selector", None)
        if sel is None:
            return  # not a selector loop: nothing to shadow
        self._selector = sel
        inner = sel.select
        w, clock, ceil = WINDOW, time.monotonic_ns, math.ceil
        cap = 2 * WindowStore.SLEEPS_CAP

        def select(timeout=None):
            t0 = clock()
            w.awake_ns += t0 - w.loop_mark
            w.passes += 1
            events = inner(timeout)
            if timeout == 0:
                w.loop_mark = t0
                return events
            t1 = w.loop_mark = clock()
            w.asleep_ns += t1 - t0
            if not events and timeout is not None:
                slept = (t1 - t0) / 1e9
                w.wake_late.observe(slept - timeout)
                w.wake_late_rest.observe(slept - ceil(timeout * 1e3) / 1e3)
            if w.keep_raw:
                if len(w.sleeps) < cap:
                    w.sleeps.append(t0)
                    w.sleeps.append(t1)
                else:
                    w.sleeps_dropped += 1
            return events

        w.loop_mark = clock()
        sel.select = select

    def _unhook(self) -> None:
        sel, self._selector = self._selector, None
        if sel is not None:
            del sel.select  # the class's bound method again

    def _arm(self) -> None:
        self._due = self._loop.time() + self.INTERVAL_S
        self._handle = self._loop.call_at(self._due, self._fire)

    def _fire(self) -> None:
        WINDOW.add_lag(max(0.0, self._loop.time() - self._due))
        self._arm()


# fallback recorder for spans opened outside any broker (unit tests,
# bench one-offs); brokers own their own instance
_default_recorder = FlightRecorder()


def default_recorder() -> FlightRecorder:
    return _default_recorder
