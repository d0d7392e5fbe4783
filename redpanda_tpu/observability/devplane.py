"""devplane — runtime telemetry for the device plane (`RP_DEVPLANE=1`).

The host/asyncio side of the broker is richly observable (metrics
registry, flightdata ring, burn-rate alerts, flight-recorder spans),
but the mesh tick frame and the ops/ kernels that ARE the tpu_offload
path emit nothing at runtime. This module is the measurement plane a
real-ICI validation run reports from, built on three legs:

  * **Frame/kernel timing** — `instrument(fn, name)` brackets a jit'd
    kernel with a dispatch→ready latency histogram (every Nth call pays
    the `block_until_ready` sync; `RP_DEVPLANE_SAMPLE` tunes N), and
    `frame_scope(kind)` brackets one full mesh frame, opening a trace
    span that joins the task's current span — a frozen slow-request
    trace shows the device frame it waited on.
  * **Transfer accounting** — `count_transfer` totals host↔device bytes
    by direction and `count_fold` counts cross-chip folds, making the
    RPL018 static discipline a *runtime* invariant: the mesh backend
    asserts `devplane_frame_folds_total == devplane_frames_total`
    (exactly one cross-chip fold per frame), and any device dispatch or
    transfer inside `tick_scope()` but outside a frame bumps
    `devplane_tick_transfers_total` — which an alert rule watches.
  * **Compile events** — `utils/compileguard.py`'s jax.monitoring hook
    is promoted to first-class metrics: compile count + duration per
    kernel, labeled warmup vs steady, feeding the recompile-storm
    burn-rate alert rule. The probe wrappers push the compileguard
    attribution stack themselves, so attribution works with the guard
    off.

The plane also says WHICH device it is observing: `device()` /
the `devplane_device_info` gauge / the digest's `device` block carry
platform, device_kind and device count, read off the arrays the first
instrumented kernel returned (never assumed from configuration), and
`startup_check()` refuses the one silent fallback there is — a device
switch on, and JAX quietly on the CPU because it found no accelerator.

All families live in one process-global `registry` (the device is
process-global; broker instances are not) and are *adopted* into each
broker/shard registry (`MetricsRegistry.adopt`), so they ride the
ordinary `/metrics` scrape, the fleet snapshot protocol, and the
flightdata history ring — windowed frame-latency quantiles reach
`alerts.py` with no extra plumbing. `GET /v1/devplane` renders the
merged digest; worker shards ship their registries as the same serde
`RegistrySnapshot` envelope `/metrics` uses (RPL009).

Off-state (`RP_DEVPLANE` unset) is zero-overhead **by construction**,
the compileguard/rpsan recipe: `instrument(f, n) is f` — no wrapper,
no per-call branch on the tick path. Scope helpers degrade to
pass-through context managers and recording calls to early returns;
none of them sit on the steady tick path's per-event hot loop.
"""

from __future__ import annotations

import logging
import os
import time
from contextlib import contextmanager

from ..metrics import HistogramChild, MetricsRegistry, _NBUCKETS
from ..utils import compileguard
from . import trace

logger = logging.getLogger("devplane")

ENABLED = os.environ.get("RP_DEVPLANE", "") == "1"


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


#: every Nth dispatch of an instrumented kernel pays the
#: block_until_ready sync that yields a dispatch→ready sample (the
#: first call always samples, so cold kernels are visible immediately)
SAMPLE_EVERY = max(1, _env_int("RP_DEVPLANE_SAMPLE", 16))

#: process-global registry the broker registries adopt; the prefix
#: matches theirs so family names merge transparently
registry = MetricsRegistry()

_KERNEL_HIST = registry.histogram(
    "devplane_kernel_seconds",
    "sampled dispatch->ready latency per instrumented kernel (labels: "
    "the static instrument() name set, RPL012)",
)
_FRAME_HIST = registry.histogram(
    "devplane_frame_seconds",
    "full mesh-frame dispatch->ready latency (labels: frame kind, "
    "tick|health)",
)
_FRAMES = registry.counter(
    "devplane_frames_total",
    "full device frames run, by frame kind",
)
_FOLDS = registry.counter(
    "devplane_frame_folds_total",
    "cross-chip folds dispatched; the RPL018 runtime invariant is "
    "exactly one per frame (== devplane_frames_total)",
)
_TRANSFER_BYTES = registry.counter(
    "devplane_transfer_bytes_total",
    "host<->device transfer bytes, by direction (h2d|d2h)",
)
_STATE_SEEDS = registry.counter(
    "devplane_state_seeds_total",
    "whole-lane uploads that seeded a resident device state: one a "
    "prewarm, capacity doubling or backend change; any more means the "
    "tick is re-uploading its lanes",
)
_SEQUENCES = registry.counter(
    "devplane_producer_sequences_total",
    "idempotent-producer sequence checks on the produce path (rm_stm "
    "dedupe), by result: checked (every batch that carries a producer "
    "id and a sequence), duplicate (a resent batch answered with its "
    "first offset), out_of_order (refused)",
)
_GROUPS = registry.counter(
    "devplane_group_coordinator_total",
    "group coordinator events, by event: rebalances (generation bumps), "
    "tx_offsets_staged, tx_offsets_committed and tx_offsets_dropped "
    "(transactional offsets, by partition), txn_offset_commits_fenced "
    "(TxnOffsetCommit requests refused by the member, generation, "
    "instance or producer-epoch fence), unstable_offset_fetches "
    "(OffsetFetch requests that answered a partition "
    "UNSTABLE_OFFSET_COMMIT)",
)
_TX_MARKERS = registry.counter(
    "devplane_tx_markers_total",
    "transaction markers the tx coordinator delivered, by event: data "
    "(a data partition's control batch), group (a group's marker), "
    "remote (either, delivered over RPC to another broker's leader), "
    "retried (either, delivered after more than one round)",
)
_TICK_TRANSFERS = registry.counter(
    "devplane_tick_transfers_total",
    "device transfers/dispatches observed on the steady tick path "
    "OUTSIDE a frame — any nonzero is an RPL018 runtime breach",
)
_COMPILES = registry.counter(
    "devplane_compiles_total",
    "XLA backend compiles attributed per kernel, by compileguard "
    "phase (warmup|steady); steady compiles feed the recompile-storm "
    "alert",
)
_COMPILE_SECS = registry.counter(
    "devplane_compile_seconds_total",
    "XLA backend compile wall seconds attributed per kernel and phase",
)

#: full family names (registry prefix applied) — the set the digest,
#: the flightdata windows, and the alert rules all key on
KERNEL_FAMILY = _KERNEL_HIST.name
FRAME_FAMILY = _FRAME_HIST.name
FRAMES_FAMILY = _FRAMES.name
FOLDS_FAMILY = _FOLDS.name
TRANSFER_FAMILY = _TRANSFER_BYTES.name
STATE_SEEDS_FAMILY = _STATE_SEEDS.name
SEQUENCES_FAMILY = _SEQUENCES.name
GROUPS_FAMILY = _GROUPS.name
TX_MARKERS_FAMILY = _TX_MARKERS.name
TICK_TRANSFER_FAMILY = _TICK_TRANSFERS.name
COMPILES_FAMILY = _COMPILES.name
COMPILE_SECS_FAMILY = _COMPILE_SECS.name
JIT_CACHE_FAMILY = f"{registry.prefix}_devplane_jit_cache_entries"

_JIT_CACHE_HELP = (
    "jit cache entries per registered kernel "
    "(compileguard.compile_counts, the series bench deltas grade)"
)


def _jit_cache_samples() -> list[tuple[dict, float]]:
    return [
        ({"kernel": k}, float(v))
        for k, v in compileguard.compile_counts().items()
    ]


registry.gauge(
    "devplane_jit_cache_entries", _jit_cache_samples, _JIT_CACHE_HELP
)


def enabled() -> bool:
    return ENABLED


def register(reg: MetricsRegistry) -> None:
    """Wire the devplane into a broker/shard registry. Armed: adopt
    every process-global family (they then ride this registry's scrape,
    fleet snapshot, and flightdata ring). Disarmed: only the jit-cache
    gauge family exports — compileguard registration is unconditional,
    so the series bench deltas grade is always scrapeable."""
    if ENABLED:
        reg.adopt(registry)
    else:
        reg.gauge(
            "devplane_jit_cache_entries", _jit_cache_samples, _JIT_CACHE_HELP
        )


# ------------------------------------------------------ device identity
#: the switches that route served-path work to a device, with the
#: values that select it (raft/shard_state._backend, models/record,
#: compression._zstd_backend, kafka/server.fetch_verify_enabled)
_DEVICE_SWITCHES = {
    "RP_QUORUM_BACKEND": ("device", "mesh"),
    "RP_CRC_BACKEND": ("device",),
    "RP_CODEC_BACKEND": ("device",),
    "RP_ZSTD_BACKEND": ("tpu",),
    "RP_FETCH_VERIFY": ("1",),
}

#: what this process's device plane runs on, as JAX reports it:
#: {"platform", "device_kind", "device_count"}. None until the first
#: instrumented kernel returns (or startup_check ran) — reading it
#: earlier would initialise a backend the process may never need.
_DEVICE: "dict | None" = None


def device_switches() -> dict[str, str]:
    """The device-selecting switches that are on in this environment."""
    on = {}
    for name, values in _DEVICE_SWITCHES.items():
        v = os.environ.get(name, "").strip().lower()
        if v in values:
            on[name] = v
    return on


def format_switches(on: dict[str, str]) -> str:
    return " ".join(f"{k}={v}" for k, v in sorted(on.items()))


def _note_device(dev) -> dict:
    global _DEVICE
    import jax

    _DEVICE = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }
    return _DEVICE


def device() -> "dict | None":
    """Where the device plane's kernels ran (None: none has run yet)."""
    return _DEVICE


def holds_accelerator() -> bool:
    """True once this process has run device work on a non-CPU
    platform: it owns the chip, and a forked or spawned child that
    needs one would fail or hang."""
    return _DEVICE is not None and _DEVICE["platform"] != "cpu"


def startup_check() -> "dict | None":
    """Broker start-up: say which device the configured device plane
    runs on, and refuse the one silent fallback there is — a device
    switch is on, JAX was not told which platform to use, and it found
    no accelerator, so every "device" kernel would run on XLA:CPU.
    JAX_PLATFORMS=cpu (tests, the smokes) states that choice and passes.
    With no switch on, JAX's backend is left untouched."""
    on = device_switches()
    if not on:
        logger.info("device plane: host (no device switch is set)")
        return None
    import jax

    facts = _note_device(jax.devices()[0])
    asked = jax.config.jax_platforms or ""
    switches = format_switches(on)
    if facts["platform"] == "cpu" and asked.split(",")[0] != "cpu":
        raise RuntimeError(
            f"device plane configured ({switches}) but JAX found no "
            f"accelerator and fell back to platform 'cpu' "
            f"(JAX_PLATFORMS={asked!r}). A chip belongs to one process "
            "at a time: another process may hold it. Set "
            "JAX_PLATFORMS=cpu to run the device path on XLA:CPU on "
            "purpose."
        )
    logger.info(
        "device plane: %s on platform=%s device_kind=%s device_count=%d",
        switches,
        facts["platform"],
        facts["device_kind"],
        facts["device_count"],
    )
    return facts


def _device_samples() -> list[tuple[dict, float]]:
    d = _DEVICE
    if d is None:
        return []
    return [
        (
            {"platform": d["platform"], "device_kind": d["device_kind"]},
            float(d["device_count"]),
        )
    ]


DEVICE_FAMILY = f"{registry.prefix}_devplane_device_info"

registry.gauge(
    "devplane_device_info",
    _device_samples,
    "the device the instrumented kernels run on, as JAX reports it "
    "(labels: platform, device_kind; value: visible device count); "
    "absent until the first kernel has run",
)


# ---------------------------------------------------------------- scopes
_TICK_DEPTH = 0
_FRAME_DEPTH = 0


@contextmanager
def tick_scope():
    """Declare the steady tick path: entered by the mesh backend's
    per-tick sweep. Device activity inside this scope but outside a
    `frame_scope` is the RPL018 breach the tick-transfer counter (and
    its alert rule) exists to catch."""
    global _TICK_DEPTH
    if not ENABLED:
        yield
        return
    _TICK_DEPTH += 1
    try:
        yield
    finally:
        _TICK_DEPTH -= 1


@contextmanager
def frame_scope(kind: str):
    """Bracket one full device frame (`kind` from the static set
    tick|health): frames counter, dispatch→ready histogram, and a
    trace span that joins the task's current span so slow-request
    trees show the device frame they waited on."""
    global _FRAME_DEPTH
    if not ENABLED:
        yield
        return
    _FRAME_DEPTH += 1
    t0 = time.perf_counter()
    try:
        with trace.span("devplane.frame", frame=kind):
            yield
    finally:
        _FRAME_DEPTH -= 1
        _FRAME_HIST.labels(frame=kind).observe(time.perf_counter() - t0)
        _FRAMES.inc(frame=kind)


def in_frame() -> bool:
    return _FRAME_DEPTH > 0


def count_fold(n: int = 1) -> None:
    """One cross-chip fold dispatched (the mesh frame's totals
    reduction). The runtime RPL018 invariant is folds == frames."""
    if ENABLED:
        _FOLDS.inc(float(n))


def count_transfer(nbytes: int, direction: str) -> None:
    """Account `nbytes` of host<->device traffic (`direction` from the
    static set h2d|d2h). A transfer on the tick outside a frame is a
    discipline breach and bumps the tick-transfer counter."""
    if not ENABLED:
        return
    _TRANSFER_BYTES.inc(float(nbytes), direction=direction)
    if _TICK_DEPTH and not _FRAME_DEPTH:
        _TICK_TRANSFERS.inc(kind="transfer")


def count_sequence(result: str) -> None:
    """One producer sequence check (`result` from the static set
    checked|duplicate|out_of_order; cluster/partition.py, beside
    ProducerStateTable.check). A pass-through produce, producer id -1,
    never gets here."""
    if ENABLED:
        _SEQUENCES.inc(result=result)


#: the events `count_group` takes, each always served (0 until it moves)
GROUP_EVENTS = (
    "rebalances", "tx_offsets_staged", "tx_offsets_committed",
    "tx_offsets_dropped", "txn_offset_commits_fenced",
    "unstable_offset_fetches",
)


def count_group(event: str, n: int = 1) -> None:
    """`n` group coordinator events (`event` from GROUP_EVENTS;
    kafka/coordinator). A produce or a transaction that names no group
    never gets here."""
    if ENABLED and n:
        _GROUPS.inc(float(n), event=event)


#: the events `count_tx_marker` takes, each always served (0 until it moves)
TX_MARKER_EVENTS = ("data", "group", "remote", "retried")


def count_tx_marker(event: str) -> None:
    """One transaction marker delivered (`event` from TX_MARKER_EVENTS;
    cluster/tx_coordinator.py `_complete`). A produce with producer id
    -1 never gets here."""
    if ENABLED:
        _TX_MARKERS.inc(event=event)


def count_state_seed() -> None:
    """The tick had no resident device state left and uploaded its
    lanes whole (ShardGroupArrays._fold_on_device)."""
    if ENABLED:
        _STATE_SEEDS.inc()


# -------------------------------------------------------------- kernels
def _block_until_ready(out):
    import jax

    return jax.block_until_ready(out)


def _note_output_device(out) -> None:
    """Record where a kernel's result actually lives — the platform is
    read off the returned arrays, not assumed from configuration."""
    import jax

    for leaf in jax.tree_util.tree_leaves(out):
        devs = getattr(leaf, "devices", None)
        if devs is not None:
            _note_device(min(devs(), key=lambda d: d.id))
            return


class _Probe:
    """Dispatch→ready probe for one instrumented kernel: forwards to
    the underlying callable (a raw jit fn or compileguard._Guard),
    samples latency every Nth call via block_until_ready, keeps the
    compile-attribution stack current, and flags tick-path dispatches
    outside a frame."""

    __slots__ = ("fn", "name", "_child", "_n")

    def __init__(self, fn, name: str) -> None:
        self.fn = fn
        self.name = name
        self._child = _KERNEL_HIST.labels(kernel=name)
        self._n = 0

    def _cache_size(self) -> int:
        return int(self.fn._cache_size())

    def __call__(self, *args, **kwargs):
        if _TICK_DEPTH and not _FRAME_DEPTH:
            _TICK_TRANSFERS.inc(kind="dispatch")
        self._n += 1
        compileguard.push_kernel(self.name)
        try:
            if self._n != 1 and self._n % SAMPLE_EVERY:
                return self.fn(*args, **kwargs)
            t0 = time.monotonic_ns()
            out = self.fn(*args, **kwargs)
            out = _block_until_ready(out)
            t1 = time.monotonic_ns()
            self._child.observe((t1 - t0) / 1e9)
            # dispatch→ready on the span clock: the device execution
            # lies inside it, which is what lays host spans beside a
            # profiler trace
            trace.record("device.dispatch", "run", t0, t1, kernel=self.name)
            if _DEVICE is None:
                _note_output_device(out)
            return out
        finally:
            compileguard.pop_kernel()

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<devplane {self.name} of {self.fn!r}>"


def instrument(fn, name: str):
    """Return the callable to bind for kernel `name`. Off-state this
    IS `fn` (structural absence: `instrument(f, n) is f` — zero
    overhead by construction, the compileguard recipe); armed, a
    `_Probe`. Stacks outside compileguard.instrument at the kernel
    sites: the guard sees the raw jit cache, the probe sees the
    guarded dispatch."""
    if not ENABLED:
        return fn
    return _Probe(fn, name)


# ------------------------------------------------------- compile events
def _on_compile(kernel: str, secs: float, phase: str) -> None:
    _COMPILES.inc(kernel=kernel, phase=phase)
    _COMPILE_SECS.inc(secs, kernel=kernel, phase=phase)


if ENABLED:
    compileguard.subscribe_compiles(_on_compile)


# ---------------------------------------------------------- alert rules
def alert_rules() -> list:
    """Devplane burn-rate rules for `AlertManager.rules` — empty when
    the plane is disarmed (the families would never move):

      * device_recompile_storm — any steady-phase XLA compile in the
        window (threshold 0 over the compiles counter delta);
      * device_tick_transfer  — any device transfer/dispatch on the
        tick outside a frame (the RPL018 runtime invariant, live);
      * device_frame_p99      — windowed frame dispatch→ready p99 vs
        `RP_DEVPLANE_FRAME_SLO_MS` (default 250 ms).
    """
    if not ENABLED:
        return []
    from . import alerts as _alerts

    try:
        frame_slo_ms = float(
            os.environ.get("RP_DEVPLANE_FRAME_SLO_MS", "") or 250.0
        )
    except ValueError:
        frame_slo_ms = 250.0
    return [
        _alerts.AlertRule(
            "device_recompile_storm", "counter", COMPILES_FAMILY,
            {"phase": "steady"}, 0.0, 0.0, "compiles",
            "steady-phase XLA recompiles of instrumented kernels — any "
            "in-window compile is a storm precursor",
        ),
        _alerts.AlertRule(
            "device_tick_transfer", "counter", TICK_TRANSFER_FAMILY,
            None, 0.0, 0.0, "events",
            "device transfers/dispatches on the steady tick path "
            "outside a frame (RPL018 runtime breach)",
        ),
        _alerts.AlertRule(
            "device_frame_p99", "quantile", FRAME_FAMILY, None,
            0.99, frame_slo_ms / 1000.0, "s",
            "windowed mesh-frame dispatch->ready p99 vs the declared "
            "frame budget",
        ),
    ]


# ------------------------------------------------------- fleet surface
def snapshot(shard: int = 0, node: int = -1):
    """This process's devplane registry as the same serde
    `RegistrySnapshot` envelope `/metrics` ships (RPL009: nothing
    pickled crosses the shard boundary)."""
    from . import fleet

    return fleet.snapshot_registry(registry, shard, node)


def _hist_digest(c: HistogramChild) -> dict:
    return {
        "count": c._count,
        "p50_ms": c.quantile(0.50) * 1e3,
        "p99_ms": c.quantile(0.99) * 1e3,
        "p999_ms": c.quantile(0.999) * 1e3,
        "mean_ms": (c._sum / c._count * 1e3) if c._count else 0.0,
    }


def merged_status(snaps: list) -> dict:
    """JSON digest of one or more devplane `RegistrySnapshot`s (one
    per shard): counters summed, histogram buckets merged exactly
    before the quantiles, jit-cache entries max'd (each process
    compiles its own copy of the same programs)."""
    frames: dict[str, float] = {}
    folds = 0.0
    transfers: dict[str, float] = {}
    state_seeds = 0.0
    sequences: dict[str, float] = {}
    groups: dict[str, float] = dict.fromkeys(GROUP_EVENTS, 0.0)
    markers: dict[str, float] = dict.fromkeys(TX_MARKER_EVENTS, 0.0)
    tick_violations = 0.0
    compiles: dict[str, dict] = {}
    jit_cache: dict[str, float] = {}
    device: "dict | None" = None
    frame_hist: dict[str, HistogramChild] = {}
    kernel_hist: dict[str, HistogramChild] = {}
    for snap in snaps:
        for fam in snap.families:
            for s in fam.samples:
                lab = dict(s.labels)
                if fam.name == FRAMES_FAMILY and "frame" in lab:
                    k = lab["frame"]
                    frames[k] = frames.get(k, 0.0) + s.value
                elif fam.name == FOLDS_FAMILY:
                    folds += s.value
                elif fam.name == TRANSFER_FAMILY and "direction" in lab:
                    d = lab["direction"]
                    transfers[d] = transfers.get(d, 0.0) + s.value
                elif fam.name == STATE_SEEDS_FAMILY:
                    state_seeds += s.value
                elif fam.name == SEQUENCES_FAMILY and "result" in lab:
                    r = lab["result"]
                    sequences[r] = sequences.get(r, 0.0) + s.value
                elif fam.name == GROUPS_FAMILY and "event" in lab:
                    e = lab["event"]
                    groups[e] = groups.get(e, 0.0) + s.value
                elif fam.name == TX_MARKERS_FAMILY and "event" in lab:
                    e = lab["event"]
                    markers[e] = markers.get(e, 0.0) + s.value
                elif fam.name == TICK_TRANSFER_FAMILY:
                    tick_violations += s.value
                elif fam.name == COMPILES_FAMILY and "kernel" in lab:
                    ent = compiles.setdefault(
                        lab["kernel"],
                        {"warmup": 0.0, "steady": 0.0, "seconds": 0.0},
                    )
                    ph = lab.get("phase", "warmup")
                    ent[ph] = ent.get(ph, 0.0) + s.value
                elif fam.name == COMPILE_SECS_FAMILY and "kernel" in lab:
                    ent = compiles.setdefault(
                        lab["kernel"],
                        {"warmup": 0.0, "steady": 0.0, "seconds": 0.0},
                    )
                    ent["seconds"] += s.value
                elif fam.name == JIT_CACHE_FAMILY and "kernel" in lab:
                    k = lab["kernel"]
                    jit_cache[k] = max(jit_cache.get(k, 0.0), s.value)
                elif fam.name == DEVICE_FAMILY and device is None:
                    # one chip, one process: only one shard can report
                    device = {
                        "platform": lab.get("platform", ""),
                        "device_kind": lab.get("device_kind", ""),
                        "device_count": int(s.value),
                    }
        for hf in snap.hists:
            if hf.name == FRAME_FAMILY:
                store, key = frame_hist, "frame"
            elif hf.name == KERNEL_FAMILY:
                store, key = kernel_hist, "kernel"
            else:
                continue
            for series in hf.series:
                k = dict(series.labels).get(key, "")
                if not k:
                    continue
                c = series.to_child()
                prev = store.get(k)
                if prev is None:
                    store[k] = c
                else:
                    prev.merge_from(c)
    frames_total = sum(frames.values())
    return {
        "enabled": True,
        "sample_every": SAMPLE_EVERY,
        "shards": len(snaps),
        "device": device,
        "frames": {k: int(v) for k, v in sorted(frames.items())},
        "frames_total": int(frames_total),
        "folds": int(folds),
        "folds_per_frame": (folds / frames_total) if frames_total else 0.0,
        "transfer_bytes": {
            k: int(v) for k, v in sorted(transfers.items())
        },
        "state_seeds": int(state_seeds),
        "producer_sequences": {
            k: int(v) for k, v in sorted(sequences.items())
        },
        "group_coordinator": {k: int(v) for k, v in sorted(groups.items())},
        "tx_markers": {k: int(v) for k, v in sorted(markers.items())},
        "tick_violations": int(tick_violations),
        "frame_ms": {
            k: _hist_digest(c) for k, c in sorted(frame_hist.items())
        },
        "kernels": {
            k: _hist_digest(c) for k, c in sorted(kernel_hist.items())
        },
        "compiles": {k: v for k, v in sorted(compiles.items())},
        "jit_cache": {k: int(v) for k, v in sorted(jit_cache.items())},
    }


def status() -> dict:
    """Local-process digest (single-shard view of merged_status), with
    what the process's spans added up to since the last reset():
    `host`, `loop`, `spans` and `spans_dropped` (trace.WindowStore)."""
    if not ENABLED:
        return {"enabled": False}
    return {**merged_status([snapshot()]), **trace.WINDOW.status()}


# ------------------------------------------------------------- harness
def reset() -> None:
    """Zero every devplane counter and histogram in place (bench/test
    harness hook). In place because probes hold pre-resolved histogram
    child refs — the objects must survive the reset. Empties the span
    window store with them; raw span records are kept from here on
    only where the probes wait for every dispatch, the plane's full
    fidelity, so a sampled run pays for aggregates alone."""
    from .. import metrics as _metrics

    trace.WINDOW.keep_raw = ENABLED and SAMPLE_EVERY == 1
    trace.WINDOW.reset()

    for m in registry.families().values():
        if isinstance(m, _metrics.Counter):
            m._values.clear()
        elif isinstance(m, _metrics.Histogram):
            children = list(m._children.values())
            if m._default is not None:
                children.append(m._default)
            for c in children:
                c._buckets = [0] * _NBUCKETS
                c._overflow = 0
                c._sum = 0.0
                c._count = 0
