"""devplane: the device-plane telemetry layer (RP_DEVPLANE=1).

Off-state tests run in-process (tier-1 never sets RP_DEVPLANE, so the
default import IS the off state and the structural-absence claim —
`instrument(f, n) is f` — is checked directly, the compileguard
recipe: identity, not timing). On-state tests run armed subprocesses
(RP_DEVPLANE is read at import), including the 8-forced-host-devices
mesh leg where the RPL018 runtime invariant — exactly one cross-chip
fold per frame, `folds == frames_total` — is asserted live, and the
recompile-storm alert leg where a post-steady() shape wobble must
transition `device_recompile_storm` to firing.
"""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from redpanda_tpu.observability import devplane  # noqa: E402

_off = pytest.mark.skipif(
    devplane.enabled(), reason="suite assumes the default off state"
)


def _run_armed(tmp_path, body: str, extra_env: dict | None = None):
    """Run `body` in a subprocess with the devplane armed."""
    script = tmp_path / "armed.py"
    script.write_text(
        "import os, sys\n"
        'os.environ.setdefault("JAX_PLATFORMS", "cpu")\n'
        f"sys.path.insert(0, {REPO_ROOT!r})\n" + body
    )
    env = dict(os.environ, RP_DEVPLANE="1")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, str(script)],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
    )


# -- off state (the tier-1 default) ------------------------------------


@_off
def test_off_instrument_is_structural_passthrough():
    def fn(x):
        return x

    assert not devplane.enabled()
    # zero overhead BY CONSTRUCTION: the bound callable IS the kernel —
    # no wrapper object, no per-call branch on the tick path
    assert devplane.instrument(fn, "t.passthrough") is fn


@_off
def test_off_surface_degrades_not_errors():
    assert devplane.status() == {"enabled": False}
    assert devplane.alert_rules() == []
    # scopes pass through; recording calls are early returns
    with devplane.tick_scope():
        with devplane.frame_scope("tick"):
            assert not devplane.in_frame()  # depth untracked when off
        devplane.count_fold()
        devplane.count_transfer(4096, "h2d")


@_off
def test_off_register_exports_only_jit_cache_gauge():
    from redpanda_tpu.metrics import MetricsRegistry

    reg = MetricsRegistry()
    devplane.register(reg)
    fams = reg.families()
    assert f"{reg.prefix}_devplane_jit_cache_entries" in fams
    # the frame/kernel/transfer families stay out of disarmed scrapes
    assert devplane.FRAMES_FAMILY not in fams
    assert devplane.KERNEL_FAMILY not in fams


def test_adopt_aliases_families():
    from redpanda_tpu.metrics import MetricsRegistry

    src = MetricsRegistry()
    c = src.counter("t_adopted_total", "t")
    dst = MetricsRegistry()
    dst.adopt(src)
    # adoption aliases, never copies: increments through the source
    # are visible in the adopting registry's scrape
    c.inc(kind="x")
    assert dst.families()[c.name] is c


# -- on state (armed subprocesses) -------------------------------------

_MESH_INVARIANT = """\
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from redpanda_tpu.observability import devplane
from redpanda_tpu.raft.shard_state import ShardGroupArrays

assert devplane.enabled()
assert len(jax.devices()) == 8

def fn(x):
    return x

probe = devplane.instrument(fn, "t.probe")
assert probe is not fn and type(probe).__name__ == "_Probe"

arrays = ShardGroupArrays(capacity=64)
rows = np.array([arrays.alloc_row() for _ in range(8)], np.int64)
arrays.is_leader[rows] = True
arrays.touch()
mf = arrays.mesh_frame
window = (
    rows[:4],
    np.full(4, 1, np.int64),
    np.full(4, 5, np.int64),
    np.full(4, 4, np.int64),
    np.full(4, 1, np.int64),
)
N = 5
for _ in range(N):
    mf.run(arrays, *window)
mf.run_health(arrays)

st = devplane.status()
assert st["enabled"] is True
# the RPL018 runtime invariant: exactly one cross-chip fold per frame
assert st["frames_total"] == N + 1, st["frames"]
assert st["folds"] == st["frames_total"], (st["folds"], st["frames"])
assert st["frames"] == {"health": 1, "tick": N}, st["frames"]
assert st["folds_per_frame"] == 1.0
# transfer accounting moved in both directions
assert st["transfer_bytes"]["h2d"] > 0 and st["transfer_bytes"]["d2h"] > 0
# no device activity escaped onto a tick outside a frame
assert st["tick_violations"] == 0
# kernel latency histograms sampled (first call always samples)
assert st["kernels"]["mesh_frame.tick_frame"]["count"] >= 1
assert st["kernels"]["mesh_frame.tick_frame"]["p99_ms"] > 0
# compile events attributed to the frame kernels, warmup phase
assert st["compiles"]["mesh_frame.tick_frame"]["warmup"] >= 1
assert st["compiles"]["mesh_frame.tick_frame"]["seconds"] > 0
assert st["compiles"]["mesh_frame.tick_frame"]["steady"] == 0
print("ARMED-INVARIANT-OK", st["frames_total"], st["folds"])
"""


def test_armed_mesh_fold_invariant(tmp_path):
    out = _run_armed(tmp_path, _MESH_INVARIANT)
    assert out.returncode == 0, out.stderr
    assert "ARMED-INVARIANT-OK 6 6" in out.stdout


_TICK_BREACH = """\
import jax
import jax.numpy as jnp
from redpanda_tpu.observability import devplane
from redpanda_tpu.utils import compileguard

kern = devplane.instrument(
    compileguard.instrument(jax.jit(lambda x: x + 1), "t.kern"), "t.kern"
)
with devplane.tick_scope():
    with devplane.frame_scope("tick"):
        kern(jnp.zeros(8, jnp.int32))       # inside a frame: clean
        devplane.count_transfer(64, "h2d")
    assert devplane.status()["tick_violations"] == 0
    kern(jnp.zeros(8, jnp.int32))           # on the tick, no frame
    devplane.count_transfer(64, "h2d")      # ditto
st = devplane.status()
assert st["tick_violations"] == 2, st["tick_violations"]
# outside any tick scope, bare dispatches are not violations
kern(jnp.zeros(8, jnp.int32))
assert devplane.status()["tick_violations"] == 2
# the digest says what the kernels ran on, read off their results
dev = devplane.status()["device"]
assert dev["platform"] == "cpu", dev
assert dev["device_count"] == len(jax.devices()), dev
assert not devplane.holds_accelerator()
print("ARMED-BREACH-OK")
"""


def test_armed_tick_transfer_breach_counted(tmp_path):
    out = _run_armed(tmp_path, _TICK_BREACH)
    assert out.returncode == 0, out.stderr
    assert "ARMED-BREACH-OK" in out.stdout


_STORM = """\
import jax
import jax.numpy as jnp
from redpanda_tpu.metrics import MetricsRegistry
from redpanda_tpu.observability import alerts as _alerts
from redpanda_tpu.observability import devplane
from redpanda_tpu.observability.flightdata import MetricsHistory
from redpanda_tpu.utils import compileguard

reg = MetricsRegistry()
devplane.register(reg)                      # adopt: families ride reg
history = MetricsHistory(reg)
mgr = _alerts.AlertManager(
    history, rules=devplane.alert_rules(), profile="devplane-test"
)
names = [r.name for r in mgr.rules]
assert "device_recompile_storm" in names, names
assert "device_tick_transfer" in names, names
assert "device_frame_p99" in names, names

kern = devplane.instrument(
    compileguard.instrument(jax.jit(lambda x: x * 2), "t.kern"), "t.kern"
)
kern(jnp.zeros(8, jnp.int32))               # warmup trace: expected
compileguard.steady()
history.sample()
assert mgr.evaluate() == []                 # quiet: nothing fires
kern(jnp.ones(8, jnp.int32))                # warm signature: no growth
history.sample()
assert mgr.evaluate() == [], mgr.active
kern(jnp.zeros(16, jnp.int32))              # shape wobble: fresh trace
st = devplane.status()
assert st["compiles"]["t.kern"]["steady"] >= 1, st["compiles"]
history.sample()
fired = mgr.evaluate()
assert "device_recompile_storm" in [a["name"] for a in fired], fired
assert mgr.active["device_recompile_storm"]["state"] == "firing"
print("ARMED-STORM-OK")
"""


def test_armed_recompile_storm_alert_fires(tmp_path):
    out = _run_armed(tmp_path, _STORM)
    assert out.returncode == 0, out.stderr
    assert "ARMED-STORM-OK" in out.stdout


_ROUNDTRIP = """\
import jax
import jax.numpy as jnp
from redpanda_tpu.observability import devplane
from redpanda_tpu.observability.fleet import RegistrySnapshot
from redpanda_tpu.utils import compileguard

kern = devplane.instrument(
    compileguard.instrument(jax.jit(lambda x: x + 1), "t.kern"), "t.kern"
)
with devplane.frame_scope("tick"):
    devplane.count_fold()
    devplane.count_transfer(1024, "h2d")
    kern(jnp.zeros(8, jnp.int32))

snap = devplane.snapshot(shard=3, node=7)
wire = snap.encode()                        # the RPL009 serde envelope
back = RegistrySnapshot.decode(wire)
assert back.shard == 3 and back.node == 7
one = devplane.merged_status([back])
assert one["frames_total"] == 1 and one["folds"] == 1
assert one["kernels"]["t.kern"]["count"] == 1
# two shards shipping the same envelope: counters sum, histogram
# buckets merge exactly, jit-cache entries max (not sum)
two = devplane.merged_status([back, RegistrySnapshot.decode(wire)])
assert two["shards"] == 2
assert two["frames_total"] == 2 and two["folds"] == 2
assert two["folds_per_frame"] == 1.0
assert two["kernels"]["t.kern"]["count"] == 2
assert two["transfer_bytes"]["h2d"] == 2048
assert two["jit_cache"]["t.kern"] == one["jit_cache"]["t.kern"]
print("ARMED-ROUNDTRIP-OK")
"""


def test_armed_snapshot_roundtrip_and_fleet_merge(tmp_path):
    out = _run_armed(tmp_path, _ROUNDTRIP)
    assert out.returncode == 0, out.stderr
    assert "ARMED-ROUNDTRIP-OK" in out.stdout


_SAMPLING = """\
import jax
import jax.numpy as jnp
from redpanda_tpu.observability import devplane

assert devplane.SAMPLE_EVERY == 4
kern = devplane.instrument(jax.jit(lambda x: x + 1), "t.kern")
for _ in range(9):                          # calls 1, 4, 8 sample
    kern(jnp.zeros(8, jnp.int32))
st = devplane.status()
assert st["kernels"]["t.kern"]["count"] == 3, st["kernels"]
print("ARMED-SAMPLING-OK")
"""


def test_armed_sampling_cadence(tmp_path):
    out = _run_armed(
        tmp_path, _SAMPLING, extra_env={"RP_DEVPLANE_SAMPLE": "4"}
    )
    assert out.returncode == 0, out.stderr
    assert "ARMED-SAMPLING-OK" in out.stdout


# -- device identity and the one-process-per-chip refusals --------------


def _clear_switches(monkeypatch):
    for name in devplane._DEVICE_SWITCHES:
        monkeypatch.delenv(name, raising=False)


def test_device_switches_lists_only_device_values(monkeypatch):
    _clear_switches(monkeypatch)
    assert devplane.device_switches() == {}
    monkeypatch.setenv("RP_QUORUM_BACKEND", "host")
    monkeypatch.setenv("RP_FETCH_VERIFY", "0")
    assert devplane.device_switches() == {}
    monkeypatch.setenv("RP_QUORUM_BACKEND", "mesh")
    monkeypatch.setenv("RP_ZSTD_BACKEND", "TPU")
    assert devplane.device_switches() == {
        "RP_QUORUM_BACKEND": "mesh", "RP_ZSTD_BACKEND": "tpu",
    }


def test_startup_check_states_device_and_refuses_silent_cpu(monkeypatch):
    import jax

    _clear_switches(monkeypatch)
    monkeypatch.setattr(devplane, "_DEVICE", None)
    # no switch: the backend is left alone, nothing to state
    assert devplane.startup_check() is None
    assert devplane.device() is None
    # a switch on the CPU the process was TOLD to use (tests pin it)
    monkeypatch.setenv("RP_QUORUM_BACKEND", "device")
    facts = devplane.startup_check()
    assert facts == devplane.device()
    assert facts["platform"] == "cpu"
    assert facts["device_count"] == len(jax.devices())
    assert not devplane.holds_accelerator()
    # the same, but nobody asked for the CPU: JAX fell back to it
    class _NotAsked:
        jax_platforms = None

    monkeypatch.setattr(jax, "config", _NotAsked)
    with pytest.raises(RuntimeError, match="fell back to platform 'cpu'"):
        devplane.startup_check()


def test_sharded_broker_refuses_a_device_plane(monkeypatch, tmp_path):
    import asyncio

    from redpanda_tpu.app import BrokerConfig
    from redpanda_tpu.ssx import shards
    from redpanda_tpu.ssx.sharded_broker import ShardedBroker

    _clear_switches(monkeypatch)
    monkeypatch.setattr(devplane, "_DEVICE", None)
    assert shards.device_plane_conflict() is None
    monkeypatch.setenv("RP_QUORUM_BACKEND", "device")
    assert "one process" in shards.device_plane_conflict()
    owner = ShardedBroker(
        BrokerConfig(node_id=0, data_dir=str(tmp_path), members=[0]),
        n_shards=2,
    )
    with pytest.raises(RuntimeError, match="--shards 2 refused"):
        asyncio.run(owner.start())
    assert owner.runtime is None and owner.broker is None
    # and with no switch on, a parent that already holds a chip may
    # not fork either (spawn_shard / crash-restart from a live parent)
    _clear_switches(monkeypatch)
    monkeypatch.setattr(
        devplane, "_DEVICE",
        {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1},
    )
    assert devplane.holds_accelerator()
    runtime = shards.ShardRuntime(2, lambda *a: None)
    with pytest.raises(RuntimeError, match="shard fork refused"):
        runtime._fork_child(1)


# -- the span window store rides reset()/status() -----------------------

_WINDOW_KEYS = """\
import numpy as np
from redpanda_tpu.observability import devplane, trace

def fn(x):
    return x

kern = devplane.instrument(fn, "t.kern")
with trace.span("before.reset"):
    pass
devplane.reset()
with trace.span("outer", "wait"):
    for _ in range(3):
        kern(np.ones(4))
st = devplane.status()
assert st["enabled"] is True and st["sample_every"] == SAMPLE
assert "before.reset" not in st["host"], st["host"]
assert st["host"]["outer"]["kind"] == "wait"
assert st["host"]["outer"]["count"] == 1
d = st["host"]["device.dispatch"]
# the probe spans exactly the calls it times
assert d["count"] == st["kernels"]["t.kern"]["count"] == TIMED, d
assert d["kind"] == "run" and d["total_s"] > 0
assert st["host"]["outer"]["self_s"] <= st["host"]["outer"]["total_s"]
# the lag timer's digest, the selector hook's counters and, in a traced
# window, the loop's sleep intervals
assert set(st["loop"]) == {
    "samples", "lag_p50_ms", "lag_p99_ms", "lag_max_ms",
    "passes", "awake_s", "asleep_s", "wake_late_p50_ms", "wake_late_p99_ms",
    "wake_late_count", "wake_late_rest_p50_ms", "sleeps_dropped",
} | ({"sleeps"} if SAMPLE == 1 else set()), sorted(st["loop"])
assert st["spans_dropped"] == 0
if SAMPLE == 1:
    rows = [s for s in st["spans"] if s[0] == "device.dispatch"]
    assert len(rows) == 3
    assert all(s[7] == {"kernel": "t.kern"} for s in rows), rows
    outer = next(s for s in st["spans"] if s[0] == "outer")
    # [name, kind, start_ns, dur_ns, id, parent, trace_id, tags]
    assert all(s[5] == outer[4] and s[6] == outer[6] for s in rows)
    assert all(s[2] >= outer[2] for s in rows)
else:
    # sampled: aggregates only, no raw record is kept
    assert st["spans"] == []
print("ARMED-WINDOW-OK")
"""


@pytest.mark.parametrize("sample,timed", [(1, 3), (16, 1)])
def test_armed_status_carries_the_span_window(tmp_path, sample, timed):
    if os.environ.get("RP_TRACE") == "0":
        pytest.skip("RP_TRACE=0: no span reaches the store")
    body = _WINDOW_KEYS.replace("SAMPLE", str(sample)).replace(
        "TIMED", str(timed))
    out = _run_armed(
        tmp_path, body, extra_env={"RP_DEVPLANE_SAMPLE": str(sample)}
    )
    assert out.returncode == 0, out.stderr
    assert "ARMED-WINDOW-OK" in out.stdout


@_off
def test_off_reset_empties_the_window_and_status_stays_off():
    from redpanda_tpu.observability import trace

    if not trace.ENABLED:
        pytest.skip("RP_TRACE=0: no span reaches the store")
    with trace.span("t.off"):
        pass
    assert trace.WINDOW.status()["host"]["t.off"]["count"] == 1
    devplane.reset()
    assert trace.WINDOW.status()["host"] == {}
    # unarmed, no raw record is kept and the digest says only "off"
    with trace.span("t.off"):
        pass
    assert trace.WINDOW.status()["spans"] == []
    assert devplane.status() == {"enabled": False}
