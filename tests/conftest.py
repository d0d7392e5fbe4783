"""Test harness configuration.

Force JAX onto a virtual 8-device CPU platform so multi-chip sharding
(mesh/pjit/shard_map paths) is exercised without TPU hardware — the
strategy SURVEY.md §4.2 calls for (multi-"node" testing in one
process). Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: tests run on the CPU
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tests run on the CPU whatever the machine holds: pin the config too
# (must run before any backend is initialized). A CPU-pinned process
# also gets no persistent compile cache (redpanda_tpu/__init__.py).
import jax

jax.config.update("jax_platforms", "cpu")

import pytest


# -- SAME-frame fingerprint verification on by default ------------------
# RP_SAME_DEBUG=1 adds a CRC over the SAME lanes to every armed frame
# and every serve, turning a missed touch() into an immediate assertion
# instead of a silent stale read. The raft suites run with it armed
# unconditionally — the fuzz suite proved the check cheap enough, and a
# regression in the mut_epoch contract should fail HERE, not in chaos.

_SAME_DEBUG_MODULES = frozenset(
    {
        "test_raft",
        "test_raft_snapshot",
        "test_same_epoch_fuzz",
        "test_replicate_batcher",
        "test_membership",
        "test_recovery_throttle",
    }
)


@pytest.fixture(autouse=True)
def _same_debug_for_raft_tests(request):
    module = getattr(request, "module", None)
    if module is None or module.__name__ not in _SAME_DEBUG_MODULES:
        yield
        return
    from redpanda_tpu.raft import shard_state

    old = shard_state.SAME_DEBUG
    shard_state.SAME_DEBUG = True
    try:
        yield
    finally:
        shard_state.SAME_DEBUG = old


# -- timing-sensitive retry (1-core full-suite interference) -----------
# This environment has ONE core; the full suite's load occasionally
# pushes a timing-sensitive multi-broker test past its election/ack
# windows (each passes in isolation and on idle runs). Tests marked
# `timing` get exactly one quiet retry after a short drain, so a single
# scheduling hiccup doesn't fail an -x run; a real regression still
# fails twice and surfaces.


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timing: timing-sensitive on the 1-core host; retried once",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow')",
    )


def pytest_runtest_protocol(item, nextitem):
    # (pytest-rerunfailures would express this as @pytest.mark.flaky,
    # but no packages can be installed in this environment)
    if item.get_closest_marker("timing") is None:
        return None
    import time

    from _pytest.runner import runtestprotocol

    item.ihook.pytest_runtest_logstart(
        nodeid=item.nodeid, location=item.location
    )
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    call_failed = any(r.failed for r in reports if r.when == "call")
    other_failed = any(r.failed for r in reports if r.when != "call")
    if call_failed and not other_failed:
        # ONLY a clean call-phase failure earns the quiet retry; a
        # setup/teardown error is a real resource problem and must
        # surface unretried
        first_repr = "\n".join(
            str(r.longrepr) for r in reports if r.failed
        )[:4000]
        time.sleep(1.5)  # let queued loop work drain before the retry
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
        # the first attempt's traceback must not vanish: on a green
        # retry it is the only record of what flaked (and keeps chronic
        # flakiness countable); on a second failure the two attempts
        # may have failed DIFFERENTLY and both reprs matter
        import pytest as _pytest

        verdict = (
            "first attempt ALSO failed (second repr reported normally)"
            if any(r.failed for r in reports)
            else "retry absorbed a call-phase failure"
        )
        item.warn(
            _pytest.PytestWarning(
                f"timing retry: {verdict}; first attempt:\n{first_repr}"
            )
        )
    for r in reports:
        item.ihook.pytest_runtest_logreport(report=r)
    item.ihook.pytest_runtest_logfinish(
        nodeid=item.nodeid, location=item.location
    )
    return True
