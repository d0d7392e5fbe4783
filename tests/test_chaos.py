"""Chaos suite: faults under load with committed-data invariants.

Reference test model: rptest/services/failure_injector.py +
consistency-validating workloads (e.g. rptest
partition_movement/availability tests). Seeds are fixed so failures
reproduce; each scenario must end with every acked record intact.
"""

import asyncio

import pytest

from chaos_harness import run_chaos


def test_chaos_network_partitions(tmp_path):
    stats = asyncio.run(
        run_chaos(
            tmp_path,
            seed=101,
            duration_s=5.0,
            faults=("partition",),
            min_acked=21,
        )
    )
    assert stats["acked"] > 20, stats
    assert any(e[0] == "partition" for e in stats["events"])


def test_chaos_crash_restart(tmp_path):
    stats = asyncio.run(
        run_chaos(tmp_path, seed=202, duration_s=5.0, faults=("crash",))
    )
    assert stats["acked"] > 10, stats
    assert any(e[0] == "crash" for e in stats["events"])


def test_chaos_mixed_faults(tmp_path):
    stats = asyncio.run(
        run_chaos(
            tmp_path,
            seed=303,
            duration_s=6.0,
            faults=("partition", "crash", "transfer"),
        )
    )
    assert stats["acked"] > 10, stats


@pytest.mark.timing
@pytest.mark.parametrize("seed", [404, 1717])
def test_chaos_tiered_storage(tmp_path, seed):
    """Faults while archival + retention churn: acked data must stay
    readable across the remote/local seam, manifests must not point at
    missing objects, and the replicated archival boundary must agree.
    (Two seeds: seed 404 under CPU load reproduced the r3 archive-gap
    data-loss bug; seed diversity keeps the fault schedule from
    ossifying.)"""
    stats = asyncio.run(
        run_chaos(
            tmp_path,
            seed=seed,
            duration_s=6.0,
            faults=("partition", "crash", "transfer"),
            tiered=True,
        )
    )
    assert stats["acked"] > 10, stats
    assert stats["tiered_archived"] >= 1, stats  # uploads happened
    # retention actually trimmed locally, so the validator's
    # fetch-from-0 crossed the remote/local seam
    assert stats["tiered_trimmed"] >= 1, stats


def test_validator_catches_seeded_violations(tmp_path):
    """The harness must be able to CATCH bugs, not just pass: feed it a
    fabricated ack beyond the watermark (simulated committed-data loss)
    and a wrong-record claim (simulated corruption) and require both to
    trip (failure_injector suites validate their validator the same way)."""

    async def main():
        from chaos_harness import ChaosCluster, SeqProducer, validate
        from redpanda_tpu.kafka.client import KafkaClient

        cluster = ChaosCluster(tmp_path, n=3)
        await cluster.start()
        try:
            c = KafkaClient(cluster.addresses())
            await c.create_topic("chaos", partitions=1, replication_factor=3)
            p = SeqProducer(cluster, "chaos", 1)
            for i in range(5):
                off = await c.produce(
                    "chaos", 0, [(b"seq-%d" % i, b"payload-%d" % i)]
                )
                p.acked.append((0, off, i))
            await c.close()
            p.acked.append((0, 99, 99))  # phantom ack: loss
            with pytest.raises(AssertionError, match="committed data lost"):
                await validate(cluster, "chaos", 1, p)
            p.acked.pop()
            p.acked[2] = (0, 2, 777)  # wrong record: corruption
            with pytest.raises(AssertionError, match="expected seq 777"):
                await validate(cluster, "chaos", 1, p)
        finally:
            await cluster.stop()

    asyncio.run(main())


@pytest.mark.timing
def test_chaos_admin_ops_seed_sweep(tmp_path):
    """VERDICT r4 #6: a time-budgeted randomized seed sweep with the
    admin-ops fuzzer churning topics/configs/partitions/leadership
    during faults. Up to 20 short seeds within a 240 s wall budget
    (>=8 must complete even on a loaded box); every one must hold the
    acked-data invariants AND actually run admin ops.
    tools/chaos_soak.py runs the unbounded version."""
    import random as _random
    import time as _time

    base = _random.Random(20260731).randrange(1 << 30)
    deadline = _time.monotonic() + 240.0
    ran = 0
    for i in range(20):
        if _time.monotonic() > deadline:
            break
        seed = base + i * 7919
        stats = asyncio.run(
            run_chaos(
                tmp_path / f"s{i}",
                seed=seed,
                duration_s=1.2,
                faults=("partition", "crash", "transfer"),
                admin_ops=True,
            )
        )
        assert stats["acked"] > 0, (seed, stats)
        assert sum(stats["admin_ops"].values()) > 0, (
            seed,
            "admin fuzzer ran zero ops",
        )
        ran += 1
    assert ran >= 8, f"only {ran} seeds fit the budget"


@pytest.mark.timing
def test_chaos_tiered_object_store_faults(tmp_path):
    """ObjectNemesis e2e: produce -> archive -> evict -> cold-read
    under a mixed object-store fault schedule (partial uploads, torn
    manifests, slow links, throttles, transient errors) layered on
    broker faults. Every acked record must stay readable across the
    remote/local seam, no manifest may reference a missing or
    truncated object, and the fault trace must replay byte-equal from
    (rules, seed, op sequence) — the determinism contract that makes a
    chaos failure a repro, not an anecdote."""
    from dataclasses import replace

    from redpanda_tpu.cloud.nemesis import (
        StoreFaultSchedule,
        StoreRule,
        replay_trace,
    )

    rules = [
        StoreRule(op="put", action="partial", prob=0.15),
        StoreRule(
            op="put", key_glob="*manifest.bin", action="error", prob=0.1
        ),
        StoreRule(
            op="get_range",
            action="slow",
            prob=0.1,
            delay_s=0.0,
            bandwidth_bps=512 * 1024,
        ),
        StoreRule(op="get", action="error", prob=0.1),
        StoreRule(op="*", action="throttle", prob=0.05, delay_s=0.02),
    ]
    sched = StoreFaultSchedule(rules=[replace(r) for r in rules], seed=515)
    stats = asyncio.run(
        run_chaos(
            tmp_path,
            seed=515,
            duration_s=6.0,
            faults=("partition", "crash", "transfer"),
            tiered=True,
            store_faults=sched,
        )
    )
    assert stats["acked"] > 10, stats
    assert stats["tiered_archived"] >= 1, stats  # uploads converged
    assert stats["tiered_trimmed"] >= 1, stats  # the seam was crossed
    assert sum(sched.injected.values()) > 0, "schedule never fired"
    # the determinism contract: a fresh rule set + the recorded op
    # sequence rebuild the firing trace byte-for-byte
    assert replay_trace(rules, 515, sched.ops) == sched.trace
    assert replay_trace(rules, 516, sched.ops) != sched.trace
