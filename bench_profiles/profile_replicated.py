"""Profile the replicated acks=all hot path (VERDICT r4 item #1).

Boots the same 3-broker / N-partition cluster as bench.py's
`replicated` config, but:
  - cProfile wraps ONLY the measurement window (setup excluded),
  - GC pauses are tracked via gc.callbacks (gen2 pause = p99 suspect),
  - per-produce latency goes into a histogram so the cliff is visible.

Run:  python -u bench_profiles/profile_replicated.py [partitions] [secs]
"""

import asyncio
import cProfile
import gc
import io
import os
import pstats
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


async def main(n_partitions: int, duration_s: float, tag: str) -> None:
    import shutil

    import bench
    from redpanda_tpu.kafka.client import KafkaClient
    from redpanda_tpu.models.record import RecordBatchBuilder

    n_producers = 4
    batch_records = 64
    record_bytes = 1024
    acks = int(os.environ.get("RP_PROF_ACKS", "-1"))
    shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
    tmp = tempfile.mkdtemp(prefix="rp_prof_", dir=shm)
    brokers = []
    client = None
    try:
        t0 = time.monotonic()
        brokers = await bench._cluster(tmp, 3)
        client = KafkaClient([b.kafka_advertised for b in brokers])
        await client.create_topic(
            "repl", partitions=n_partitions, replication_factor=3
        )
        payload = os.urandom(record_bytes - 16)
        builder = RecordBatchBuilder()
        for i in range(batch_records):
            builder.add(payload, key=b"k%012d" % i)
        wire = builder.build().to_kafka_wire()
        deadline = time.monotonic() + 120.0
        pid_probe = 0
        while pid_probe < n_partitions:
            try:
                await client.produce_wire("repl", pid_probe, wire, acks=-1)
                pid_probe += max(1, n_partitions // 16)
            except Exception:
                if time.monotonic() > deadline:
                    raise
                await asyncio.sleep(0.25)
        print(f"setup done in {time.monotonic()-t0:.1f}s", flush=True)

        if os.environ.get("RP_PROF_GCFREEZE", "0") == "1":
            # candidate fix for the gen2 p99 cliff: move the settled
            # broker object graph out of the collector (same trick the
            # live-tick bench applies)
            gc.collect()
            gc.freeze()
            print("gc.freeze applied after setup", flush=True)
        from redpanda_tpu.observability import devplane

        devplane.reset()  # drop setup-phase accumulation (elections etc.)
        # GC pause tracking
        gc_pauses: list[tuple[int, float]] = []
        gc_t0 = [0.0]

        def gc_cb(phase, info):
            if phase == "start":
                gc_t0[0] = time.perf_counter()
            else:
                gc_pauses.append(
                    (info["generation"], (time.perf_counter() - gc_t0[0]) * 1e3)
                )

        gc.callbacks.append(gc_cb)

        lat_ms: list[float] = []
        sent = [0]
        t_end = time.perf_counter() + duration_s

        async def producer(idx: int) -> None:
            c = KafkaClient([b.kafka_advertised for b in brokers])
            pid = idx * (n_partitions // n_producers)
            try:
                while time.perf_counter() < t_end:
                    t0 = time.perf_counter()
                    await c.produce_wire("repl", pid, wire, acks=acks)
                    lat_ms.append((time.perf_counter() - t0) * 1e3)
                    sent[0] += batch_records * record_bytes
                    pid = (pid + 1) % n_partitions
            finally:
                await c.close()

        use_profile = os.environ.get("RP_PROF_CPROFILE", "0") == "1"
        use_sampler = os.environ.get("RP_PROF_SAMPLE", "0") == "1"
        sampler = None
        if use_sampler:
            if os.environ.get("RP_PROF_PHASES", "0") == "1":
                from sampler import PhaseSampler as Sampler
            elif os.environ.get("RP_PROF_STACKS", "0") == "1":
                from sampler import StackSampler as Sampler
            else:
                from sampler import Sampler

            sampler = Sampler()
            sampler.start()
        pr = cProfile.Profile()
        t0 = time.perf_counter()
        if use_profile:
            pr.enable()
        await asyncio.gather(*(producer(i) for i in range(n_producers)))
        if use_profile:
            pr.disable()
        wall = time.perf_counter() - t0
        if sampler is not None:
            sampler.stop()
            print(sampler.report(35), flush=True)
        gc.callbacks.remove(gc_cb)

        mbps = sent[0] / wall / 1e6
        arr = np.array(lat_ms)
        print(
            f"partitions={n_partitions} mbps={mbps:.1f} rounds={len(lat_ms)} "
            f"p50={np.percentile(arr,50):.2f}ms p90={np.percentile(arr,90):.2f}ms "
            f"p99={np.percentile(arr,99):.2f}ms max={arr.max():.2f}ms",
            flush=True,
        )
        hist, edges = np.histogram(
            arr, bins=[0, 2, 5, 10, 20, 50, 100, 200, 400, 10000]
        )
        print("latency histogram (ms buckets):", flush=True)
        for h, lo, hi in zip(hist, edges, edges[1:]):
            print(f"  [{lo:>5.0f},{hi:>5.0f}): {h}", flush=True)
        gen2 = [p for g, p in gc_pauses if g == 2]
        gen_all = [p for _, p in gc_pauses]
        print(
            f"gc: {len(gc_pauses)} collections, "
            f"gen2={len(gen2)} (max {max(gen2) if gen2 else 0:.1f}ms), "
            f"max_any={max(gen_all) if gen_all else 0:.1f}ms "
            f"sum={sum(gen_all):.1f}ms",
            flush=True,
        )
        # t_end was computed before task startup: re-derive effective
        # duration from the latency stream when reporting
        here = os.path.dirname(os.path.abspath(__file__))
        if use_profile:
            for sort, name in (("tottime", "tottime"), ("cumulative", "cum")):
                s = io.StringIO()
                pstats.Stats(pr, stream=s).sort_stats(sort).print_stats(50)
                path = os.path.join(
                    here, f"replicated_{tag}_{n_partitions}p_{name}.txt"
                )
                open(path, "w").write(s.getvalue())
                print("saved", path, flush=True)
        # the window's spans (RP_DEVPLANE=1 arms the digest)
        for name, h in devplane.status().get("host", {}).items():
            print(f"span {name:<24} {h}", flush=True)
    finally:
        if client is not None:
            try:
                await client.close()
            except Exception:
                pass
        for b in brokers:
            try:
                await b.stop()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    parts = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    secs = float(sys.argv[2]) if len(sys.argv) > 2 else 4.0
    tag = sys.argv[3] if len(sys.argv) > 3 else "before"
    asyncio.run(main(parts, secs, tag))
