"""What decides `correct`: what the timed window itself acknowledged,
held to the plain reference once the window has closed.

Every number compared has a limit; `after_window` returns them all, and
the run is correct when each holds:

  platform            the kernels' results live on a TPU              = 1
  acked               batches acknowledged in the window              >= 1
  unanswered          requests that never got an answer, a minute
                      past the close                                  = 0
  never_fetched       acknowledged batches no fetch returned at the
                      offset the ack gave                             = 0
  fetched_wrong       acknowledged batches of which a fetch returned
                      something their template says did not come back
                      (a pass-through topic: other bytes than the
                      reference's, crc field onward; a topic with a
                      codec: a CRC that does not hold, another codec or
                      header, or records that the reference's decoder
                      does not read back byte for byte)               = 0
  replicas_missing    of a seeded sample of the acknowledged batches
                      (each partition's last among them), copies that
                      the configuration's replication factor promises
                      and a broker's log does not hold: not there, not
                      what the template says came back, or not the
                      bytes of the other copies, crc field onward     = 0
  not_flushed_at_ack  of the acks sampled as they arrived (one every
                      `ack_sample_s`), those that fewer than a majority
                      of the replicas had flushed to their logs when
                      the harness read them, without waiting          = 0
  acks_sampled        acks so read                                    >= 1
  dispatched.<kernel> for every kernel the configuration's
                      `device_kernels` names: dispatches of it that
                      the program's device counters sampled in the
                      window (each, under `--trace 1`; one in 16
                      otherwise)                                      >= 1

The load generator asks its template of each fetched batch as it
arrives (generators/open_loop.py: `fetched_template`), in its own
process; the replicas are read here, in-process, through each broker's
partition, as chip_smoke.py's `_check_replicas` does, and held to the
same template. Neither compares a stored batch with bytes of its own:
what has to come back is the template's to say (reference.py).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark import cluster
from benchmark.reference import CRC_AT

# columns of a record row (generators/open_loop.py)
TPL, BASE, ERR, GOT = 2, 3, 6, 11
SAMPLE = 256          # acknowledged batches whose replicas are read
CATCH_UP_S = 30.0     # for all the sample: followers learn the commit index by heartbeat


def check(name: str, value, limit, ok: bool) -> dict:
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


class FlushWitness:
    """Holds the program to `fsync before ack` and `a majority hold it
    before the ack`: the load generator tells the harness of an ack as
    it arrives, and the harness reads at once, waiting for nothing, how
    far each replica's log has been flushed (storage's own flushed
    offset, not what raft was told)."""

    def __init__(self, brokers: list, config: dict) -> None:
        self.brokers = brokers
        self.rf = {t["name"]: t["replication_factor"] for t in config["topics"]}
        self.sampled = self.not_flushed = 0
        self.first: list = []

    def on_ack(self, topic: str, partition: int, end: int) -> None:
        flushed = []
        for part in cluster.replicas(self.brokers, topic, partition):
            raft = part.log.offsets().committed_offset
            flushed.append(part.translator.to_kafka(raft) + 1 if raft >= 0 else 0)
        self.sampled += 1
        if sum(1 for f in flushed if f >= end) < self.rf[topic] // 2 + 1:
            self.not_flushed += 1
            if len(self.first) < 5:
                self.first.append([topic, partition, end, flushed])


def sample_rows(acked: list, seed: int, n: int) -> list:
    """`n` of the acknowledged rows drawn from the seed, each
    partition's last acknowledged batch first among them."""
    last: dict = {}
    for r in acked:
        key = (r[0], r[1])
        if key not in last or r[BASE] > last[key][BASE]:
            last[key] = r
    rng = np.random.default_rng(seed)
    lasts = list(last.values())
    picked = [lasts[i] for i in rng.permutation(len(lasts))[: n // 2]]
    seen = {id(r) for r in picked}
    rest = [r for r in acked if id(r) not in seen]
    more = rng.permutation(len(rest))[: n - len(picked)]
    return picked + [rest[i] for i in more]


async def replicas_missing(brokers: list, config: dict, rows: list, tpl: list) -> int:
    """Copies of the sampled batches that are not held. The copies of
    one batch also have to be each other's bytes from the crc field on:
    where the reference pins the bytes that follows, and where the
    broker rewrites the batch it says that the followers hold what the
    leader stored and no compression of their own."""
    rf = {t["name"]: t["replication_factor"] for t in config["topics"]}
    missing = 0
    wait_until = time.monotonic() + CATCH_UP_S
    for topic, p, ti, base, *_rest in rows:
        parts = cluster.replicas(brokers, topic, p)
        missing += max(0, rf[topic] - len(parts))
        end = base + tpl[ti].records
        first = None
        for part in parts[: rf[topic]]:
            while part.high_watermark() < end and time.monotonic() < wait_until:
                await asyncio.sleep(0.05)
            held = False
            if part.high_watermark() >= end:
                got = part.read_kafka(base, 1, upto_kafka=end)
                if got and got[0][0] == base:
                    wire = got[0][1].to_kafka_wire()
                    held = tpl[ti].came_back(wire)
                    if held and first is None:
                        first = wire[CRC_AT:]
                    held = held and wire[CRC_AT:] == first
            missing += not held
    return missing


async def after_window(
    brokers: list, config: dict, rec: dict, tpl: list, window: dict,
    device: dict, seed: int, dry_run: bool,
) -> list[dict]:
    plane, flush = window["devplane"], window["flush"]
    acked = [r for r in rec["rows"] if r[BASE] >= 0 and r[ERR] is None]
    never = sum(1 for r in acked if r[GOT] == -2)
    wrong = sum(1 for r in acked if r[GOT] != -2 and r[GOT] != r[TPL])
    missing = await replicas_missing(
        brokers, config, sample_rows(acked, seed, SAMPLE), tpl
    )
    kernels = plane.get("kernels", {})
    want = "cpu" if dry_run else "tpu"
    lives_on = (plane.get("device") or {}).get("platform")
    on_platform = int(device["platform"] == want and lives_on == want)
    unanswered = rec["unanswered"] + rec["consumers_stuck"]
    dispatched = [
        (k, int(kernels.get(k, {}).get("count", 0))) for k in config["device_kernels"]
    ]
    return [
        check("platform", on_platform, 1, on_platform == 1),
        check("acked", len(acked), ">=1", len(acked) >= 1),
        check("unanswered", unanswered, 0, unanswered == 0),
        check("never_fetched", never, 0, never == 0),
        check("fetched_wrong", wrong, 0, wrong == 0),
        check("replicas_missing", missing, 0, missing == 0),
        check("not_flushed_at_ack", flush.not_flushed, 0, flush.not_flushed == 0),
        check("acks_sampled", flush.sampled, ">=1", flush.sampled >= 1),
        *(check(f"dispatched.{k}", n, ">=1", n >= 1) for k, n in dispatched),
    ]
