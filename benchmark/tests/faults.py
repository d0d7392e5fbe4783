"""Faults planted under the timed path, for tests/test_faults.py: each
must make a run come out as not correct. `plant(name)` is called in a
fresh interpreter before `benchmark.run.main`; the fault goes in once
set-up has its first ack everywhere, so it is the window that runs on
the broken path.

  tick_frozen      the tick returns its state unchanged: no commit index
                   advances, so no acks=all produce is acknowledged
  replica_left_out one broker is cut off (LoopbackNetwork.isolate): its
                   replicas miss what the window acknowledges
  answer_altered   one byte of every fetched record set is flipped after
                   the broker's own verify-on-read
  recompress_skipped  on a topic that sets a codec the broker stores
                   what it was sent: the batches come back plain
"""

from __future__ import annotations

import numpy as np

FAULTS = ("tick_frozen", "replica_left_out", "answer_altered")
#: faults only a topic with a codec can have (tests/recompress/)
CODEC_FAULTS = ("recompress_skipped",)


def plant(name: str) -> None:
    from benchmark import cluster

    original = cluster.first_ack_everywhere
    made: list = []
    make = cluster.make_brokers

    def make_and_keep(config, data_dir):
        made.extend(make(config, data_dir))
        return list(made)

    async def then_break(bootstrap, config, tpl):
        await original(bootstrap, config, tpl)
        _FAULTS[name](made)

    cluster.make_brokers = make_and_keep
    cluster.first_ack_everywhere = then_break


def _tick_frozen(brokers) -> None:
    from redpanda_tpu.raft.shard_state import ShardGroupArrays

    empty = np.zeros(0, np.int64)
    ShardGroupArrays.device_tick = lambda self, *a, **kw: empty


def _replica_left_out(brokers) -> None:
    brokers[-1]._loopback.isolate(brokers[-1].node_id)


def _answer_altered(brokers) -> None:
    from redpanda_tpu.kafka import server

    cls = next(
        c for c in vars(server).values()
        if isinstance(c, type) and "_verify_fetch_response" in vars(c)
    )
    verify = cls._verify_fetch_response

    def verify_then_flip(self, responses):
        verify(self, responses)
        for t in responses:
            for p in t.partitions:
                if p.records:
                    raw = bytearray(p.records)
                    raw[-1] ^= 0x01
                    p.records = bytes(raw)

    cls._verify_fetch_response = verify_then_flip


def _recompress_skipped(brokers) -> None:
    from redpanda_tpu.models.record import RecordBatch

    RecordBatch.recompressed = lambda self, ctype, verify_crc=None: self


_FAULTS = {
    "tick_frozen": _tick_frozen,
    "replica_left_out": _replica_left_out,
    "answer_altered": _answer_altered,
    "recompress_skipped": _recompress_skipped,
}
