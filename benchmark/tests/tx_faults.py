"""Faults planted under the transactional path, in faults.py's manner:
each breaks one guarantee that `omb_100_tx` states and must make a run
of its cell come out as not correct, by the rule named. `plant(name)`
is called in a fresh interpreter before `benchmark.run.main`; the fault
goes in once set-up has its first ack everywhere, so it is the window
that runs on the broken path.

  abort_delivered     the broker's list of aborted transactions is left
                      empty: the consumer's filter has no word to drop an
                      aborted batch on, and hands it on     (atomicity)
  lso_ignored         a read_committed fetch is served to the high
                      watermark: consumers hold batches of transactions
                      that no marker has closed yet          (isolation)
  sequence_unchecked  the partition takes any sequence and tells the
                      producer of every first attempt, once stored, to
                      send again: the resent batch is stored twice
                                                           (idempotence)
"""

from __future__ import annotations

FAULTS = {
    "abort_delivered": "atomicity",
    "lso_ignored": "isolation",
    "sequence_unchecked": "idempotence",
}


def plant(name: str) -> None:
    from benchmark import cluster

    original = cluster.first_ack_everywhere

    async def then_break(bootstrap, config, tpl):
        await original(bootstrap, config, tpl)
        _FAULTS[name]()

    cluster.first_ack_everywhere = then_break


def _abort_delivered() -> None:
    from redpanda_tpu.cluster.partition import Partition

    Partition.aborted_in = lambda self, start, end: []


def _lso_ignored() -> None:
    from redpanda_tpu.cluster.partition import Partition

    Partition.last_stable_offset = Partition.high_watermark


def _sequence_unchecked() -> None:
    from redpanda_tpu.cluster.partition import Partition
    from redpanda_tpu.cluster.producer_state import ProducerStateTable
    from redpanda_tpu.raft.consensus import NotLeaderError

    ProducerStateTable.check = lambda self, *a, **kw: None
    replicate = Partition.replicate_in_stages
    stored: set = set()

    async def store_then_ask_again(self, batch, acks=-1):
        stages = await replicate(self, batch, acks)
        h = batch.header
        key = (self.ntp, h.producer_id, h.base_sequence)
        if h.is_transactional and not h.is_control and key not in stored:
            stored.add(key)
            await stages.done
            raise NotLeaderError(None)
        return stages

    Partition.replicate_in_stages = store_then_ask_again


_FAULTS = {
    "abort_delivered": _abort_delivered,
    "lso_ignored": _lso_ignored,
    "sequence_unchecked": _sequence_unchecked,
}
