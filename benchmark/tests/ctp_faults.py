"""Faults planted under the pipeline's path, in tx_faults.py's manner:
each breaks the `exactly_once` guarantee that `omb_100_ctp` states and
must make a run of its cell come out as not correct. `plant(name)` is
called in a fresh interpreter before `benchmark.run.main`; the fault goes
in once set-up has its first ack everywhere, so it is the window that
runs on the broken path.

  second_copy            the partition takes any sequence and tells the
                         member of every copy's first attempt, once
                         stored, to send again: the copy is committed
                         twice                 (exactly_once, fetched_wrong)
  abort_delivered        the broker's list of aborted transactions is
                         left empty: a sink consumer hands on a copy
                         from an aborted transaction (atomicity,
                                                            fetched_wrong)
  offset_survives_abort  the group coordinator applies a transaction's
                         staged offsets at its abort marker as at a
                         commit: the member resumes past batches whose
                         copies were aborted, and never copies them
                         again                (exactly_once, never_fetched)
"""

from __future__ import annotations

from benchmark.tests import tx_faults

FAULTS = {
    "second_copy": ("exactly_once", "fetched_wrong"),
    "abort_delivered": ("atomicity", "fetched_wrong"),
    "offset_survives_abort": ("exactly_once", "never_fetched"),
}


def plant(name: str) -> None:
    from benchmark import cluster

    original = cluster.first_ack_everywhere

    async def then_break(bootstrap, config, tpl):
        await original(bootstrap, config, tpl)
        _FAULTS[name]()

    cluster.first_ack_everywhere = then_break


def _offset_survives_abort() -> None:
    from redpanda_tpu.kafka.coordinator import group_manager

    apply = group_manager._apply_tx_marker
    group_manager._apply_tx_marker = lambda g, pid, epoch, commit: apply(g, pid, epoch, True)


_FAULTS = {
    "second_copy": tx_faults._FAULTS["sequence_unchecked"],
    "abort_delivered": tx_faults._FAULTS["abort_delivered"],
    "offset_survives_abort": _offset_survives_abort,
}
