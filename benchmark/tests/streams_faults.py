"""Faults planted under the re-keying pipeline's path, in ctp_faults.py's
manner: each breaks a guarantee that `omb_100_streams` states and must
make a run of its cell come out as not correct, by the rule named and
the check named. `plant(name)` is called in a fresh interpreter before
`benchmark.run.main`. A fault of the brokers goes in once set-up has its
first ack everywhere, so it is the window that runs on the broken path;
a fault of the application goes into the load generator's own process
(run.LoadGenerator spawns it with the fault planted first), where the
members re-key.

  lost_record      the application drops one record of each source
                   batch it re-keys, from its largest sink partition's
                   share                              (exactly_once,
                                                      never_fetched)
  second_copy      the partition takes any sequence and tells the member
                   of every batch's first attempt, once stored, to send
                   again: each is committed twice     (exactly_once,
                                                      fetched_wrong)
  wrong_partition  the application writes one record of each source
                   batch to the sink partition after the one its key
                   names                              (partitioning,
                                                      fetched_wrong)
  reorder          the application reverses the records of each source
                   batch bound for its first sink partition that takes
                   two or more                        (order,
                                                      fetched_wrong)
  abort_delivered  the broker's list of aborted transactions is left
                   empty: a sink consumer hands on a batch of an aborted
                   transaction                        (atomicity,
                                                      fetched_wrong)
"""

from __future__ import annotations

import asyncio

from benchmark.tests import tx_faults

FAULTS = {
    "lost_record": ("exactly_once", "never_fetched"),
    "second_copy": ("exactly_once", "fetched_wrong"),
    "wrong_partition": ("partitioning", "fetched_wrong"),
    "reorder": ("order", "fetched_wrong"),
    "abort_delivered": ("atomicity", "fetched_wrong"),
}
IN_THE_BROKERS = {
    "second_copy": tx_faults._FAULTS["sequence_unchecked"],
    "abort_delivered": tx_faults._FAULTS["abort_delivered"],
}


def plant(name: str) -> None:
    from benchmark import cluster, run

    if name in IN_THE_BROKERS:
        original = cluster.first_ack_everywhere

        async def then_break(bootstrap, config, tpl):
            await original(bootstrap, config, tpl)
            IN_THE_BROKERS[name]()

        cluster.first_ack_everywhere = then_break
        return
    spawn = run.LoadGenerator.spawn

    async def planted_first(self) -> None:
        start = asyncio.create_subprocess_exec

        async def in_its_place(*argv, **kw):
            assert argv[1:] == ("-m", "benchmark.loadgen"), argv
            return await start(
                argv[0], "-c",
                "import sys; from benchmark.tests import streams_faults; "
                f"streams_faults.in_the_application({name!r}); "
                "from benchmark import loadgen; sys.exit(loadgen.main())", **kw)

        asyncio.create_subprocess_exec = in_its_place
        try:
            await spawn(self)
        finally:
            asyncio.create_subprocess_exec = start

    run.LoadGenerator.spawn = planted_first


def in_the_application(name: str) -> None:
    """Make the generator's re-key break as `name` says, in this process."""
    from benchmark.generators import streams

    rekey = streams.rekey

    def broken(batch: bytes, partitions: int) -> dict:
        out = rekey(batch, partitions)
        if name == "lost_record":
            q = max(out, key=lambda k: (len(out[k]), k))
            out[q] = out[q][:-1]
            if not out[q]:
                del out[q]
        elif name == "wrong_partition":
            q = min(out)
            moved = out[q].pop(0)
            if not out[q]:
                del out[q]
            out.setdefault((q + 1) % partitions, []).insert(0, moved)
        elif name == "reorder":
            q = min((k for k, v in out.items() if len(v) > 1), default=None)
            if q is not None:
                out[q] = out[q][::-1]
        return out

    streams.rekey = broken
