"""A cell run with loop time added, to show what a cell sees: for the
builder, never the driver, and never a cell.

`python3 benchmark/tools/loop_added.py --busy-ms 1 -- --workload <cell>
--seed <n> --seconds 40 --trace 0 [...]` runs `benchmark.run` with a
busy-wait of `--busy-ms` milliseconds a batch on the brokers' event loop,
inside the `produce.dispatch` span (before `Partition.replicate_in_stages`,
once a batch of a produce request). A cell judged on what the loop
completes (`sustained_mb_s`) has to fall by about `busy-ms` over its
`loop_run_ms_per_batch`; a cell under its knee answers `busy-ms` later
(PERF.md section 6, PR 34). The result line carries `"loop_added_ms"`."""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402


def add_busy_wait(ms: float) -> None:
    """Patch the program once `run.main` has set its switches and
    imported it: `run_cell` is the first thing to run after that."""
    run_cell = run.run_cell

    async def patched(args, loaded, device):
        from redpanda_tpu.cluster.partition import Partition

        replicate = Partition.replicate_in_stages

        async def busy_then_replicate(self, batch, acks=-1):
            until = time.perf_counter() + ms / 1e3
            while time.perf_counter() < until:
                pass
            return await replicate(self, batch, acks=acks)

        Partition.replicate_in_stages = busy_then_replicate
        result = await run_cell(args, loaded, device)
        result["loop_added_ms"] = ms
        result["checks"] = result.pop("checks")  # stays last in the line
        return result

    run.run_cell = patched


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--busy-ms", type=float, required=True)
    ap.add_argument("rest", nargs=argparse.REMAINDER,
                    help="after `--`: the arguments of benchmark.run")
    args = ap.parse_args()
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    add_busy_wait(args.busy_ms)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
