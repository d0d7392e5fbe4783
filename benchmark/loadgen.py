"""The load generator's process: `python -m benchmark.loadgen <spec.json>`.

Started by run.py in a fresh interpreter with JAX_PLATFORMS=cpu in its
environment, so it can never take the chip. It is started first, so that
its imports run beside the brokers' set-up; then it waits for the one
line run.py writes to its standard input when set-up is done: the spec
(bootstrap addresses, configuration, traffic, seed, seconds, output
file). It loads the generator the traffic file names from
benchmark/generators/, drives the window and writes every request's
record to the file the spec names. Lines on its standard output
(`armed`, `window_start <t>`, `window_end <t>`) tell the harness where
the window lies on the machine's monotonic clock, which both processes
read; `acked [topic, partition, end]` tells it of an ack as it arrives,
so that it can read at once what the replicas have flushed.
"""

from __future__ import annotations

import asyncio
import json
import sys

from benchmark.run import resolve


def _say(line: str) -> None:
    print(line, flush=True)


def main() -> int:
    import redpanda_tpu.kafka.client  # noqa: F401  (the slow import, early)

    line = sys.stdin.readline()
    if not line.strip():
        return 1  # the harness went away before set-up was done
    spec = json.loads(line)
    gen = resolve(spec["traffic"]["generator"], "generators")
    result = asyncio.run(gen(spec, _say))
    with open(spec["out"], "w") as f:
        json.dump(result, f)
    _say("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
