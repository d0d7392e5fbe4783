"""What an fsync costs where the brokers' data directories sit:
`python3 benchmark/tools/fsync_probe.py [--bytes N] [--count N]` appends
one batch's worth of bytes and fsyncs, over and over, in
`<checkout>/.bench_data/`, and prints the median and the 95th percentile
in milliseconds with the filesystem's type. Both cells' produce tails
rest on this cost (fsync before ack); it is a fact about the machine,
not a result of the benchmark. Imports nothing of JAX."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.run import ROOT, fs_type, percentile  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bytes", type=int, default=66_610)
    ap.add_argument("--count", type=int, default=300)
    args = ap.parse_args()
    d = os.path.join(ROOT, ".bench_data")
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "fsync_probe.bin")
    block = os.urandom(args.bytes)
    write_ms, sync_ms = [], []
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    try:
        for _ in range(args.count):
            t0 = time.perf_counter()
            os.write(fd, block)
            t1 = time.perf_counter()
            os.fsync(fd)
            t2 = time.perf_counter()
            write_ms.append((t1 - t0) * 1e3)
            sync_ms.append((t2 - t1) * 1e3)
    finally:
        os.close(fd)
        os.remove(path)
    print(json.dumps({
        "fstype": fs_type(d), "bytes": args.bytes, "count": args.count,
        "write_p50_ms": percentile(write_ms, 0.5),
        "fsync_p50_ms": percentile(sync_ms, 0.5),
        "fsync_p95_ms": percentile(sync_ms, 0.95),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
