"""Rehearsal tests of the benchmark, run by hand on the CPU:
`python -m pytest benchmark/tests`. Not part of the repo's tier-1 tests.
Nothing here yields a measurement: every run is a --cpu-dry-run at toy
sizes."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def dry_run(workload: str, *extra: str, seed: int = 2147483999,
            trace: int = 0, plant: str | None = None) -> dict:
    """One --cpu-dry-run of a cell in a fresh interpreter; returns its
    last line."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "3",
            "--trace", str(trace), "--cpu-dry-run", *extra]
    if plant is None:
        cmd = [sys.executable, "-m", "benchmark.run", *argv]
    else:
        cmd = [sys.executable, "-c",
               "import sys; from benchmark.tests import faults; "
               f"faults.plant({plant!r}); from benchmark import run; "
               f"sys.exit(run.main({argv!r}))"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    got = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    return json.loads(got.stdout.strip().splitlines()[-1])
