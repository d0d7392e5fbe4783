"""The event loop's readers (benchmark/readers/looptime.py) under
tier-1: the cases of benchmark/tests/test_looptime.py, collected here by
import, as tests/test_benchmark_reference.py collects the reference's."""

import pytest

from benchmark import run
from benchmark.tests import test_looptime

for _name, _case in vars(test_looptime).items():
    if _name.startswith("test_") and callable(_case):
        assert _name not in globals(), _name
        globals()[_name] = _case


# benchmark/tests/test_looptime.py pins the five as the last entries of
# `per_layer`, and the pipeline's four metrics come after them now: under
# tier-1 this case, of the same name, takes the collected one's place and
# holds the five to coming after the transactional cell's entries, in
# order and together


@pytest.mark.parametrize("name", test_looptime.NEW_METRICS)
def test_the_five_are_appended_and_every_cell_reads_them(name):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(next(iter(test_looptime.NEW_METRICS)))
    assert names[at:at + 5] == list(test_looptime.NEW_METRICS)
    assert at > names.index("leader_appends_per_acked_batch")
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert "workloads" not in entry
    spec = run.load_json(run.HERE, "metrics", name + ".json")
    assert spec["name"] == name and spec["reader"] == f"looptime.{name}"
    for cell in manifest["workloads"]:
        assert name in {m["name"] for m in run.load_cell(cell["name"])["per_layer"]}
