"""The event loop's readers (benchmark/readers/looptime.py) under
tier-1: the cases of benchmark/tests/test_looptime.py, collected here by
import, as tests/test_benchmark_reference.py collects the reference's."""

from benchmark.tests import test_looptime

for _name, _case in vars(test_looptime).items():
    if _name.startswith("test_") and callable(_case):
        assert _name not in globals(), _name
        globals()[_name] = _case
