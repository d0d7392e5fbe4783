"""The plain reference's own cases under tier-1: the in-process cases of
benchmark/tests/test_reference.py (CRC-32C, the templates, the
reduction, the LZ4 decoder and xxHash32) and test_came_back.py (what a
template says came back), collected here by import. PR 31's contract
kept them out of tests/; nothing is copied, and the cases that start an
interpreter of their own stay with the rehearsals."""

from benchmark.tests import test_came_back, test_reference

#: starts a fresh interpreter for each of the program's encoders
OWN_PROCESS = {"test_what_the_program_s_encoders_store_comes_back"}

for _module in (test_reference, test_came_back):
    for _name, _case in vars(_module).items():
        if _name.startswith("test_") and callable(_case) and _name not in OWN_PROCESS:
            assert _name not in globals(), _name
            globals()[_name] = _case
