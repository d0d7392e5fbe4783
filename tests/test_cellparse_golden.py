"""The cell parse and both codecs that emit from it, held to the blocks
and vectors the tree gave before ISSUE 33 took the byte gathers and the
binary search out of them (tests/cellparse_corpus.py makes the corpus;
tests/corpus/cellparse_golden.json was recorded at commit 07cb742):
the same decisions, so the same bytes, for every caller."""

import json

import pytest

import cellparse_corpus as corpus

with open(corpus.GOLDEN) as f:
    GOLDEN = json.load(f)


def test_the_golden_file_is_of_this_corpus():
    assert sorted(GOLDEN) == sorted(corpus.cases())
    assert {corpus.bucket_of(c.size) for c in corpus.cases().values()} == set(
        corpus.BUCKETS)


@pytest.mark.parametrize("case", sorted(GOLDEN))
def test_parse_and_blocks_are_the_golden_ones(case):
    got = corpus.digests(corpus.bucket_of(corpus.cases()[case].size))[case]
    want = GOLDEN[case]
    assert sorted(got) == sorted(want) == sorted(("lz4", "snappy") + corpus.VECTORS)
    wrong = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    assert not wrong, wrong
