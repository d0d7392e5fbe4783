"""Wire-format compatibility corpus (reference: src/v/compat/run.cc).

Locks every serde Envelope's on-wire encoding against the checked-in
corpus. A failure here means a ROLLING-UPGRADE BREAK: an already-
shipped peer (or an already-written controller log / kvstore entry)
encodes these exact bytes. Regenerate the corpus only for deliberate,
version-gated format changes:

    python -m redpanda_tpu.utils.compat tests/corpus/serde_corpus.json
"""

import json
import os
import random

import pytest

from redpanda_tpu.utils import serde
from redpanda_tpu.utils.compat import (
    all_envelope_types,
    corpus_cases,
    discovery_failures,
    gen_instance,
    render,
)

CORPUS_PATH = os.path.join(os.path.dirname(__file__), "corpus", "serde_corpus.json")
# the cases of types whose encoding has grown since they were locked,
# as they stood before: bytes that shipped peers and written logs still
# hold. The generator never writes this file; add a type's outgoing
# cases here before regenerating the corpus over them.
LEGACY_PATH = os.path.join(
    os.path.dirname(__file__), "corpus", "serde_corpus_legacy.json"
)


def load_corpus(path=CORPUS_PATH):
    with open(path) as f:
        return json.load(f)


def _holds(old, new) -> bool:
    """Every value an older build rendered is what this build decodes;
    fields appended since are this build's to default."""
    if isinstance(old, dict):
        grown = "__type__" in old  # an envelope; bytes and maps are exact
        return (
            isinstance(new, dict)
            and (grown or old.keys() == new.keys())
            and all(k in new and _holds(v, new[k]) for k, v in old.items())
        )
    if isinstance(old, list):
        return (
            isinstance(new, list)
            and len(old) == len(new)
            and all(_holds(a, b) for a, b in zip(old, new))
        )
    return old == new


def test_every_wire_type_has_corpus_coverage():
    corpus = load_corpus()
    types = all_envelope_types()
    # a module that fails to import silently shrinks the key space —
    # its wire types would never be locked
    assert not discovery_failures, discovery_failures
    missing = sorted(set(types) - set(corpus))
    assert not missing, (
        f"wire types without corpus entries (regenerate the corpus): {missing}"
    )


def test_corpus_types_still_exist():
    corpus = load_corpus()
    types = all_envelope_types()
    gone = sorted(set(corpus) - set(types))
    assert not gone, (
        f"corpus types vanished — renaming/deleting a wire type breaks "
        f"peers that still send it: {gone}"
    )


def test_corpus_versions_unchanged():
    corpus = load_corpus()
    types = all_envelope_types()
    for q, entry in corpus.items():
        cls = types[q]
        assert (cls.SERDE_VERSION, cls.SERDE_COMPAT_VERSION) == (
            entry["version"],
            entry["compat"],
        ), f"{q}: serde version changed without corpus regeneration"


def test_corpus_bytes_decode_and_reencode_identically():
    corpus = load_corpus()
    types = all_envelope_types()
    for q, entry in corpus.items():
        cls = types[q]
        assert len(entry["cases"]) == len(entry["values"]) == 3, q
        for case_hex, want_values in zip(
            entry["cases"], entry["values"], strict=True
        ):
            blob = bytes.fromhex(case_hex)
            obj = cls.decode(blob)
            assert obj.encode() == blob, (
                f"{q}: re-encode differs from corpus — wire format changed"
            )
            # semantic lock: a pure field reorder of same-width types
            # re-encodes byte-identically, so values must match too
            assert render(obj) == want_values, (
                f"{q}: decoded values differ from corpus — field "
                f"meaning/order changed"
            )
    # what SERDE_COMPAT_VERSION promises: bytes written before a type
    # grew still decode, to the values they were written with
    for q, entry in load_corpus(LEGACY_PATH).items():
        cls = types[q]
        assert entry["compat"] == cls.SERDE_COMPAT_VERSION, q
        for case_hex, old_values in zip(
            entry["cases"], entry["values"], strict=True
        ):
            obj = cls.decode(bytes.fromhex(case_hex))
            assert _holds(old_values, render(obj)), (
                f"{q}: bytes of version {entry['version']} no longer "
                f"decode to what they were written with"
            )


def test_generator_is_deterministic():
    """The corpus can always be reproduced bit-for-bit from source —
    a regeneration diff shows EXACTLY which types changed."""
    corpus = load_corpus()
    types = all_envelope_types()
    for q in list(corpus)[::7]:  # sample
        cases, values = corpus_cases(q, types[q])
        assert cases == corpus[q]["cases"], q
        assert values == corpus[q]["values"], q


def test_forward_compat_skip_extra_fields():
    """A NEWER peer appending fields inside the envelope body must be
    readable by this build (payload-size-bounded skip)."""
    rng = random.Random(99)
    types = all_envelope_types()
    for q in sorted(types)[::5]:  # sample across the space
        cls = types[q]
        obj = gen_instance(cls, rng)
        blob = bytearray(obj.encode())
        extra = b"\xde\xad\xbe\xef"
        # splice extra bytes into the body and bump the declared size
        size = int.from_bytes(blob[2:6], "little")
        blob[2:6] = (size + len(extra)).to_bytes(4, "little")
        blob += extra
        obj2 = cls.decode(bytes(blob))
        assert obj2 == obj, q


def test_compat_reject_future_compat_version():
    from redpanda_tpu.cluster.commands import DeleteTopicCmd

    blob = bytearray(DeleteTopicCmd(ns="kafka", topic="t").encode())
    blob[1] = 200  # compat_version far beyond this build
    with pytest.raises(serde.SerdeError):
        DeleteTopicCmd.decode(bytes(blob))
