"""The exactly-once pipeline's reference and its cell,
`omb_100_ctp.copy_0p8`, as far as the CPU can hold them.

In this process: what `CopiedTemplate` says came back of a source batch
and of its copy; the range assignor; the pipeline's reference
(ctpreplay.py) on hand-made logs, with a duplicate copy, a lost copy, an
aborted copy handed on and an offset committed past its copy; and the
generator against three in-process brokers at toy size (12 + 12
partitions, RF=3), its logs read back from the leaders and held to the
reference independently of the generator's own read.

In fresh interpreters (about half a minute each): a traced --cpu-dry-run
of the cell is correct and reads the four per-layer metrics of the group
path; each of the three planted faults (ctp_faults.py) comes out as not
correct by the rule it breaks.
tests/test_benchmark_omb_100_ctp.py collects all of it into tier-1 by
import.
"""

import asyncio
import json
import os
import struct
import subprocess
import sys

import pytest

from benchmark import cluster, ctpreplay, run, txreplay
from benchmark.generators import ctp
from benchmark.reference import ATTRIBUTES_AT, BODY_AT, CRC_AT, batch_holds, crc32c, encode_batch
from benchmark.templates.ctp import CopiedTemplate, incompressible
from benchmark.tests import ctp_faults
from benchmark.tests.conftest import ROOT, dry_run

CELL = "omb_100_ctp.copy_0p8"
TRAFFIC = {"templates": {"count": 2}, "batch_records": 3}
CONFIG = {"record_bytes": 64}


@pytest.fixture(scope="module")
def copied():
    return incompressible(2**31 + 11, TRAFFIC, CONFIG)


# ------------------------------------------------------------ the template
@pytest.mark.parametrize("pid, epoch, seq", [(0, 0, 0), (1007, 3, 39 * 41),
                                             (2**40 + 9, 32767, 2**31 - 1)])
def test_both_stamps_carry_the_reference_s_crc(copied, pid, epoch, seq):
    for t in copied:
        for wire, attributes in ((t.stamp(pid, epoch, seq), 0x00),
                                 (t.stamp_copy(pid, epoch, seq), 0x10)):
            assert batch_holds(wire)
            assert struct.unpack_from(">I", wire, CRC_AT)[0] == crc32c(wire[BODY_AT:])
            assert struct.unpack_from(">h", wire, ATTRIBUTES_AT)[0] == attributes
            assert struct.unpack_from(">qhi", wire, 43) == (pid, epoch, seq)
            assert t.key_of(wire) == t.key


def test_a_source_batch_is_not_a_copy_and_a_copy_is_not_a_source_batch(copied):
    t = copied[0]
    source, copy = t.stamp(5, 0, 0), t.stamp_copy(9, 1, 3)
    assert t.came_back(source) and not t.came_back(copy)
    assert t.copy_came_back(copy) and not t.copy_came_back(source)
    # not stamped, or another template's records: neither
    for batch in (t.wire, copied[1].stamp(5, 0, 0), copied[1].stamp_copy(9, 1, 3)):
        assert not t.came_back(batch) and not t.copy_came_back(batch)


def test_a_copied_template_is_made_from_a_plain_batch(copied):
    with pytest.raises(ValueError):
        CopiedTemplate(copied[0].stamp_copy(1, 0, 0), [(b"k", b"v")] * 3)


@pytest.mark.parametrize("members, partitions, want", [
    (["b", "a", "c"], 7, {"a": [0, 1, 2], "b": [3, 4], "c": [5, 6]}),
    (["m"] * 1, 3, {"m": [0, 1, 2]}),
    ([f"m{i:02d}" for i in range(16)], 100, None),
])
def test_range_assign_is_kafka_s_range_assignor(members, partitions, want):
    got = ctp.range_assign(members, partitions)
    if want is not None:
        assert got == want
    assert sorted(p for ps in got.values() for p in ps) == list(range(partitions))
    sizes = [len(got[m]) for m in sorted(got)]
    assert sizes == sorted(sizes, reverse=True) and sizes[0] - sizes[-1] <= 1


# -------------------------------------------------------------- the reference
def _mended(batch: bytearray) -> bytes:
    struct.pack_into(">I", batch, CRC_AT, crc32c(bytes(batch[BODY_AT:])))
    return bytes(batch)


def _batch(base, pid, seq, records, transactional, epoch=0):
    wire = bytearray(encode_batch(records))
    struct.pack_into(">q", wire, 0, base)
    struct.pack_into(">h", wire, ATTRIBUTES_AT, 0x10 if transactional else 0)
    struct.pack_into(">qhi", wire, 43, pid, epoch, seq)
    return _mended(wire)


def _marker(base, pid, kind):
    wire = bytearray(encode_batch([(struct.pack(">hh", 0, kind), b"")]))
    struct.pack_into(">q", wire, 0, base)
    struct.pack_into(">h", wire, ATTRIBUTES_AT, 0x30)
    struct.pack_into(">qhi", wire, 43, pid, 0, -1)
    return _mended(wire)


A, B, C = ([(b"k", v * 8)] * 2 for v in (b"a", b"b", b"c"))
SOURCE = [_batch(39, 7, 0, A, False), _batch(41, 8, 0, B, False),
          _batch(43, 7, 2, C, False)]   # two idempotent producers


def _copies(*items):
    """The sink log from offset 39: (data records | "commit" | "abort"),
    the member's producer 50, its sequences continuous."""
    out, at, seq = [], 39, 0
    for item in items:
        if isinstance(item, str):
            out.append(_marker(at, 50, txreplay.COMMIT if item == "commit" else txreplay.ABORT))
            at += 1
        else:
            out.append(_batch(at, 50, seq, item, True))
            at += 2
            seq += 2
    return out


def test_the_reference_holds_a_clean_pipeline_with_an_abort_and_its_second_copy():
    sink = _copies(A, "commit", B, "abort", B, "commit", C, "commit")
    want = ctpreplay.expected(SOURCE, 39, sink, 45)
    assert want.breaks == []
    assert want.sources == [39, 41, 43] and want.end == 45
    assert want.handed == [39, 45, 48] and want.aborted == [42]
    assert want.copies == [39, 45, 48]
    assert ctpreplay.departures(want, [39, 45, 48]) == []


@pytest.mark.parametrize("case, sink, committed, rule", [
    ("a_duplicate_copy", (A, "commit", A, "commit", B, C, "commit"), 45, "a second copy"),
    ("a_lost_copy", (A, "commit", C, "commit"), 45, "is not of the source batch"),
    ("the_last_copy_lost", (A, "commit", B, "commit"), 45, "has no committed copy"),
    ("an_offset_committed_past_its_copy", (A, "commit", B, "commit"), 45,
     "has no committed copy"),
    ("an_offset_short_of_the_end", (A, "commit", B, "commit", C, "commit"), 43,
     "the group committed 43, the source ends at 45"),
    ("an_offset_past_the_end", (A, "commit", B, "commit", C, "commit"), 47,
     "the group committed 47, the source ends at 45"),
    ("no_offset_at_all", (A, "commit", B, "commit", C, "commit"), None,
     "the group committed None"),
    ("a_transaction_left_open", (A, "commit", B, "commit", C), 45, "no marker closed it"),
], ids=lambda x: x if isinstance(x, str) and "_" in x else "")
def test_the_reference_names_each_departure(case, sink, committed, rule):
    want = ctpreplay.expected(SOURCE, 39, _copies(*sink), committed)
    assert any(rule in b for b in want.breaks), want.breaks
    rules = {b.split(":")[0] for b in want.breaks}
    assert rules <= {"exactly_once", "atomicity", "idempotence"}
    if case == "a_duplicate_copy":   # the copies up to the second one are mapped
        assert want.copies == [39, 42, 45][:len(want.copies)]


def test_the_reference_names_a_copy_from_an_aborted_transaction_handed_on():
    sink = _copies(A, "commit", B, "abort", B, "commit", C, "commit")
    want = ctpreplay.expected(SOURCE, 39, sink, 45)
    got = ctpreplay.departures(want, [39, 42, 45, 48])
    assert got == ["atomicity: the aborted copy at 42 was handed on"]
    # anything else the consumers were handed that the replay does not show
    assert ctpreplay.departures(want, [39, 48])[0].startswith("exactly_once:")


def test_the_reference_names_a_broken_sequence_in_either_log():
    source = [_batch(39, 7, 0, A, False), _batch(41, 7, 5, B, False)]
    want = ctpreplay.expected(source, 39, _copies(A, B, "commit"), 43)
    assert [b.split(" at ")[0] for b in want.breaks] == ["idempotence: source"]


# ----------------------------------- the system against the reference, toy size
async def _toy_run(tmp_path, seed: int) -> dict:
    """The cell's generator against the configuration's brokers at toy
    size, in this process: no device switch is on, the served path is
    the same."""
    loaded = run.load_cell(CELL)
    config = cluster.sized(loaded["config"], True, None)
    traffic = {**cluster.toy_traffic(loaded["traffic"], True), "drain_s": 10}
    tpl = run.resolve(traffic["templates"]["maker"], "templates")(seed, traffic, config)
    brokers = cluster.make_brokers(config, str(tmp_path))
    said: list[str] = []
    try:
        cluster.reserve(brokers, config)
        bootstrap = await cluster.start(brokers, config)
        await cluster.first_ack_everywhere(bootstrap, config, tpl)
        spec = {"bootstrap": bootstrap, "topics": config["topics"], "config": config,
                "traffic": traffic, "seed": seed, "seconds": 2.0, "out": None}
        rec = await ctp.run(spec, said.append)
        logs = {}
        for t in config["topics"]:
            for p in range(t["partitions"]):
                (leader,) = [r for r in cluster.replicas(brokers, t["name"], p) if r.is_leader]
                # framed with the kafka offset, which the CRC does not cover
                logs[t["name"], p] = [struct.pack(">q", k) + b.to_kafka_wire()[8:]
                                      for k, b in leader.read_kafka(0, 1 << 30)]
        group = None
        for b in brokers:
            for shard in b.group_coordinator._groups.values():
                group = shard.get(traffic["group"], group)
        offsets = {p: off for (topic, p), (off, _md, _ts) in group.offsets.items()}
        placed = {name: [sum(b.partition_manager.get(ntp) is not None for b in brokers)
                         for ntp in _ntps(config[name])]
                  for name in ("coordinator_topic", "group_coordinator_topic")}
    finally:
        await cluster.stop(brokers)
    return {"rec": rec, "said": said, "logs": logs, "tpl": tpl, "config": config,
            "offsets": offsets, "pending": dict(group.pending_tx), "placed": placed}


def _ntps(topic: dict) -> list:
    from redpanda_tpu.models.fundamental import NTP

    return [NTP(topic["namespace"], topic["name"], p) for p in range(topic["partitions"])]


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return asyncio.run(_toy_run(tmp_path_factory.mktemp("omb_100_ctp"), 2**31 + 91))


def test_toy_run_every_source_batch_is_a_row_and_its_copy_came_back(toy):
    rec = toy["rec"]
    rows = rec["rows"]
    assert rec["columns"][10:] == ["t_fetch", "fetched_template", "fetched_bytes"]
    assert len(rows) == 40 and rec["clients"]["copies_handed_on"] == 40
    assert rec["clients"]["transactions"] >= 20 and rec["clients"]["aborted"] >= 1
    assert rec["clients"]["rewinds"] == rec["clients"]["aborted"]
    assert rec["clients"]["rejoins"] == 0
    assert rec["fetch_errors"] == [] and rec["unanswered"] == rec["consumers_stuck"] == 0
    assert set(rec["clients"]["broken"].values()) == {0}
    for r in rows:
        assert r[0] == "bench" and r[3] >= 39 and r[6] is None and r[11] == r[2]
        assert r[12] == len(toy["tpl"][0].wire)
        assert r[4] < r[5] < r[10]          # due, acknowledged, its copy in a consumer's hands
    reduced = run.reduce_records(rec, 10.0)
    assert reduced["failed"] == 0 and reduced["acked"] == 40
    assert reduced["metrics"]["e2e_p50_ms"] > reduced["metrics"]["produce_p50_ms"] > 0
    assert toy["said"][0] == "armed" and toy["said"][1].startswith("window_start ")


def test_toy_run_the_served_logs_are_what_the_reference_says(toy):
    """The logs as the leaders hold them and the group as its
    coordinator holds it, read in this process: independent of the
    generator's own read."""
    parts = toy["config"]["topics"][0]["partitions"]
    copies = aborted = 0
    for p in range(parts):
        source = toy["logs"]["bench", p]
        sink = toy["logs"]["bench-out", p]
        # from where the window began: set-up's plain batch lies before
        want = ctpreplay.expected(source[1:], 39, sink[1:], toy["offsets"].get(p))
        assert want.breaks == [], (p, want.breaks)
        assert len(want.copies) == len(source) - 1
        copies += len(want.copies)
        aborted += len(want.aborted)
    assert copies == 40 and aborted >= toy["rec"]["clients"]["aborted"]
    assert toy["pending"] == {}     # no offset left staged after the drain
    # every copy is a template's, stamped for a transaction
    tpl = toy["tpl"]
    by_key = {t.key: t for t in tpl}
    for p in range(parts):
        for batch in toy["logs"]["bench-out", p][1:]:
            if not txreplay.head_of(batch).control:
                assert by_key[tpl[0].key_of(batch)].copy_came_back(batch)


def test_toy_run_both_coordinator_topics_are_as_the_configuration_states(toy):
    from redpanda_tpu.cluster import tx_coordinator
    from redpanda_tpu.kafka.coordinator import group_manager
    from redpanda_tpu.models.fundamental import DEFAULT_NS

    config = toy["config"]
    tx, groups = config["coordinator_topic"], config["group_coordinator_topic"]
    assert (tx["namespace"], tx["name"], tx["partitions"]) == (
        tx_coordinator.TX_NS, tx_coordinator.TX_TOPIC, tx_coordinator.DEFAULT_TX_PARTITIONS)
    assert (groups["namespace"], groups["name"], groups["partitions"]) == (
        DEFAULT_NS, group_manager.OFFSETS_TOPIC, group_manager.DEFAULT_OFFSETS_PARTITIONS)
    assert toy["placed"] == {"coordinator_topic": [3] * 4, "group_coordinator_topic": [3] * 4}


# ------------------------------------------------ fresh interpreters: the cell
def test_traced_dry_run_is_correct_and_reads_the_group_path():
    line = dry_run(CELL, seed=2**31 + 401, trace=1)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    for name in ("tx_add_offsets_ms", "txn_offset_commit_ms", "group_marker_ms"):
        assert metrics[name] > 0, name
    assert metrics["rebalances_in_window"] == 0
    assert "tx_add_partitions_ms" not in metrics      # listed to omb_100_tx alone
    for name in ("unanswered", "never_fetched", "fetched_wrong", "replicas_missing",
                 "not_flushed_at_ack"):
        assert line["checks"][name] == {"value": 0, "limit": 0}
    clients = line["detail"]["clients"]
    assert clients["copies_handed_on"] == line["attempted"] > 0
    assert clients["aborted"] >= 1 and set(clients["broken"].values()) == {0}
    assert line["detail"]["compiles_in_window"] == 0


def _planted(fault: str, seed: int) -> dict:
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "3", "--trace", "0",
            "--cpu-dry-run"]
    cmd = [sys.executable, "-c",
           "import sys; from benchmark.tests import ctp_faults; "
           f"ctp_faults.plant({fault!r}); from benchmark import run; "
           f"sys.exit(run.main({argv!r}))"]
    got = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    return json.loads(got.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", sorted(ctp_faults.FAULTS))
def test_a_planted_fault_is_not_correct_by_the_rule_it_breaks(fault):
    line = _planted(fault, 2**31 + 410 + sorted(ctp_faults.FAULTS).index(fault))
    assert line["correct"] is False, line["checks"]
    rule, check = ctp_faults.FAULTS[fault]
    assert line["checks"][check]["value"] > 0, line["checks"]
    # the source's batches were stored, replicated and flushed all the same
    for name in ("replicas_missing", "not_flushed_at_ack"):
        assert line["checks"][name]["value"] == 0, name
    assert line["checks"]["acked"]["value"] > 0
    broken = line["detail"]["clients"]["broken"]
    assert broken[rule] > 0, broken
    assert any(e.startswith(rule + ":") for e in line["detail"]["fetch_errors"])
