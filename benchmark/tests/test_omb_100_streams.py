"""The re-keying pipeline's reference and its cell,
`omb_100_streams.rekey_0p8`, as far as the CPU can hold them.

In this process: what `KeyedTemplate` stamps and says came back of a
source batch; the reference's partitioner and record decoder against the
program's; the reference (rekeyreplay.py) on hand-made logs, clean and
with each departure it names; the generator's re-key; and the generator
against three in-process brokers at toy size (12 + 12 partitions,
RF=3), its logs read back from the leaders and held to the reference
independently of the generator's own read.

In fresh interpreters (about a minute each): a traced --cpu-dry-run of
the cell is correct and reads the two per-layer metrics of the marker
fan-out; each of the five planted faults (streams_faults.py) comes out
as not correct by the rule it breaks.
tests/test_benchmark_omb_100_streams.py collects all of it into tier-1
by import.
"""

import asyncio
import json
import os
import struct
import subprocess
import sys

import pytest

from benchmark import cluster, rekeyreplay, run, txreplay
from benchmark.generators import streams
from benchmark.reference import (
    ATTRIBUTES_AT, BODY_AT, CRC_AT, RECORDS_AT, batch_holds, crc32c, encode_batch,
)
from benchmark.templates.streams import EVENT_ID, KEY_FIELD, KeyedTemplate, keyed
from benchmark.tests import streams_faults
from benchmark.tests.conftest import ROOT, dry_run

CELL = "omb_100_streams.rekey_0p8"
TRAFFIC = {"templates": {"count": 3}, "batch_records": 5}
CONFIG = {"record_bytes": 64}


@pytest.fixture(scope="module")
def pool():
    return keyed(2**31 + 17, TRAFFIC, CONFIG)


# ------------------------------------------------------------ the template
@pytest.mark.parametrize("pid, epoch, seq, p", [(0, 0, 0, 0), (1007, 3, 39 * 41, 99),
                                                (2**40 + 9, 32767, 2**31 - 6, 7)])
def test_a_stamp_carries_the_reference_s_crc_and_every_event_id(pool, pid, epoch, seq, p):
    for t in pool:
        wire = t.stamp_event(pid, epoch, seq, p)
        assert batch_holds(wire)
        assert struct.unpack_from(">I", wire, CRC_AT)[0] == crc32c(wire[BODY_AT:])
        assert struct.unpack_from(">h", wire, ATTRIBUTES_AT)[0] == 0
        assert struct.unpack_from(">qhi", wire, 43) == (pid, epoch, seq)
        values = [v for _o, _k, v in rekeyreplay.records_of(wire)]
        assert [struct.unpack(">iqi", v[EVENT_ID]) for v in values] == [
            (p, pid, seq + i) for i in range(5)]
        assert t.key_of(wire) == t.key and t.came_back(wire)


def test_a_source_batch_came_back_only_as_its_producer_stamped_it(pool):
    t = pool[0]
    wire = t.stamp_event(5, 0, 10, 3)
    assert t.came_back(wire) and not pool[1].came_back(wire)
    assert not t.came_back(t.wire)                     # not stamped
    assert not t.came_back(t.stamp_copy(5, 0, 10))     # a transactional copy
    for at, what in ((t.ids_at[2] + 4, "another producer's id"),
                     (t.ids_at[4] + 15, "another sequence"),
                     (t.ids_at[1] + 16, "another byte of a value")):
        bad = bytearray(wire)
        bad[at] ^= 1
        struct.pack_into(">I", bad, CRC_AT, crc32c(bytes(bad[BODY_AT:])))
        assert not t.came_back(bytes(bad)), what
    # the event id's partition is the reference's to hold, not the template's
    bad = bytearray(wire)
    bad[t.ids_at[0]] ^= 1
    assert not t.came_back(bytes(bad))                 # the CRC no longer holds
    struct.pack_into(">I", bad, CRC_AT, crc32c(bytes(bad[BODY_AT:])))
    assert t.came_back(bytes(bad))


def test_the_key_fields_are_the_pool_s_own_and_the_template_starts_unstamped(pool):
    fields = [v[KEY_FIELD] for t in pool for _o, _k, v in rekeyreplay.records_of(t.wire)]
    assert len(fields) == len(set(fields)) == 15 and all(len(f) == 16 for f in fields)
    assert keyed(2**31 + 17, TRAFFIC, CONFIG)[0].wire == pool[0].wire   # from the seed
    assert keyed(2**31 + 18, TRAFFIC, CONFIG)[0].wire != pool[0].wire
    with pytest.raises(ValueError):
        KeyedTemplate(pool[0].stamp_event(1, 0, 0, 0), [(b"k", b"v")] * 5)


# ----------------------------------------------------- the reference's parts
def test_the_reference_partitions_as_the_program_s_kafka_partitioner():
    from redpanda_tpu.utils.hash import kafka_partition_for_key, murmur2

    rng = __import__("numpy").random.default_rng(7)
    for n in range(0, 41):
        for _ in range(25):
            key = rng.integers(0, 256, n, dtype="uint8").tobytes()
            assert rekeyreplay.murmur2(key) == murmur2(key)
            assert rekeyreplay.partition_for(key, 100) == kafka_partition_for_key(key, 100)


def test_the_reference_reads_records_as_the_program_s_decoder(pool):
    from redpanda_tpu.kafka.client import decode_record_set

    wire = pool[2].stamp_event(9, 1, 0, 4)
    assert rekeyreplay.records_of(wire) == decode_record_set(wire)
    odd = encode_batch([(b"", b"v"), (b"key", b""), (b"k" * 200, b"v" * 300)])
    assert rekeyreplay.records_of(odd) == decode_record_set(odd)


def test_the_generator_re_keys_each_record_by_its_key_field(pool):
    wire = pool[1].stamp_event(3, 0, 0, 2)
    out = streams.rekey(wire, 7)
    records = rekeyreplay.records_of(wire)
    assert sum(len(v) for v in out.values()) == len(records)
    at = 0
    for q in sorted(out):
        for key, value in out[q]:
            assert key == value[KEY_FIELD] and rekeyreplay.partition_for(key, 7) == q
    for q, got in out.items():      # each partition's records in source order
        want = [v for _o, _k, v in records if rekeyreplay.partition_for(v[KEY_FIELD], 7) == q]
        assert [v for _k, v in got] == want
        at += len(got)
    assert at == 5


# -------------------------------------------------------------- the reference
def _mended(batch: bytearray) -> bytes:
    struct.pack_into(">I", batch, CRC_AT, crc32c(bytes(batch[BODY_AT:])))
    return bytes(batch)


def _batch(base, pid, seq, records, transactional):
    wire = bytearray(encode_batch(records))
    struct.pack_into(">q", wire, 0, base)
    struct.pack_into(">h", wire, ATTRIBUTES_AT, 0x10 if transactional else 0)
    struct.pack_into(">qhi", wire, 43, pid, 0, seq)
    return _mended(wire)


def _marker(base, pid, kind):
    wire = bytearray(encode_batch([(struct.pack(">hh", 0, kind), b"")]))
    struct.pack_into(">q", wire, 0, base)
    struct.pack_into(">h", wire, ATTRIBUTES_AT, 0x30)
    struct.pack_into(">qhi", wire, 43, pid, 0, -1)
    return _mended(wire)


SINKS = 3


def _value(n: int) -> bytes:
    """A record's value: a key field that the reference hashes to sink
    partition n % 3, and an event id of its own."""
    i = 0
    while True:
        field = b"%015d" % i + bytes([n % 3])
        if rekeyreplay.partition_for(field, SINKS) == n % SINKS:
            return field + struct.pack(">q", n) + b"." * 8
        i += 1


V = [_value(n) for n in range(6)]    # V[n] belongs in sink partition n % 3
SOURCES = {0: [_batch(10, 7, 0, [(b"a", V[0]), (b"b", V[1]), (b"c", V[3])], False)],
           1: [_batch(20, 8, 0, [(b"d", V[2]), (b"e", V[4])], False),
               _batch(22, 8, 2, [(b"f", V[5])], False)]}
STARTS = {0: 10, 1: 20}
ENDS = {0: 13, 1: 23}


def _sink(*items, pid=50):
    """A sink partition's log from offset 0: (records | "commit" |
    "abort"), the member's producer `pid`, its sequences continuous; a
    record is a value's index, keyed by its key field."""
    out, at, seq = [], 0, 0
    for item in items:
        if isinstance(item, str):
            out.append(_marker(at, pid, txreplay.COMMIT if item == "commit" else txreplay.ABORT))
            at += 1
        else:
            recs = [(V[n][:16], V[n]) for n in item]
            out.append(_batch(at, pid, seq, recs, True))
            at += len(recs)
            seq += len(recs)
    return out


def test_the_reference_holds_a_clean_pipeline_with_an_abort_and_its_second_write():
    sinks = {0: _sink([0, 3], "abort", [0, 3], "commit"),
             1: _sink([1], "abort", [1, 4], "commit"),
             2: _sink([2], "commit", [5], "commit")}
    want = rekeyreplay.expected(SOURCES, STARTS, sinks, ENDS, SINKS)
    assert want.breaks == [] and want.wrong == set() and want.wrong_sinks == set()
    assert want.ends == ENDS
    assert want.handed == {0: [3], 1: [2], 2: [0, 2]}
    assert want.aborted == {0: [0], 1: [0], 2: []}
    assert want.placed == {(0, 10): (0, 3), (0, 11): (1, 2), (0, 12): (0, 3),
                           (1, 20): (2, 0), (1, 21): (1, 2), (1, 22): (2, 2)}
    assert all(rekeyreplay.departures(want, q, h) == [] for q, h in want.handed.items())


def _broken(case: str):
    """The clean sinks with one departure, and what the group committed."""
    committed = dict(ENDS)
    sinks = {0: _sink([0, 3], "abort", [0, 3], "commit"),
             1: _sink([1], "abort", [1, 4], "commit"),
             2: _sink([2], "commit", [5], "commit")}
    if case == "a_lost_record":
        sinks[1] = _sink([1], "abort", [1], "commit")
    elif case == "a_second_copy":
        sinks[2] = _sink([2], "commit", [5], "commit", [5], "commit")
    elif case == "a_record_in_another_partition":
        sinks[1] = _sink([1], "abort", [1, 4, 3], "commit")
        sinks[0] = _sink([0, 3], "abort", [0], "commit")
    elif case == "a_record_under_another_key":
        log = _sink([1], "abort", [1, 4], "commit")
        wire = bytearray(log[2])
        at = wire.index(V[4][:16], RECORDS_AT)
        wire[at] ^= 1                           # the key, not the value
        log[2] = _mended(wire)
        sinks[1] = log
    elif case == "a_reorder_within_a_pair":
        sinks[0] = _sink([0, 3], "abort", [3, 0], "commit")
    elif case == "a_record_no_source_holds":
        sinks[2] = _sink([2], "commit", [5], "commit")
        wire = bytearray(sinks[2][2])
        wire[-3] ^= 1                           # inside the value
        sinks[2][2] = _mended(wire)
    elif case == "a_transaction_left_open":
        sinks[2] = _sink([2], "commit", [5])
    elif case == "a_broken_sequence":
        log = _sink([1], "abort", [1, 4], "commit")
        wire = bytearray(log[2])
        struct.pack_into(">i", wire, 53, 5)
        log[2] = _mended(wire)
        sinks[1] = log
    elif case == "an_offset_short_of_the_end":
        committed[1] = 22
    elif case == "an_offset_past_the_end":
        committed[0] = 14
    elif case == "no_offset_at_all":
        committed[0] = None
    return sinks, committed


@pytest.mark.parametrize("case, rule, words", [
    ("a_lost_record", "exactly_once", "source 1 at 21 has no committed copy"),
    ("a_second_copy", "exactly_once", "source 1 at 22 has 2 committed copies"),
    ("a_record_in_another_partition", "partitioning", "belongs in partition 0"),
    ("a_record_under_another_key", "partitioning", "is keyed"),
    ("a_reorder_within_a_pair", "order", "source 0's record at 10 after its record at 12"),
    ("a_record_no_source_holds", "exactly_once", "holds a record no source holds"),
    ("a_transaction_left_open", "atomicity", "sink 2 at 2: no marker closed it"),
    ("a_broken_sequence", "idempotence", "stored sequence 5 where 1 follows"),
    ("an_offset_short_of_the_end", "exactly_once", "committed 22 for source 1"),
    ("an_offset_past_the_end", "exactly_once", "committed 14 for source 0"),
    ("no_offset_at_all", "exactly_once", "committed None for source 0"),
])
def test_the_reference_names_each_departure(case, rule, words):
    sinks, committed = _broken(case)
    want = rekeyreplay.expected(SOURCES, STARTS, sinks, committed, SINKS)
    named = [b for b in want.breaks if b.startswith(rule + ":")]
    assert any(words in b for b in named), want.breaks
    assert {b.split(":")[0] for b in want.breaks} <= {
        "exactly_once", "partitioning", "order", "atomicity", "idempotence"}
    # what it touches is said, so that a generator can fail the rows
    assert want.wrong or want.wrong_sinks


def test_the_reference_names_an_aborted_batch_handed_on():
    sinks, committed = _broken("none")
    want = rekeyreplay.expected(SOURCES, STARTS, sinks, committed, SINKS)
    assert rekeyreplay.departures(want, 0, [0, 3]) == [
        "atomicity: sink 0: the aborted batch at 0 was handed on"]
    assert rekeyreplay.departures(want, 2, [0])[0].startswith("exactly_once: sink 2:")
    assert rekeyreplay.departures(want, 2, [0, 2]) == []


def test_the_reference_s_consumer_view_is_the_replay_s():
    """What txreplay.Filter hands on, given the aborted transactions the
    log holds, is the replay's visible sequence, with a plain batch and
    two producers' transactions interleaved."""
    log = [_batch(0, 50, 0, [(V[0][:16], V[0])], True),
           _batch(1, 60, 0, [(V[3][:16], V[3])], True),
           _batch(2, -1, -1, [(b"plain", b"p")], False),
           _marker(3, 50, txreplay.ABORT),
           _batch(4, 50, 1, [(V[0][:16], V[0])], True),
           _marker(5, 60, txreplay.COMMIT),
           _marker(6, 50, txreplay.COMMIT)]
    out = txreplay.replay(log)
    assert out.visible == [1, 2, 4] and out.aborted == [0]
    assert rekeyreplay._consumer_view(log, out) == out.visible


# ----------------------------------- the system against the reference, toy size
async def _toy_run(tmp_path, seed: int) -> dict:
    """The cell's generator against the configuration's brokers at toy
    size, in this process: no device switch is on, the served path is
    the same."""
    loaded = run.load_cell(CELL)
    config = cluster.sized(loaded["config"], True, None)
    # half the transactions abort, so that a member's one or two of the
    # toy window hold aborts
    traffic = {**cluster.toy_traffic(loaded["traffic"], True), "drain_s": 10,
               "abort_share": 0.5}
    tpl = run.resolve(traffic["templates"]["maker"], "templates")(seed, traffic, config)
    brokers = cluster.make_brokers(config, str(tmp_path))
    said: list[str] = []
    try:
        cluster.reserve(brokers, config)
        bootstrap = await cluster.start(brokers, config)
        await cluster.first_ack_everywhere(bootstrap, config, tpl)
        spec = {"bootstrap": bootstrap, "topics": config["topics"], "config": config,
                "traffic": traffic, "seed": seed, "seconds": 2.0, "out": None}
        rec = await streams.run(spec, said.append)
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
    finally:
        await cluster.stop(brokers)
    return {"rec": rec, "said": said, "logs": logs, "tpl": tpl, "config": config,
            "offsets": offsets, "pending": dict(group.pending_tx)}


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return asyncio.run(_toy_run(tmp_path_factory.mktemp("omb_100_streams"), 2**31 + 93))


def test_toy_run_every_source_batch_is_a_row_and_all_its_records_came_back(toy):
    rec = toy["rec"]
    rows = rec["rows"]
    assert rec["columns"][10:] == ["t_fetch", "fetched_template", "fetched_bytes"]
    assert len(rows) == 16
    clients = rec["clients"]
    assert clients["transactions"] >= 8 and clients["aborted"] >= 1
    assert clients["rewinds"] == clients["aborted"] and clients["rejoins"] == 0
    # 39 records over 12 partitions: nearly every partition a transaction
    assert 10 <= clients["fanout_p50"] <= 12 and clients["stuck"] == []
    assert rec["fetch_errors"] == [] and rec["unanswered"] == rec["consumers_stuck"] == 0
    assert set(clients["broken"].values()) == {0}
    for r in rows:
        assert r[0] == "bench" and r[3] >= 39 and r[6] is None and r[11] == r[2]
        # the row's share of the sink batches: its records and their headers
        assert 39 * 1024 < r[12] <= 39 * 1040 + 12 * 61
        assert r[4] < r[5] < r[10]          # due, acknowledged, whole in a consumer's hands
    reduced = run.reduce_records(rec, 10.0)
    assert reduced["failed"] == 0 and reduced["acked"] == 16
    assert reduced["metrics"]["e2e_p50_ms"] > reduced["metrics"]["produce_p50_ms"] > 0
    assert toy["said"][0] == "armed" and toy["said"][1].startswith("window_start ")


def test_toy_run_the_served_logs_are_what_the_reference_says(toy):
    """The logs as the leaders hold them and the group as its
    coordinator holds it, read in this process: independent of the
    generator's own read."""
    parts = toy["config"]["topics"][0]["partitions"]
    # from where the window began: set-up's plain batch lies before
    sources = {p: toy["logs"]["bench", p][1:] for p in range(parts)}
    sinks = {q: toy["logs"]["bench-out", q][1:] for q in range(parts)}
    starts = {p: 39 for p in range(parts)}
    want = rekeyreplay.expected(sources, starts, sinks, toy["offsets"], parts)
    assert want.breaks == []
    assert len(want.placed) == 16 * 39
    assert sum(len(a) for a in want.aborted.values()) >= toy["rec"]["clients"]["aborted"]
    assert toy["pending"] == {}     # no offset left staged after the drain
    # every source batch is a template's, stamped with its producer's ids
    tpl = toy["tpl"]
    by_key = {t.key: t for t in tpl}
    for log in sources.values():
        for batch in log:
            assert by_key[tpl[0].key_of(batch)].came_back(batch)


# ------------------------------------------------ fresh interpreters: the cell
def test_traced_dry_run_is_correct_and_reads_the_marker_fan_out():
    line = dry_run(CELL, seed=2**31 + 431, trace=1)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["tx_marker_ms"] > 0
    # one marker after another: the data markers' time over the whole
    # wait, short of 1 by the group marker's share
    assert 0.8 < metrics["marker_overlap"] <= 1.0
    for name in ("tx_markers_ms", "group_marker_ms", "tx_add_partitions_ms"):
        assert name not in metrics      # listed to the other cells alone
    for name in ("unanswered", "never_fetched", "fetched_wrong", "replicas_missing",
                 "not_flushed_at_ack"):
        assert line["checks"][name] == {"value": 0, "limit": 0}
    clients = line["detail"]["clients"]
    assert clients["aborted"] >= 1 and set(clients["broken"].values()) == {0}
    assert 10 <= clients["fanout_p50"] <= 12
    assert line["detail"]["compiles_in_window"] == 0


def _planted(fault: str, seed: int) -> dict:
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "3", "--trace", "0",
            "--cpu-dry-run"]
    cmd = [sys.executable, "-c",
           "import sys; from benchmark.tests import streams_faults; "
           f"streams_faults.plant({fault!r}); from benchmark import run; "
           f"sys.exit(run.main({argv!r}))"]
    got = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    return json.loads(got.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", sorted(streams_faults.FAULTS))
def test_a_planted_fault_is_not_correct_by_the_rule_it_breaks(fault):
    line = _planted(fault, 2**31 + 440 + sorted(streams_faults.FAULTS).index(fault))
    assert line["correct"] is False, line["checks"]
    rule, check = streams_faults.FAULTS[fault]
    assert line["checks"][check]["value"] > 0, line["checks"]
    # the source's batches were stored, replicated and flushed all the same
    for name in ("replicas_missing", "not_flushed_at_ack"):
        assert line["checks"][name]["value"] == 0, name
    assert line["checks"]["acked"]["value"] > 0
    broken = line["detail"]["clients"]["broken"]
    assert broken[rule] > 0, broken
    assert any(e.startswith(rule + ":") for e in line["detail"]["fetch_errors"])
