"""The exactly-once deployment's reference and its cell,
`omb_100_tx.txn_per_batch_0p8`, as far as the CPU can hold them.

In this process: what `TransactionalTemplate` says came back of
hand-made batches (each field it checks switched, one case each) and
that its stamp's CRC is the reference's plain CRC-32C; the consumer's
filter and the replay on hand-made logs; the generator against three
in-process brokers at toy size (12 partitions, RF=3), a seeded schedule
of commits and aborts read back `read_committed` through the served
path and held to the replay of the `read_uncommitted` log.

In fresh interpreters (about half a minute each): a --cpu-dry-run of
the cell prints the four end-to-end metrics and, traced, the six
per-layer metrics that read the transaction path's spans; each of the
three planted faults (tx_faults.py) comes out as not correct by the
rule it breaks. tests/test_benchmark_omb_100_tx.py collects all of it
into tier-1 by import.
"""

import asyncio
import json
import os
import struct
import subprocess
import sys

import pytest

from benchmark import cluster, run, txreplay
from benchmark.generators import transactional
from benchmark.reference import (
    ATTRIBUTES_AT, BODY_AT, CRC_AT, RECORDS_AT, Template, batch_holds, crc32c, encode_batch,
)
from benchmark.templates.transactional import (
    _raw, TransactionalTemplate, incompressible, zeros_advance,
)
from benchmark.tests import tx_faults
from benchmark.tests.conftest import ROOT, dry_run

CELL = "omb_100_tx.txn_per_batch_0p8"
TRAFFIC = {"templates": {"count": 2}, "batch_records": 3}
CONFIG = {"record_bytes": 64}


@pytest.fixture(scope="module")
def tpl():
    return incompressible(2**31 + 5, TRAFFIC, CONFIG)


# ------------------------------------------------------------ the template
def test_a_stamp_s_crc_is_the_reference_s_crc_of_the_stamped_bytes(tpl):
    for t in tpl:
        for pid, epoch, seq in ((0, 0, 0), (1007, 3, 39 * 41), (2**40 + 9, 32767, 2**31 - 1)):
            wire = t.stamp(pid, epoch, seq)
            assert len(wire) == len(t.wire) and batch_holds(wire)
            assert struct.unpack_from(">I", wire, CRC_AT)[0] == crc32c(wire[BODY_AT:])
            assert struct.unpack_from(">qhi", wire, 43) == (pid, epoch, seq)
            assert struct.unpack_from(">h", wire, ATTRIBUTES_AT)[0] == 0x10
            assert wire[RECORDS_AT:] == t.wire[RECORDS_AT:] == t.key == t.key_of(wire)
            assert t.came_back(wire)


@pytest.mark.parametrize("n", [0, 1, 3, 255, 4096, 40291])
def test_zeros_advance_is_the_register_run_over_that_many_zero_bytes(n):
    matrix = zeros_advance(n)
    for start in (1, 0x80000000, 0xDEADBEEF, 0xFFFFFFFF):
        got = 0
        for bit in range(32):
            if start >> bit & 1:
                got ^= matrix[bit]
        assert got == _raw(bytes(n), start)


def _mended(batch: bytearray) -> bytes:
    """The batch with its CRC made anew by the reference's plain CRC."""
    struct.pack_into(">I", batch, CRC_AT, crc32c(bytes(batch[BODY_AT:])))
    return bytes(batch)


def _switched(tpl, what: str) -> bytes:
    """A stamped batch of the first template with one thing switched."""
    b = bytearray(tpl[0].stamp(1007, 2, 78))
    if what == "as_stamped":
        return bytes(b)
    if what == "another_producer_s_stamp":
        return tpl[0].stamp(99, 0, 0)
    if what == "not_stamped":
        return tpl[0].wire
    if what == "crc_field":
        b[CRC_AT] ^= 0x01
        return bytes(b)
    if what == "truncated":
        return bytes(b[:-1])
    if what == "another_template":
        return tpl[1].stamp(1007, 2, 78)
    at, fmt, value = {
        "transactional_bit_off": (ATTRIBUTES_AT, ">h", 0x00),
        "control_bit_on": (ATTRIBUTES_AT, ">h", 0x30),
        "a_codec_named": (ATTRIBUTES_AT, ">h", 0x13),
        "last_offset_delta": (23, ">i", 1),
        "base_timestamp": (27, ">q", 1),
        "max_timestamp": (35, ">q", 1),
        "producer_id_negative": (43, ">q", -1),
        "epoch_negative": (51, ">h", -1),
        "sequence_negative": (53, ">i", -1),
        "record_count": (57, ">i", 2),
        "a_record_s_byte": (len(b) - 1, ">B", b[-1] ^ 0x01),
        "magic": (16, ">b", 1),
    }[what]
    struct.pack_into(fmt, b, at, value)
    return _mended(b)


@pytest.mark.parametrize("what", ["as_stamped", "another_producer_s_stamp"])
def test_a_stamped_batch_came_back(tpl, what):
    assert tpl[0].came_back(_switched(tpl, what))


@pytest.mark.parametrize("what", [
    "not_stamped", "crc_field", "truncated", "another_template",
    "transactional_bit_off", "control_bit_on", "a_codec_named", "last_offset_delta",
    "base_timestamp", "max_timestamp", "producer_id_negative", "epoch_negative",
    "sequence_negative", "record_count", "a_record_s_byte", "magic",
])
def test_a_batch_with_one_thing_switched_did_not_come_back(tpl, what):
    batch = _switched(tpl, what)
    # the CRC was mended wherever the switch left the crc field alone:
    # it is the field that is refused, not a broken checksum
    assert what in ("crc_field", "truncated") or batch_holds(batch)
    assert not tpl[0].came_back(batch)


def test_the_broker_s_offset_and_leader_epoch_are_not_the_template_s(tpl):
    b = bytearray(tpl[0].stamp(5, 0, 0))
    struct.pack_into(">q", b, 0, 123456)
    struct.pack_into(">i", b, 12, 7)
    assert tpl[0].came_back(bytes(b))


def test_a_template_is_made_from_a_plain_batch(tpl):
    with pytest.raises(ValueError):
        TransactionalTemplate(tpl[0].stamp(1, 0, 0), [(b"k", b"v")] * 3)
    assert isinstance(tpl[0], Template) and tpl[0].records == 3


# ---------------------------------------------------- the filter, the replay
def _data(base, pid, seq, epoch=0, transactional=True, n=3):
    wire = bytearray(encode_batch([(b"k%d" % i, b"v") for i in range(n)]))
    struct.pack_into(">q", wire, 0, base)
    struct.pack_into(">h", wire, ATTRIBUTES_AT, 0x10 if transactional else 0)
    struct.pack_into(">qhi", wire, 43, pid, epoch, seq)
    return _mended(wire)


def _marker(base, pid, kind, epoch=0):
    """A control batch as KIP-98 describes it: one record whose key is
    (version 0, type)."""
    wire = bytearray(encode_batch([(struct.pack(">hh", 0, kind), b"")]))
    struct.pack_into(">q", wire, 0, base)
    struct.pack_into(">h", wire, ATTRIBUTES_AT, 0x30)
    struct.pack_into(">qhi", wire, 43, pid, epoch, -1)
    return _mended(wire)


LOG = [
    _data(0, -1, -1, epoch=-1, transactional=False),   # set-up's plain batch
    _data(3, 7, 0),
    _data(6, 8, 0),
    _marker(9, 7, txreplay.COMMIT),
    _data(10, 7, 3),
    _marker(13, 8, txreplay.ABORT),
    _data(14, 8, 3),
    _marker(17, 7, txreplay.ABORT),
    _marker(18, 8, txreplay.COMMIT),
    _data(19, 9, 0),                                   # still open
]


def test_head_and_marker_read_what_kip_98_describes():
    assert txreplay.head_of(LOG[1]) == (3, 5, 7, 0, 0, 3, True, False)
    marker = txreplay.head_of(LOG[3])
    assert marker.control and marker.transactional and marker.producer_id == 7
    assert [txreplay.marker_of(LOG[i]) for i in (3, 5)] == [txreplay.COMMIT, txreplay.ABORT]
    # a key that is no (version 0, type) pair, and a batch cut short: no marker
    assert txreplay.marker_of(_data(0, 1, 0)) is None
    assert txreplay.marker_of(LOG[3][:62]) is None


def test_the_replay_s_visible_sequence_is_what_a_commit_marker_closed():
    got = txreplay.replay(LOG)
    assert got.visible == [0, 3, 14]
    assert got.aborted == [6, 10] and got.open == [19]
    assert got.closed_by == {3: 9, 6: 13, 10: 17, 14: 18}
    assert got.sequence_breaks == []


@pytest.mark.parametrize("log, breaks", [
    ([_data(0, 7, 0), _data(3, 7, 0)], [(7, 3, 3, 0)]),              # stored twice
    ([_data(0, 7, 0), _data(3, 7, 6)], [(7, 3, 3, 6)]),              # one missing
    ([_data(0, 7, 3)], [(7, 0, 0, 3)]),                              # the first missing
    ([_data(0, 7, 0), _data(3, 8, 0), _data(6, 7, 3)], []),          # two producers
    ([_data(0, 7, 0), _data(3, 7, 0, epoch=1)], []),                 # a new epoch starts over
], ids=["twice", "gap", "first_missing", "interleaved", "new_epoch"])
def test_the_replay_holds_every_producer_s_sequences_continuous(log, breaks):
    assert txreplay.replay(log).sequence_breaks == breaks


def _filtered(aborted, log):
    flt = txreplay.Filter(aborted)
    return [(txreplay.head_of(b).base, flt.take(b)) for b in log]


def test_the_filter_drops_an_aborted_producer_s_batches_until_its_abort_marker():
    got = _filtered([(8, 6), (7, 10)], LOG[1:9])
    assert got == [(3, "deliver"), (6, "aborted"), (9, "control"), (10, "aborted"),
                   (13, "control"), (14, "deliver"), (17, "control"), (18, "control")]


def test_the_filter_hands_on_what_the_broker_did_not_call_aborted():
    # the broker's word left out: the aborted batches are handed on
    assert [w for _b, w in _filtered([], LOG[1:9])].count("deliver") == 4
    # a plain batch is no transaction's, whoever is aborted
    assert _filtered([(-1, 0)], LOG[:1]) == [(0, "deliver")]
    # an entry counts from its first offset on, not before
    assert _filtered([(7, 10)], LOG[1:2]) == [(3, "deliver")]


def test_filter_and_replay_agree_where_the_broker_s_word_is_the_log_s():
    log = LOG[:9]
    replayed = txreplay.replay(log)
    aborted = [(txreplay.head_of(b).producer_id, txreplay.head_of(b).base)
               for b in log if txreplay.head_of(b).base in replayed.aborted]
    handed = [base for base, what in _filtered(aborted, log) if what == "deliver"]
    assert handed == replayed.visible


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
        rec = await transactional.run(spec, said.append)
        logs = {}
        for p in range(config["topics"][0]["partitions"]):
            (leader,) = [r for r in cluster.replicas(brokers, "bench", p) if r.is_leader]
            # framed with the kafka offset, which the CRC does not cover
            logs[p] = [struct.pack(">q", k) + b.to_kafka_wire()[8:]
                       for k, b in leader.read_kafka(0, 1 << 30)]
        coordinator = [sum(b.partition_manager.get(ntp) is not None for b in brokers)
                       for ntp in _coordinator_ntps(config)]
    finally:
        await cluster.stop(brokers)
    return {"rec": rec, "said": said, "logs": logs, "tpl": tpl, "config": config,
            "coordinator": coordinator}


def _coordinator_ntps(config: dict) -> list:
    from redpanda_tpu.models.fundamental import NTP

    topic = config["coordinator_topic"]
    return [NTP(topic["namespace"], topic["name"], p) for p in range(topic["partitions"])]


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return asyncio.run(_toy_run(tmp_path_factory.mktemp("omb_100_tx"), 2**31 + 77))


def test_toy_run_every_transaction_is_a_row_and_read_back_as_its_template_says(toy):
    rec = toy["rec"]
    rows = rec["rows"]
    assert rec["columns"][10:] == ["t_fetch", "fetched_template", "fetched_bytes"]
    assert len(rows) == 40 and rec["clients"]["transactions"] == 40
    assert rec["clients"]["aborted"] == 4 == rec["clients"]["aborted_dropped_by_filter"]
    assert rec["clients"]["committed_handed_on"] == 36
    assert rec["fetch_errors"] == [] and rec["unanswered"] == rec["consumers_stuck"] == 0
    for r in rows:
        assert r[3] >= 1 and r[6] is None and r[11] == r[2] and r[12] == len(toy["tpl"][0].wire)
        assert r[4] < r[5] <= r[10]          # due, acknowledged, in a consumer's hands
    reduced = run.reduce_records(rec, 10.0)
    assert reduced["failed"] == 0 and reduced["acked"] == 40
    assert reduced["metrics"]["e2e_p50_ms"] > reduced["metrics"]["produce_p50_ms"] > 0
    assert toy["said"][0] == "armed" and toy["said"][1].startswith("window_start ")
    assert any(line.startswith("acked ") for line in toy["said"])


def test_toy_run_the_served_logs_replay_to_what_the_consumers_were_handed(toy):
    """The logs as the leaders hold them, read in this process and
    replayed by the reference: independent of the generator's own read."""
    by_partition: dict = {}
    committed_rows = {(r[1], r[3]) for r in toy["rec"]["rows"]}
    for p, log in toy["logs"].items():
        got = txreplay.replay(log)
        assert got.open == [] and got.sequence_breaks == []
        assert got.visible[0] == 0                  # set-up's plain batch
        by_partition[p] = got
        for base in got.visible[1:] + got.aborted:
            assert (p, base) in committed_rows
    n_visible = sum(len(g.visible) - 1 for g in by_partition.values())
    n_aborted = sum(len(g.aborted) for g in by_partition.values())
    assert (n_visible, n_aborted) == (36, 4)
    # every data batch is a template's, stamped: the template says so
    tpl = toy["tpl"]
    by_key = {t.key: t for t in tpl}
    for log in toy["logs"].values():
        for batch in log[1:]:
            if not txreplay.head_of(batch).control:
                assert by_key[tpl[0].key_of(batch)].came_back(batch)


def test_toy_run_the_coordinator_topic_is_as_the_configuration_states(toy):
    topic = toy["config"]["coordinator_topic"]
    from redpanda_tpu.cluster import tx_coordinator

    assert (topic["namespace"], topic["name"], topic["partitions"]) == (
        tx_coordinator.TX_NS, tx_coordinator.TX_TOPIC, tx_coordinator.DEFAULT_TX_PARTITIONS)
    assert toy["coordinator"] == [topic["replication_factor"]] * topic["partitions"]


# ------------------------------------------------ fresh interpreters: the cell
def test_dry_run_prints_the_four_end_to_end_metrics_and_is_correct():
    line = dry_run(CELL, seed=2**31 + 351)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert set(line["metrics"]) == {"produce_mb_s", "produce_p50_ms", "e2e_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    clients = line["detail"]["clients"]
    assert clients["transactions"] == line["attempted"] > 0
    assert clients["aborted"] == round(0.1 * clients["transactions"]) \
        == clients["aborted_dropped_by_filter"]
    for name in ("unanswered", "never_fetched", "fetched_wrong", "replicas_missing",
                 "not_flushed_at_ack"):
        assert line["checks"][name] == {"value": 0, "limit": 0}
    assert line["checks"]["dispatched.crc32c.device"]["value"] >= 1
    assert line["detail"]["compiles_in_window"] == 0


def test_traced_dry_run_reads_the_six_metrics_of_the_transaction_path():
    line = dry_run(CELL, seed=2**31 + 352, trace=1)
    assert line["correct"] is True, line["checks"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    for name in ("tx_add_partitions_ms", "tx_end_ms", "tx_markers_ms", "lso_wait_ms"):
        assert metrics[name] > 0, name
    assert metrics["tx_end_ms"] > metrics["tx_markers_ms"]
    assert metrics["fetch_reads_per_fetch"] > 1          # a fetch parks and reads again
    assert 4.9 <= metrics["leader_appends_per_acked_batch"] <= 5.3
    assert "crc_roofline" not in metrics and "tick_roofline" not in metrics


def _planted(fault: str, seed: int) -> dict:
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "3", "--trace", "0",
            "--cpu-dry-run"]
    cmd = [sys.executable, "-c",
           "import sys; from benchmark.tests import tx_faults; "
           f"tx_faults.plant({fault!r}); from benchmark import run; "
           f"sys.exit(run.main({argv!r}))"]
    got = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    return json.loads(got.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", sorted(tx_faults.FAULTS))
def test_a_planted_fault_is_not_correct_by_the_rule_it_breaks(fault):
    line = _planted(fault, 2**31 + 360 + sorted(tx_faults.FAULTS).index(fault))
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["fetched_wrong"]["value"] > 0
    # every other check holds: the batches were stored, replicated and flushed
    for name in ("unanswered", "replicas_missing", "not_flushed_at_ack"):
        assert line["checks"][name]["value"] == 0, name
    assert line["checks"]["acked"]["value"] > 0
    rule = tx_faults.FAULTS[fault]
    broken = line["detail"]["clients"]["broken"]
    assert broken[rule] > 0, broken
    # a fetch served past the LSO also hands on what is then aborted;
    # the other two faults break their own guarantee and no other
    if fault != "lso_ignored":
        assert [r for r, n in broken.items() if n] == [rule], broken
    assert line["detail"]["fetch_errors"][0].startswith(rule + ":")
