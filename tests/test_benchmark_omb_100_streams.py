"""The re-keying pipeline's benchmark cell, as far as the CPU can hold
it: its files as `benchmark.run.load_cell` finds them by name, held to
`omb_100_ctp`'s where they must be equal; and, by import, every case of
benchmark/tests/test_omb_100_streams.py: the template, the reference's
partitioner and decoder, the reference (benchmark/rekeyreplay.py) on
hand-made logs, the system against the reference at toy size, a traced
--cpu-dry-run of the cell and the five planted faults (one file: the
dry runs of a cell share its data directory, so they run one after
another)."""

import json
import os

import pytest

from benchmark import run
from benchmark.tests import test_omb_100_streams

for _name, _case in vars(test_omb_100_streams).items():
    if _name.startswith("test_") or _name in ("pool", "toy"):   # its fixtures too
        assert _name not in globals(), _name
        globals()[_name] = _case

CELL = "omb_100_streams.rekey_0p8"
NEW_METRICS = {
    "tx_marker_ms": ("ms", "lower", "hostspans.span_p50_ms", {"span": "tx.marker"}),
    "marker_overlap": ("ratio", "higher", "markers.overlap",
                       {"part": "tx.marker", "whole": "tx.markers"}),
}


@pytest.fixture(scope="module")
def loaded():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def omb_100_ctp():
    return run.load_json(run.HERE, "configs", "omb_100_ctp.json")


def _own_traffic() -> dict:
    with open(os.path.join(run.HERE, "traffic", "rekey_0p8.json")) as f:
        return json.load(f)


# ------------------------------------------------------ the configuration
def test_the_cell_loads_with_its_config(loaded):
    cell = loaded["cell"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("omb_100_streams",
                                                                "rekey_0p8", 1)
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "produce_mb_s", "produce_p50_ms", "e2e_p50_ms", "setup_s"}


def test_the_configuration_is_omb_100_ctp_s_key_for_key_but_what_it_adds(loaded, omb_100_ctp):
    config = loaded["config"]
    differ = {k for k in set(config) | set(omb_100_ctp) if config.get(k) != omb_100_ctp.get(k)}
    assert differ == {"source", "deployment", "transform", "guarantees", "warm", "assumed"}
    for word in ("selectKey", "exactly_once_v2", "KIP-447",
                 "openmessaging_benchmark_configs.py:123-135"):
        assert word in config["source"], word
    for word in ("murmur2", "key field", "default partitioner", "modulo 100"):
        assert word in config["transform"], word
    assert config["warm"] == ["streams.sink_batches"] + omb_100_ctp["warm"]
    assert all(callable(run.resolve(w, "warmers")) for w in config["warm"])
    assert config["reduced"] == omb_100_ctp["reduced"]


def test_the_assumptions_are_omb_100_ctp_s_and_the_re_key_s(loaded, omb_100_ctp):
    assumed, theirs = loaded["config"]["assumed"], omb_100_ctp["assumed"]
    assert set(theirs) <= set(assumed)
    assert {"key", "sink_partitions", "batches"} == set(assumed) - set(theirs)
    for key in ("compression", "tx_partitions", "transaction_timeout_ms", "lane_capacity",
                "source_lines", "commit_interval_ms", "assignor", "heartbeat_and_session",
                "offsets_partitions"):
        assert assumed[key] == theirs[key], key
    for word in ("16 bytes", "--seed", "2,496 distinct keys", "100 sink partitions",
                 "event id"):
        assert word in assumed["key"], word
    assert "16 members" in assumed["members"] and "bench-streams" in assumed["members"]
    assert "0.1" in assumed["abort_share"]
    assert "100, the source's" in assumed["sink_partitions"]


def test_no_guarantee_is_weaker_than_omb_100_ctp_s(loaded, omb_100_ctp):
    mine, theirs = loaded["config"]["guarantees"], omb_100_ctp["guarantees"]
    assert set(mine) == set(theirs) | {"exactly_once_rekeyed"}
    for key in ("acks", "durability", "replication", "atomicity", "isolation", "served_on"):
        assert mine[key] == theirs[key], key
    # restated for re-keyed records, none of what they hold dropped
    for word in ("producer id, epoch and base sequence", "continuous from 0",
                 "nothing stored twice, nothing missing", "same sequence"):
        assert word in mine["idempotence"] and word in theirs["idempotence"], word
    for word in ("exactly once", "read_committed", "committed offset", "partition's end",
                 "not short of it and not past it", "aborted transaction",
                 "broker's word"):
        assert word in mine["exactly_once"] and word in theirs["exactly_once"], word
    assert "rekeyreplay.py" in mine["exactly_once"]
    for word in ("verify-on-read", "every replica", "crc holds", "tolerance none"):
        assert word in mine["read_back"] and word in theirs["read_back"], word
    for word in ("key field", "murmur2", "source's order", "rekeyreplay.py"):
        assert word in mine["exactly_once_rekeyed"], word


def test_the_warmer_refuses_a_program_without_the_many_partition_produce(monkeypatch, loaded):
    from benchmark.warmers import streams
    from redpanda_tpu.kafka.client import TransactionalProducer

    monkeypatch.delattr(TransactionalProducer, "produce_batches")
    with pytest.raises(SystemExit, match="produce_batches"):
        streams.sink_batches([], loaded["config"], loaded["traffic"], [])


def test_the_manifest_entries_are_appended_and_match_the_files(loaded):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    assert manifest["configs"][-1]["name"] == "omb_100_streams"
    entry = manifest["configs"][-1]
    assert entry["source"] == loaded["config"]["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/omb_100_streams.json"
    assert sorted(entry["reduced"]) == sorted(loaded["config"]["reduced"])
    assert manifest["workloads"][-1]["name"] == CELL
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == "omb_100_streams"] == [CELL]
    why = loaded["cell"]["why"]
    assert len(why) <= 200 and "knee" in why and str(_own_traffic()["batches_per_s"]) in why
    assert [m["name"] for m in manifest["per_layer"][-2:]] == ["tx_marker_ms", "marker_overlap"]
    # every cell on one chip, at most 24
    assert len(manifest["workloads"]) <= 24
    assert {w["chips"] for w in manifest["workloads"]} == {1}


# ------------------------------------------------------------ the traffic
def test_the_traffic_is_the_streams_application_s(loaded):
    traffic, own = loaded["traffic"], _own_traffic()
    assert own["base"] == "omb_client" and "schedule" not in own
    assert traffic["generator"] == "streams.run"
    assert callable(run.resolve(traffic["generator"], "generators"))
    assert traffic["templates"] == {"maker": "streams.keyed", "count": 64}
    assert (traffic["producers"], traffic["members"], traffic["consumers"],
            traffic["batch_records"]) == (16, 16, 8, 39)
    assert (traffic["abort_share"], traffic["isolation_level"], traffic["fetch_min_bytes"],
            traffic["fetch_max_wait_ms"], traffic["fetch_max_bytes"],
            traffic["transaction_timeout_ms"], traffic["commit_interval_ms"],
            traffic["heartbeat_interval_ms"], traffic["session_timeout_ms"],
            traffic["group"]) == (0.1, "read_committed", 1, 500, 131072, 60000, 100,
                                  3000, 45000, "bench-streams")
    base = run.load_json(run.HERE, "traffic", "omb_client.json")
    for key in ("linger_ms", "batch_bytes", "request_timeout_ms", "ack_sample_s", "drain_s"):
        assert traffic[key] == base[key], key
    # the client's traffic file is the copy pipeline's where it is not the re-key's
    ctp = run.load_json(run.HERE, "traffic", "copy_0p8.json")
    for key in ("producers", "members", "consumers", "batch_records", "abort_share",
                "fetch_min_bytes", "fetch_max_wait_ms", "commit_interval_ms",
                "heartbeat_interval_ms", "session_timeout_ms", "transaction_timeout_ms"):
        assert own[key] == ctp[key], key
    for word in ("idempotent", "bench-streams-<i>", "require_stable", "TxnOffsetCommit v3",
                 "key field", "one ProduceRequest a leader", "out of band",
                 "rekeyreplay.py"):
        assert word in own["what"], word


def test_the_rate_is_four_fifths_of_the_knee_its_derived_names():
    own = _own_traffic()
    knee = own["derived"]["knee"]
    rate = own["batches_per_s"]
    assert rate == int(0.8 * knee["batches_per_s"])
    # the knee's criterion (a 40 s window's median within 1.5 x the
    # unloaded one, 0.99 of the offered acknowledged inside it), on both
    # medians the cell reports, on two seeds: at the knee both windows
    # hold, above it a window fails by a median or by what it acknowledged
    limits = {m: 1.5 * knee["unloaded_p50_ms"][m] for m in ("produce", "e2e")}
    at_knee = str(knee["batches_per_s"])
    assert len(knee["windows"][at_knee]) == 2
    for r, reads in knee["windows"].items():
        held = all(read[m] <= limits[m] for read in reads for m in limits) and all(
            a >= 0.99 * o for a, o in knee["acked_of_offered"][r])
        assert held == (float(r) <= knee["batches_per_s"]), r
    assert any(float(r) > knee["batches_per_s"] for r in knee["windows"])
    said = json.dumps(own["derived"])
    for name in knee["files"]:
        assert name in said and os.path.exists(os.path.join(run.HERE, "tools", name))
    assert "rekey_sweep.json" in knee["files"]


def test_the_sweeps_lay_over_the_cell_s_traffic():
    tools = os.path.join(run.HERE, "tools")
    sweeps = sorted(f for f in os.listdir(tools) if f.startswith("rekey_"))
    assert len(sweeps) >= 3
    for name in sweeps:
        with open(os.path.join(tools, name)) as f:
            own = json.load(f)
        assert own["base"] == "../traffic/rekey_0p8"
        assert set(own) <= {"base", "what", "schedule", "batches_per_s"}
        sweep = run.load_traffic(os.path.join(tools, name))
        assert sweep["generator"] == "streams.run" and sweep["abort_share"] == 0.1
        if "batches_per_s" in own:   # a window at one rate, to its close
            assert own["schedule"] == [[40, own["batches_per_s"]]]
        else:
            assert all(secs == 8 and rate > 0 for secs, rate in own["schedule"])


# ------------------------------------------------------------ the metrics
@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metrics_are_read_on_the_cell_and_on_no_other(loaded, name):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    unit, better, reader, params = NEW_METRICS[name]
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better, "source": "program_span",
                     "layer": "transactions", "moves": "e2e_p50_ms", "workloads": [CELL]}
    assert run.load_json(run.HERE, "metrics", name + ".json") == {
        "name": name, "reader": reader, "params": params}
    assert callable(run.resolve(reader, "readers"))
    assert name in {m["name"] for m in loaded["per_layer"]}
    for cell in manifest["workloads"]:
        if cell["name"] != CELL:
            assert name not in {m["name"] for m in run.load_cell(cell["name"])["per_layer"]}


def test_the_cell_reads_every_unlisted_metric_and_its_own():
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    unlisted = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in run.load_cell(CELL)["per_layer"]} == unlisted | set(NEW_METRICS)


def test_the_readers_say_nothing_of_a_program_without_the_marker_span():
    """The parent's records: `tx.markers` with no `tx.marker` under it."""
    spans = [["tx.markers", "wait", 0, 1000, 2, 0, 2, {}]]
    host = {"tx.markers": {"kind": "wait", "count": 1, "total_s": 1e-6, "self_s": 1e-6,
                           "p50_ms": 0.001, "p99_ms": 0.001}}
    ctx = {"devplane": {"spans": spans, "spans_dropped": 0, "host": host}}
    for name, (_u, _b, reader, params) in NEW_METRICS.items():
        assert run.resolve(reader, "readers")(ctx, params) is None, name


def test_the_overlap_is_the_markers_time_over_the_wait_that_holds_them():
    from benchmark.readers import markers

    def transaction(at, side_by_side):
        """32 markers of 7 ms and a group marker of 8, under one wait."""
        parts = [["tx.marker", "wait", at + (0 if side_by_side else 7 * k), 7, at + 1 + k,
                  at, 1, {"remote": 1, "retries": 0}] for k in range(32)]
        whole = 7 + 8 if side_by_side else 32 * 7 + 8
        return parts + [["tx.markers", "wait", at, whole, at, 0, 1, {"partitions": 32}]]

    params = {"part": "tx.marker", "whole": "tx.markers"}
    for side_by_side, want in ((False, 224 / 232), (True, 224 / 15)):
        spans = transaction(1000, side_by_side) + transaction(2000, side_by_side)
        ctx = {"devplane": {"spans": spans, "spans_dropped": 0, "host": {}}}
        assert markers.overlap(ctx, params) == pytest.approx(want)


def test_the_overlap_from_raw_records_leaves_out_a_wait_the_window_cut():
    from benchmark.readers import markers

    def marker(i, parent, start, dur):
        return ["tx.marker", "wait", start, dur, i, parent, 1, {"remote": 1, "retries": 0}]

    spans = [
        # a whole of three, all in the window: 3 x 7 under 25
        marker(11, 10, 100, 7), marker(12, 10, 107, 7), marker(13, 10, 114, 7),
        ["tx.markers", "wait", 100, 25, 10, 1, 1, {"partitions": 3}],
        # a whole whose first part ended before the window opened
        marker(22, 20, 207, 7),
        ["tx.markers", "wait", 200, 16, 20, 1, 1, {"partitions": 2}],
        ["tick.upload", "run", 0, 1, 30, 0, 3, {"rows": 3}],
    ]
    ctx = {"devplane": {"spans": spans, "spans_dropped": 0, "host": {}}}
    assert markers.overlap(ctx, {"part": "tx.marker", "whole": "tx.markers"}) == \
        pytest.approx(21 / 25)
    # raw records dropped: nothing is said
    ctx["devplane"]["spans_dropped"] = 5
    assert markers.overlap(ctx, {"part": "tx.marker", "whole": "tx.markers"}) is None
