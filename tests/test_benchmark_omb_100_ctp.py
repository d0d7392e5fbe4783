"""The exactly-once pipeline's benchmark cell, as far as the CPU can hold
it: its files as `benchmark.run.load_cell` finds them
by name, held to `omb_100_tx`'s where they must be equal; and, by import,
every case of benchmark/tests/test_omb_100_ctp.py: the templates, the
range assignor and the reference (benchmark/ctpreplay.py) on hand-made
logs, the system against the reference at toy size, a traced
--cpu-dry-run of the cell and the three planted faults."""

import json
import os

import pytest

from benchmark import run
from benchmark.tests import test_omb_100_ctp

for _name, _case in vars(test_omb_100_ctp).items():
    if _name.startswith("test_") or _name in ("copied", "toy"):   # its fixtures too
        assert _name not in globals(), _name
        globals()[_name] = _case

CELL = "omb_100_ctp.copy_0p8"
NEW_METRICS = {
    "tx_add_offsets_ms": ("ms", "transactions", "program_span",
                          "hostspans.span_p50_ms", {"span": "tx.add_offsets"}),
    "txn_offset_commit_ms": ("ms", "group coordinator", "program_span",
                             "hostspans.span_p50_ms", {"span": "group.txn_offset_commit"}),
    "group_marker_ms": ("ms", "group coordinator", "program_span",
                        "hostspans.span_p50_ms", {"span": "tx.group_marker"}),
    "rebalances_in_window": ("count", "group coordinator", "program_counter",
                             "groups.event_count", {"event": "rebalances"}),
}


@pytest.fixture(scope="module")
def loaded():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def omb_100_tx():
    return run.load_json(run.HERE, "configs", "omb_100_tx.json")


def _own_traffic() -> dict:
    with open(os.path.join(run.HERE, "traffic", "copy_0p8.json")) as f:
        return json.load(f)


# ------------------------------------------------------ the configuration
def test_the_cell_loads_with_its_config(loaded):
    config, cell = loaded["config"], loaded["cell"]
    for key in ("source", "deployment", "brokers", "layout", "topics", "coordinator_topic",
                "group_coordinator_topic", "record_bytes", "acks", "broker", "lane_capacity",
                "guarantees", "env", "device_kernels", "warm", "assumed", "reduced", "toy"):
        assert key in config, key
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("omb_100_ctp", "copy_0p8", 1)
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "produce_mb_s", "produce_p50_ms", "e2e_p50_ms", "setup_s"}


@pytest.mark.parametrize("key", ["brokers", "record_bytes", "acks", "broker", "env",
                                 "device_kernels", "layout", "chips", "coordinator_topic",
                                 "pass_marks"])
def test_the_rest_is_omb_100_tx_s_key_for_key(loaded, omb_100_tx, key):
    mine, theirs = loaded["config"][key], omb_100_tx[key]
    if key == "coordinator_topic":   # its words name this cell's clients
        mine, theirs = ({k: v for k, v in t.items() if k != "what"} for t in (mine, theirs))
    assert mine == theirs


def test_the_topics_are_the_source_and_the_sink_at_the_release_smoke_s_shape(loaded, omb_100_tx):
    source, sink = loaded["config"]["topics"]
    assert source == omb_100_tx["topics"][0] and source["name"] == "bench"
    assert {**sink, "name": "bench"} == source and sink["name"] == "bench-out"


def test_the_group_coordinator_s_topic_is_stated_and_fits_the_lanes(loaded):
    config = loaded["config"]
    topic = config["group_coordinator_topic"]
    assert (topic["namespace"], topic["name"], topic["partitions"],
            topic["replication_factor"]) == ("kafka", "__consumer_offsets", 4, 3)
    assert "set-up" in topic["what"] and "fsync before ack" in topic["what"]
    groups = (sum(t["partitions"] for t in config["topics"]) + 1
              + config["coordinator_topic"]["partitions"] + topic["partitions"])
    assert groups == 209 <= config["lane_capacity"] == 256
    assert "209" in config["assumed"]["lane_capacity"]


def test_the_cut_is_the_rate_alone(loaded, omb_100_tx):
    config = loaded["config"]
    assert set(config["reduced"]) == {"hosts", "shards", "offered_rate"}
    for key in ("hosts", "shards"):
        assert config["reduced"][key] == omb_100_tx["reduced"][key]
    assert {"members", "commit_interval_ms", "abort_share", "transform", "assignor",
            "heartbeat_and_session", "offsets_partitions", "tx_partitions", "lane_capacity",
            "source_lines"} <= set(config["assumed"])
    assert config["toy"] == {"partitions": 12, "lane_capacity": 64}


@pytest.mark.parametrize("key", ["acks", "durability", "replication", "atomicity",
                                 "isolation", "served_on"])
def test_no_guarantee_is_weaker_than_omb_100_tx_s(loaded, omb_100_tx, key):
    assert loaded["config"]["guarantees"][key] == omb_100_tx["guarantees"][key]


def test_exactly_once_is_stated(loaded, omb_100_tx):
    g = loaded["config"]["guarantees"]
    assert set(g) == set(omb_100_tx["guarantees"]) | {"exactly_once"}
    for word in ("exactly once", "source order", "same number", "read_committed",
                 "committed offset", "partition's end", "aborted transaction",
                 "broker's word", "ctpreplay.py"):
        assert word in g["exactly_once"], word
    for word in ("producer id, epoch and base sequence", "continuous",
                 "nothing stored twice, nothing missing", "idempotent"):
        assert word in g["idempotence"], word


def test_the_warmers_are_omb_100_tx_s_and_the_group_s(loaded, omb_100_tx):
    config = loaded["config"]
    assert config["warm"] == omb_100_tx["warm"] + ["group.coordinator_and_offsets"]
    assert all(callable(run.resolve(w, "warmers")) for w in config["warm"])


def test_the_warmer_refuses_a_program_without_kip_447(monkeypatch, loaded):
    from benchmark.warmers import group
    from redpanda_tpu.kafka.protocol.tx_apis import TXN_OFFSET_COMMIT

    assert TXN_OFFSET_COMMIT.max_version >= 3
    monkeypatch.setattr(TXN_OFFSET_COMMIT, "max_version", 2)
    with pytest.raises(SystemExit, match="txn_offset_commit up to v2"):
        group.coordinator_and_offsets([], loaded["config"], loaded["traffic"], [])


def test_the_manifest_entry_matches_the_file(loaded):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    names = [c["name"] for c in manifest["configs"]]
    entry = manifest["configs"][names.index("omb_100_ctp")]
    assert names.index("omb_100_ctp") == names.index("omb_100_tx") + 1     # appended
    assert entry["source"] == loaded["config"]["source"] and len(entry["source"]) <= 200
    for word in ("transactions_test.py", "TransactionsTest", "KIP-447",
                 "openmessaging_benchmark_configs.py:123-135"):
        assert word in entry["source"], word
    assert sorted(entry["reduced"]) == sorted(loaded["config"]["reduced"])
    assert entry["file"] == "benchmark/configs/omb_100_ctp.json"
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index("omb_100_tx.txn_per_batch_0p8") + 1
    assert [w["name"] for w in manifest["workloads"] if w["config"] == "omb_100_ctp"] == [CELL]
    why = loaded["cell"]["why"]
    assert len(why) <= 200 and "knee" in why and str(_own_traffic()["batches_per_s"]) in why


# ------------------------------------------------------------ the traffic
def test_the_traffic_is_the_exactly_once_application_s(loaded):
    traffic, own = loaded["traffic"], _own_traffic()
    assert own["base"] == "omb_client" and "schedule" not in own
    assert traffic["generator"] == "ctp.run"
    assert callable(run.resolve(traffic["generator"], "generators"))
    assert traffic["templates"] == {"maker": "ctp.incompressible", "count": 8}
    assert (traffic["producers"], traffic["members"], traffic["consumers"],
            traffic["batch_records"]) == (16, 16, 8, 39)
    assert (traffic["abort_share"], traffic["isolation_level"], traffic["fetch_min_bytes"],
            traffic["fetch_max_wait_ms"], traffic["fetch_max_bytes"],
            traffic["transaction_timeout_ms"], traffic["commit_interval_ms"],
            traffic["heartbeat_interval_ms"], traffic["session_timeout_ms"],
            traffic["group"]) == (0.1, "read_committed", 1, 500, 131072, 60000, 100,
                                  3000, 45000, "bench-ctp")
    # the rest of the client is the base's, untouched
    base = run.load_json(run.HERE, "traffic", "omb_client.json")
    for key in ("linger_ms", "batch_bytes", "request_timeout_ms", "ack_sample_s", "drain_s"):
        assert traffic[key] == base[key], key
    for word in ("idempotent", "bench-ctp-<i>", "require_stable", "TxnOffsetCommit v3",
                 "commit_interval_ms", "out of band", "ctpreplay.py"):
        assert word in own["what"], word


def test_the_rate_is_four_fifths_of_the_knee_its_derived_names():
    own = _own_traffic()
    knee = own["derived"]["knee"]
    rate = own["batches_per_s"]
    assert isinstance(rate, int) and rate == int(0.8 * knee["batches_per_s"])
    # the knee's criterion (a 40 s window's median within 1.5 x the
    # unloaded one, 0.99 of the offered acknowledged inside it), on both
    # medians the cell reports, on two seeds: at the knee both windows hold, above it a window fails by a
    # median or by what it acknowledged inside the window
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
    assert "sweep_omb_100_ctp.json" in knee["files"]


def test_the_sweeps_lay_over_the_cell_s_traffic():
    tools = os.path.join(run.HERE, "tools")
    sweeps = sorted(f for f in os.listdir(tools) if f.startswith("sweep_omb_100_ctp"))
    assert len(sweeps) >= 3
    for name in sweeps:
        with open(os.path.join(tools, name)) as f:
            own = json.load(f)
        assert own["base"] == "../traffic/copy_0p8"
        assert set(own) <= {"base", "what", "schedule", "batches_per_s"}
        sweep = run.load_traffic(os.path.join(tools, name))
        assert sweep["generator"] == "ctp.run" and sweep["abort_share"] == 0.1
        if "batches_per_s" in own:   # a window at one rate, to its close
            assert own["schedule"] == [[40, own["batches_per_s"]]]
        else:
            assert all(secs == 8 and rate > 0 for secs, rate in own["schedule"])


# ------------------------------------------------------------ the metrics
@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metrics_are_data_over_readers(loaded, name):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    unit, layer, source, reader, params = NEW_METRICS[name]
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": layer, "moves": "e2e_p50_ms", "workloads": [CELL]}
    spec = run.load_json(run.HERE, "metrics", name + ".json")
    assert spec == {"name": name, "reader": reader, "params": params}
    by_name = {m["name"]: m for m in loaded["per_layer"]}
    assert callable(run.resolve(by_name[name]["reader"], "readers"))
    for cell in manifest["workloads"]:
        if cell["name"] != CELL:
            assert name not in {m["name"] for m in run.load_cell(cell["name"])["per_layer"]}


def test_the_four_are_appended_in_order_and_the_unlisted_are_read_too(loaded):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("tx_add_offsets_ms")
    assert names[at:at + 4] == ["tx_add_offsets_ms", "txn_offset_commit_ms", "group_marker_ms",
                                "rebalances_in_window"]
    assert at == names.index("idle_loop_asleep_pct") + 1   # appended after the loop's
    unlisted = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in loaded["per_layer"]} == unlisted | set(NEW_METRICS)
    assert {"crc_roofline", "tick_roofline", "compiles_in_window"} <= unlisted


def test_the_readers_say_nothing_of_a_program_without_the_spans_and_counters():
    """The parent's records: no `tx.add_offsets`, `group.txn_offset_commit`
    or `tx.group_marker` span, and no `group_coordinator` counters."""
    spans = [["tx.add_partitions", "wait", 0, 1000, 1, 0, 1, {"partitions": 1}],
             ["tx.markers", "wait", 0, 1000, 2, 0, 2, {}]]
    ctx = {"devplane": {"spans": spans, "spans_dropped": 0, "host": {},
                        "producer_sequences": {"checked": 3}}}
    for name, (_u, _l, _s, reader, params) in NEW_METRICS.items():
        assert run.resolve(reader, "readers")(ctx, params) is None, name


def test_rebalances_read_the_counter_where_the_program_has_it():
    from benchmark.readers import groups

    ctx = {"devplane": {"group_coordinator": {"rebalances": 0, "tx_offsets_staged": 9}}}
    assert groups.event_count(ctx, {"event": "rebalances"}) == 0.0
    ctx["devplane"]["group_coordinator"]["rebalances"] = 2
    assert groups.event_count(ctx, {"event": "rebalances"}) == 2.0
