"""What ISSUE 35 added to the benchmark, as far as the CPU can hold it:
the exactly-once deployment's files as `benchmark.run.load_cell` finds
them by name, held to `omb_100`'s where they must be equal; and, by
import, every case of benchmark/tests/test_omb_100_tx.py: the
reference's template, filter and replay on hand-made batches, the system
against the reference at toy size, a --cpu-dry-run of the cell and the
three planted faults."""

import json
import os

import pytest

from benchmark import run
from benchmark.tests import test_omb_100_tx

for _name, _case in vars(test_omb_100_tx).items():
    if _name.startswith("test_") or _name in ("tpl", "toy"):   # its fixtures too
        assert _name not in globals(), _name
        globals()[_name] = _case

CELL = "omb_100_tx.txn_per_batch_0p8"
NEW_METRICS = {
    "tx_add_partitions_ms": ("ms", "transactions", "produce_p50_ms",
                             "hostspans.span_p50_ms", {"span": "tx.add_partitions"}),
    "tx_end_ms": ("ms", "transactions", "e2e_p50_ms",
                  "hostspans.span_p50_ms", {"span": "tx.end"}),
    "tx_markers_ms": ("ms", "transactions", "e2e_p50_ms",
                      "hostspans.span_p50_ms", {"span": "tx.markers"}),
    "lso_wait_ms": ("ms", "Kafka front end", "e2e_p50_ms",
                    "hostspans.span_p50_ms", {"span": "fetch.lso_wait"}),
    "fetch_reads_per_fetch": ("1/fetch", "Kafka front end", "e2e_p50_ms",
                              "spantags.tag_mean", {"span": "kafka.fetch", "tag": "reads"}),
    "leader_appends_per_acked_batch": (
        "1/batch", "replicate path", "produce_p50_ms",
        "spantags.spans_per_acked_batch", {"span": "raft.append", "tag": "batches"}),
}


@pytest.fixture(scope="module")
def loaded():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def omb_100():
    return run.load_json(run.HERE, "configs", "omb_100.json")


def _own_traffic() -> dict:
    with open(os.path.join(run.HERE, "traffic", "txn_per_batch_0p8.json")) as f:
        return json.load(f)


# ------------------------------------------------------ the configuration
def test_the_cell_loads_with_its_config(loaded):
    config, cell = loaded["config"], loaded["cell"]
    for key in ("source", "brokers", "layout", "topics", "record_bytes", "acks",
                "broker", "lane_capacity", "guarantees", "env", "device_kernels",
                "warm", "assumed", "reduced", "toy", "deployment", "coordinator_topic"):
        assert key in config, key
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "omb_100_tx", "txn_per_batch_0p8", 1)
    assert len(cell["why"]) <= 200 and "aborted" in cell["why"]
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "produce_mb_s", "produce_p50_ms", "e2e_p50_ms", "setup_s"}


@pytest.mark.parametrize("key", ["brokers", "topics", "record_bytes", "acks", "broker",
                                 "lane_capacity", "env", "device_kernels", "toy",
                                 "layout", "chips"])
def test_the_shape_is_omb_100_s(loaded, omb_100, key):
    assert loaded["config"][key] == omb_100[key]


def test_idempotence_is_not_cut(loaded, omb_100):
    config = loaded["config"]
    assert set(config["reduced"]) == set(omb_100["reduced"]) - {"idempotence"}
    for key in config["reduced"]:
        assert config["reduced"][key] == omb_100["reduced"][key]
    assert {"tx_partitions", "abort_share", "transaction", "transaction_timeout_ms",
            "lane_capacity", "source_lines"} <= set(config["assumed"])


@pytest.mark.parametrize("key", ["acks", "durability", "replication", "served_on"])
def test_no_guarantee_is_weaker_than_omb_100_s(loaded, omb_100, key):
    assert loaded["config"]["guarantees"][key] == omb_100["guarantees"][key]


def test_the_transaction_s_guarantees_are_stated(loaded, omb_100):
    g = loaded["config"]["guarantees"]
    assert set(g) == set(omb_100["guarantees"]) | {"idempotence", "atomicity", "isolation"}
    for word in ("read_committed", "offset the ack gave", "template", "verify-on-read",
                 "tolerance none"):
        assert word in g["read_back"], word
    for word in ("producer id, epoch and base sequence", "continuous",
                 "nothing stored twice, nothing missing"):
        assert word in g["idempotence"], word
    for word in ("EndTxn(commit)", "exactly once", "offset order", "EndTxn(abort)", "replay"):
        assert word in g["atomicity"], word
    assert "at or past the first offset of an open transaction" in g["isolation"]


def test_the_coordinator_topic_is_stated_and_fits_the_lanes(loaded):
    config = loaded["config"]
    topic = config["coordinator_topic"]
    assert (topic["namespace"], topic["name"], topic["partitions"],
            topic["replication_factor"]) == ("kafka_internal", "tx", 4, 3)
    assert "set-up" in topic["what"] and "fsync before ack" in topic["what"]
    groups = sum(t["partitions"] for t in config["topics"]) + 1 + topic["partitions"]
    assert groups == 105 <= config["lane_capacity"]
    assert "105" in config["assumed"]["lane_capacity"]


def test_the_warmers_are_omb_100_s_and_the_transaction_path_s(loaded, omb_100):
    config = loaded["config"]
    assert config["warm"] == omb_100["warm"] + ["tx.coordinator_and_markers"]
    assert all(callable(run.resolve(w, "warmers")) for w in config["warm"])


def test_the_manifest_entry_matches_the_file(loaded):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    names = [c["name"] for c in manifest["configs"]]
    entry = manifest["configs"][names.index("omb_100_tx")]
    assert names.index("omb_100_tx") > names.index("omb_100_lz4")    # appended
    assert entry["source"] == loaded["config"]["source"] and len(entry["source"]) <= 200
    for word in ("transactions_test.py", "openmessaging_benchmark_configs.py:123-135,237-248",
                 "KIP-98"):
        assert word in entry["source"], word
    assert sorted(entry["reduced"]) == sorted(loaded["config"]["reduced"])
    assert "idempotence" not in entry["reduced"]
    assert entry["file"] == "benchmark/configs/omb_100_tx.json"
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("omb_100_lz4.half_random_0p8")
    assert [w["name"] for w in manifest["workloads"] if w["config"] == "omb_100_tx"] == [CELL]


# ------------------------------------------------------------ the traffic
def test_the_traffic_is_the_one_issue_35_names(loaded):
    traffic, own = loaded["traffic"], _own_traffic()
    assert own["base"] == "omb_client" and "schedule" not in own
    assert traffic["generator"] == "transactional.run"
    assert callable(run.resolve(traffic["generator"], "generators"))
    assert traffic["templates"] == {"maker": "transactional.incompressible", "count": 8}
    assert (traffic["producers"], traffic["consumers"], traffic["batch_records"]) == (16, 8, 39)
    assert (traffic["abort_share"], traffic["isolation_level"], traffic["fetch_min_bytes"],
            traffic["fetch_max_wait_ms"], traffic["fetch_max_bytes"],
            traffic["transaction_timeout_ms"]) == (0.1, "read_committed", 1, 500, 131072, 60000)
    # the rest of the client is the base's, untouched
    base = run.load_json(run.HERE, "traffic", "omb_client.json")
    for key in ("linger_ms", "batch_bytes", "request_timeout_ms", "ack_sample_s", "drain_s"):
        assert traffic[key] == base[key], key
    for word in ("transactional.id", "bench-tx-", "one transaction open at a time",
                 "same sequence", "out of band"):
        assert word in own["what"], word


def test_the_rate_is_four_fifths_of_the_knee_its_derived_names():
    own = _own_traffic()
    knee = own["derived"]["knee"]
    rate = own["batches_per_s"]
    assert isinstance(rate, int) and rate == int(0.8 * knee["batches_per_s"])
    limit = 1.5 * knee["unloaded_produce_p50_ms"]
    # ISSUE 27's criterion, unmodified, on two seeds: at the knee both
    # windows hold, above it a window fails by its median or by what it
    # acknowledged inside the window
    at_knee = str(knee["batches_per_s"])
    assert len(knee["windows"][at_knee]) == 2
    for r, read in knee["windows"].items():
        held = all(ms <= limit for ms in read) and all(
            a >= 0.99 * o for a, o in knee["acked_of_offered"][r])
        assert held == (float(r) <= knee["batches_per_s"]), r
    assert any(float(r) > knee["batches_per_s"] for r in knee["windows"])
    said = json.dumps(own["derived"])
    for name in knee["files"]:
        assert name in said and os.path.exists(os.path.join(run.HERE, "tools", name))
    assert "sweep_omb_100_tx.json" in knee["files"]
    assert "generator" in own["derived"] and "half a core" in own["derived"]["generator"]


def test_the_sweeps_lay_over_the_cell_s_traffic():
    tools = os.path.join(run.HERE, "tools")
    sweeps = sorted(f for f in os.listdir(tools) if f.startswith("sweep_omb_100_tx"))
    assert len(sweeps) >= 4
    for name in sweeps:
        with open(os.path.join(tools, name)) as f:
            own = json.load(f)
        assert own["base"] == "../traffic/txn_per_batch_0p8"
        assert set(own) <= {"base", "what", "schedule", "batches_per_s"}
        sweep = run.load_traffic(os.path.join(tools, name))
        assert sweep["generator"] == "transactional.run" and sweep["abort_share"] == 0.1
        if "batches_per_s" in own:   # a window at one rate, to its close
            assert own["schedule"] == [[40, own["batches_per_s"]]]
        else:
            assert all(secs == 8 and rate > 0 for secs, rate in own["schedule"])


# ------------------------------------------------------------ the metrics
@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metrics_are_data_over_readers_that_were_there(loaded, name):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    unit, layer, moves, reader, params = NEW_METRICS[name]
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer, "moves": moves,
                     "workloads": [CELL]}
    spec = run.load_json(run.HERE, "metrics", name + ".json")
    assert spec == {"name": name, "reader": reader, "params": params}
    by_name = {m["name"]: m for m in loaded["per_layer"]}
    assert callable(run.resolve(by_name[name]["reader"], "readers"))
    for cell in manifest["workloads"]:
        if cell["name"] != CELL:
            assert name not in {m["name"] for m in run.load_cell(cell["name"])["per_layer"]}


def test_the_six_are_appended_in_issue_35_s_order_and_the_unlisted_are_read_too(loaded):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("tx_add_partitions_ms")
    assert names[at:at + 6] == ["tx_add_partitions_ms", "tx_end_ms", "tx_markers_ms",
                                "lso_wait_ms", "fetch_reads_per_fetch",
                                "leader_appends_per_acked_batch"]
    assert at > names.index("stored_bytes_per_sent_byte")
    unlisted = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in loaded["per_layer"]} == unlisted | set(NEW_METRICS)
    assert {"crc_roofline", "tick_roofline", "compiles_in_window"} <= unlisted


def test_the_readers_say_nothing_of_a_program_without_the_spans():
    """The parent's records: `raft.append` without `batches`,
    `kafka.fetch` without `reads`, no `tx.*` span at all."""
    from benchmark.reference import make_templates

    spans = [["raft.append", "run", 0, 1000, 1, 0, 1, {"items": 1}],
             ["kafka.fetch", "wait", 0, 1000, 2, 0, 2, {"path": "python"}],
             ["kafka.produce", "wait", 0, 1000, 3, 0, 3, {"open": 0}]]
    templates = make_templates(5, 2, 3, 64)
    ctx = {"devplane": {"spans": spans, "spans_dropped": 0, "host": {}},
           "templates": templates, "acked_payload_bytes": 4 * templates[0].payload_bytes}
    for name, (_u, _l, _m, reader, params) in NEW_METRICS.items():
        assert run.resolve(reader, "readers")(ctx, params) is None, name
