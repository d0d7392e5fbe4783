"""What ISSUE 27 added to the benchmark, as far as the CPU can hold it:
the readers of the span tags on hand-made records, and the new cell's
files as `benchmark.run.load_cell` finds them by name."""

import json
import os
import re

import pytest

from benchmark import run
from benchmark.readers import spantags
from benchmark.reference import make_templates

CELL = "omb_100.smoke_0p8"
CELLS = ["rf3_1k.smoke_24", "single_1p.1p1kb_115", CELL]
ROWS = {"span": "tick.upload", "tag": "rows"}
OPEN = {"span": "kafka.produce", "tag": "open"}


def _span(name: str, tags: dict | None) -> list:
    return [name, "run", 0, 1000, 1, 0, 1, tags]


def _ctx(spans: list, acked_batches: int = 4, dropped: int = 0) -> dict:
    templates = make_templates(5, 2, 3, 64)
    return {
        "devplane": {"spans": spans, "spans_dropped": dropped},
        "templates": templates,
        "acked_payload_bytes": acked_batches * templates[0].payload_bytes,
    }


FOLDS = [
    _span("tick.upload", {"seed": 0, "rows": 1, "replies": 2, "bucket": 8}),
    _span("tick.upload", {"seed": 0, "rows": 5, "replies": 9, "bucket": 16}),
    _span("tick.upload", {"seed": 0, "rows": 2, "replies": 2, "bucket": 8}),
    _span("tick.upload", {"seed": 0, "rows": 0, "replies": 0, "bucket": 8}),
    _span("tick.upload", {"seed": 1}),       # the parent's record: no `rows`
    _span("tick.readback", None),
    _span("kafka.produce", {"path": "native", "open": 3}),
]


def test_tag_mean_is_the_mean_of_the_tag():
    assert spantags.tag_mean(_ctx(FOLDS), OPEN) == 3.0
    assert spantags.tag_mean(_ctx(FOLDS), ROWS) == 2.0
    # one request in four found another open: the mean says so, a median reads 0
    arrivals = [_span("kafka.produce", {"open": n}) for n in (0, 0, 1, 0)]
    assert spantags.tag_mean(_ctx(arrivals), OPEN) == 0.25


def test_spans_per_acked_batch_counts_the_tagged_spans_alone():
    # four of the five `tick.upload` records carry `rows`
    assert spantags.spans_per_acked_batch(_ctx(FOLDS, acked_batches=4), ROWS) == 1.0
    assert spantags.spans_per_acked_batch(_ctx(FOLDS, acked_batches=8), ROWS) == 0.5


@pytest.mark.parametrize(
    "ctx",
    [
        _ctx([_span("tick.upload", {"seed": 0}), _span("tick.upload", None)]),
        _ctx([]),
        {"devplane": {}, "templates": make_templates(5, 2, 3, 64),
         "acked_payload_bytes": 100},
        {"devplane": None},
        _ctx(FOLDS, dropped=1),
    ],
    ids=["no_tag", "no_spans", "no_raw_key", "no_devplane", "spans_dropped"],
)
@pytest.mark.parametrize("reader", [spantags.tag_mean, spantags.spans_per_acked_batch])
def test_nothing_to_read_is_none_never_zero(reader, ctx):
    assert reader(ctx, ROWS) is None


def test_no_acknowledged_batch_is_none():
    assert spantags.spans_per_acked_batch(_ctx(FOLDS, acked_batches=0), ROWS) is None


@pytest.fixture(scope="module")
def loaded():
    return run.load_cell(CELL)


def test_the_cell_loads_with_its_config(loaded):
    config = loaded["config"]
    # the keys benchmark/README.md lists under "Add a configuration"
    for key in ("source", "brokers", "layout", "topics", "record_bytes", "acks",
                "broker", "lane_capacity", "guarantees", "env", "device_kernels",
                "warm", "assumed", "reduced", "toy"):
        assert key in config, key
    cell = loaded["cell"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("omb_100", "smoke_0p8", 1)
    assert len(cell["why"]) <= 200
    (topic,) = config["topics"]
    assert (topic["partitions"], topic["replication_factor"]) == (100, 3)
    assert (config["brokers"], config["record_bytes"], config["acks"]) == (3, 1024, -1)
    assert config["lane_capacity"] == 128 > topic["partitions"] + 1
    assert set(config["reduced"]) == {"hosts", "shards", "offered_rate", "idempotence"}
    assert {"compression", "lane_capacity", "source_lines"} <= set(config["assumed"])
    assert config["toy"] == {"partitions": 12, "lane_capacity": 64}


def test_no_guarantee_is_weaker_than_rf3_1k_s(loaded):
    rf3 = run.load_json(run.HERE, "configs", "rf3_1k.json")
    for key in ("guarantees", "env", "device_kernels", "warm", "broker"):
        assert loaded["config"][key] == rf3[key], key


def test_the_manifest_entry_matches_the_file(loaded):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "omb_100")
    assert entry["source"] == loaded["config"]["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(loaded["config"]["reduced"])
    assert entry["file"] == "benchmark/configs/omb_100.json"


def test_the_traffic_is_data_over_omb_client(loaded):
    traffic = loaded["traffic"]
    with open(os.path.join(run.HERE, "traffic", "smoke_0p8.json")) as f:
        own = json.load(f)
    assert own["base"] == "omb_client" and "schedule" not in own
    rate = own["batches_per_s"]
    assert isinstance(rate, int) and not isinstance(rate, bool) and rate > 0
    assert (traffic["producers"], traffic["consumers"], traffic["batch_records"]) \
        == (16, 8, 39)
    # the client is the base's, untouched
    base = run.load_json(run.HERE, "traffic", "omb_client.json")
    for key in ("generator", "linger_ms", "max_in_flight", "fetch_max_bytes",
                "templates", "drain_s"):
        assert traffic[key] == base[key], key
    assert (traffic["linger_ms"], traffic["max_in_flight"],
            traffic["fetch_max_bytes"], traffic["drain_s"]) == (1, 5, 131072, 60)
    # the knee, the sweeps that found it and the source's rate are in the file
    said = json.dumps(own["derived"]) + json.dumps(own["reduced"])
    assert "16,025" in said and "sweep_omb_100" in said
    knee = int(re.search(r"knee of (\d+)", said).group(1))
    assert rate == knee * 8 // 10


def test_the_sweeps_lay_over_the_cell_s_traffic():
    tools = os.path.join(run.HERE, "tools")
    # `sweep_omb_100_lz4*` are the codec deployment's (test_benchmark_omb_100_lz4.py),
    # `sweep_omb_100_tx*` the exactly-once one's (test_benchmark_omb_100_tx.py),
    # `sweep_omb_100_ctp*` the pipeline's (test_benchmark_omb_100_ctp.py)
    sweeps = sorted(f for f in os.listdir(tools) if f.startswith("sweep_omb_100")
                    and not f.startswith(("sweep_omb_100_lz4", "sweep_omb_100_tx",
                                          "sweep_omb_100_ctp")))
    assert len(sweeps) >= 2
    for name in sweeps:
        sweep = run.load_traffic(os.path.join(tools, name))
        # straight over the cell's traffic, or over the knee's window (another rate)
        assert sweep["base"] in ("../traffic/smoke_0p8", "sweep_omb_100_window")
        assert sweep["producers"] == 16 and sweep["batch_records"] == 39
        # a staircase of 8 s steps, or one rate for a window of its own
        steps = sweep.get("schedule", [[8, sweep["batches_per_s"]]])
        assert all(secs == 8 and rate > 0 for secs, rate in steps)


@pytest.mark.parametrize("cell", CELLS)
def test_both_new_metrics_are_read_in_every_cell(cell):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in run.load_cell(cell)["per_layer"]}
    for name, layer, params in (
        ("folds_per_acked_batch", "tick frame", ROWS),
        ("produce_open_mean", "Kafka front end", OPEN),
    ):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        # at least these cells: a later `benchmark` PR may list more
        assert set(CELLS) <= set(entry["workloads"])
        assert (entry["source"], entry["moves"], entry["layer"]) == (
            "program_span", "produce_p50_ms", layer)
        assert callable(run.resolve(by_name[name]["reader"], "readers"))
        assert by_name[name]["params"] == params
    # `follower_rtt_ms` is read on rf3_1k at the least
    rtt = next(m for m in manifest["per_layer"] if m["name"] == "follower_rtt_ms")
    assert "rf3_1k.smoke_24" in rtt["workloads"]
    assert ("follower_rtt_ms" in by_name) == (cell in rtt["workloads"])
