"""What ISSUE 32 added to the benchmark, as far as the CPU can hold it:
the codec deployment's files as `benchmark.run.load_cell` finds them by
name, held to `omb_100`'s where they must be equal, and the readers of
the `produce.recompress` span on hand-made records."""

import json
import math
import os

import pytest

from benchmark import codecbytes, run
from benchmark.readers import codec, hostspans

CELL = "omb_100_lz4.half_random_0p8"
OLD_CELLS = ["rf3_1k.smoke_24", "single_1p.1p1kb_115", "omb_100.smoke_0p8"]
NEW_METRICS = {
    "recompress_ms": ("ms", "lower", "program_span", "produce_p50_ms"),
    "lz4_roofline": ("%", "higher", "device_trace", "produce_p50_ms"),
    "stored_bytes_per_sent_byte": ("B/B", "lower", "program_span", "e2e_p50_ms"),
}
WINDOW_S = 40


@pytest.fixture(scope="module")
def loaded():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def omb_100():
    return run.load_json(run.HERE, "configs", "omb_100.json")


def _params(name: str) -> dict:
    return run.load_json(run.HERE, "metrics", name + ".json")["params"]


# ------------------------------------------------------ the configuration
def test_the_cell_loads_with_its_config(loaded):
    config, cell = loaded["config"], loaded["cell"]
    # the keys benchmark/README.md lists under "Add a configuration"
    for key in ("source", "brokers", "layout", "topics", "record_bytes", "acks",
                "broker", "lane_capacity", "guarantees", "env", "device_kernels",
                "warm", "assumed", "reduced", "toy"):
        assert key in config, key
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "omb_100_lz4", "half_random_0p8", 1)
    assert len(cell["why"]) <= 200
    assert set(config["reduced"]) == {"hosts", "shards", "offered_rate", "idempotence"}
    assert {"compression", "random_share", "lane_capacity", "source_lines"} \
        <= set(config["assumed"])
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "produce_mb_s", "produce_p50_ms", "e2e_p50_ms", "setup_s"}


@pytest.mark.parametrize("key", ["brokers", "record_bytes", "acks", "broker",
                                 "lane_capacity", "env", "toy", "layout", "chips"])
def test_the_shape_is_omb_100_s(loaded, omb_100, key):
    assert loaded["config"][key] == omb_100[key]


def test_the_topic_is_omb_100_s_with_a_codec(loaded, omb_100):
    (topic,), (plain,) = loaded["config"]["topics"], omb_100["topics"]
    assert topic == {**plain, "configs": {"compression.type": "lz4"}}
    assert (topic["partitions"], topic["replication_factor"]) == (100, 3)


def test_kernels_and_warmers_are_omb_100_s_plus_the_codec_s(loaded, omb_100):
    config = loaded["config"]
    assert config["device_kernels"] == omb_100["device_kernels"] + ["fused.crc_lz4"]
    assert config["warm"] == omb_100["warm"] + ["codec.recompressed"]
    assert all(callable(run.resolve(w, "warmers")) for w in config["warm"])


@pytest.mark.parametrize("key", ["acks", "durability", "served_on"])
def test_no_guarantee_is_weaker_than_omb_100_s(loaded, omb_100, key):
    assert loaded["config"]["guarantees"][key] == omb_100["guarantees"][key]


def test_the_rewritten_batch_s_guarantees_are_stated(loaded, omb_100):
    g = loaded["config"]["guarantees"]
    assert set(g) == set(omb_100["guarantees"])
    for word in ("crc", "lz4", "lastOffsetDelta", "recordCount", "byte for byte",
                 "tolerance none", "offset the ack gave", "verify-on-read"):
        assert word in g["read_back"], word
    assert "every one of the 3 replicas" in g["replication"]
    assert "as the leader stored it" in g["replication"]


def test_the_manifest_entry_matches_the_file(loaded):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "omb_100_lz4")
    # appended after the three it found, not inserted; later PRs append after it
    assert [c["name"] for c in manifest["configs"]][:4] == [
        "rf3_1k", "single_1p", "omb_100", "omb_100_lz4"]
    assert entry["source"] == loaded["config"]["source"] and len(entry["source"]) <= 200
    assert "compression.type=lz4" in entry["source"] and "randomBytesRatio" in entry["source"]
    assert sorted(entry["reduced"]) == sorted(loaded["config"]["reduced"])
    assert entry["file"] == "benchmark/configs/omb_100_lz4.json"
    names = [w["name"] for w in manifest["workloads"]]
    assert names[:3] == OLD_CELLS and names[3] == CELL
    assert manifest["run_seconds"] == WINDOW_S


# ------------------------------------------------------------ the traffic
def _own_traffic() -> dict:
    with open(os.path.join(run.HERE, "traffic", "half_random_0p8.json")) as f:
        return json.load(f)


def test_the_traffic_is_data_over_omb_client(loaded):
    traffic, own = loaded["traffic"], _own_traffic()
    assert own["base"] == "omb_client"
    assert (traffic["producers"], traffic["consumers"], traffic["batch_records"]) \
        == (16, 8, 39)
    assert traffic["templates"] == {
        "maker": "compressible.random_share", "count": 8, "random_share": 0.5}
    # the client is the base's, untouched
    base = run.load_json(run.HERE, "traffic", "omb_client.json")
    for key in ("generator", "linger_ms", "batch_bytes", "max_in_flight",
                "max_request_bytes", "request_timeout_ms", "fetch_max_bytes",
                "fetch_max_wait_ms", "ack_sample_s", "drain_s"):
        assert traffic[key] == base[key], key
    assert "16,025" in json.dumps(own["reduced"])


def _last_due_s(rate: float, secs: float = WINDOW_S) -> float:
    """When the last batch of `secs` seconds at `rate` is due (batches
    are due every 1/rate seconds from the start, generators/open_loop)."""
    return (math.ceil(secs * rate - 1e-9) - 1) / rate


def test_the_rate_is_the_stated_share_of_the_knee_its_derived_names():
    own = _own_traffic()
    knee = own["derived"]["knee"]
    rate = own["batches_per_s"]
    assert knee["share"] in (0.8, 0.6)
    if knee["share"] == 0.6:  # only with the twelve readings that forced it
        assert len(knee["readings_at_0p8"]["produce_p50_ms"]) == 12
        assert len(knee["readings_at_0p8"]["e2e_p50_ms"]) == 12
    by_share = math.floor(knee["share"] * knee["batches_per_s"] * 10 + 1e-9) / 10
    assert rate == round(by_share - knee["lowered_tenths"] / 10, 1)
    # the last batch of a window is due 1.4 unloaded medians before the
    # close, so that `produce_mb_s` reads as offered; a tenth lower where not
    room_s = 1.4 * knee["unloaded_produce_p50_ms"] / 1e3
    assert WINDOW_S - _last_due_s(rate) >= room_s
    for lowered in range(knee["lowered_tenths"]):
        assert WINDOW_S - _last_due_s(round(by_share - lowered / 10, 1)) < room_s
    # ISSUE 32's cell offers to the close of the window: no schedule of its
    # own, 64 batches, the last due at 39.375 s
    assert "schedule" not in own and "schedule" not in own["derived"]
    assert math.ceil(WINDOW_S * rate - 1e-9) == 64 and _last_due_s(rate) == 39.375
    # the latency part of ISSUE 27's criterion: at the knee and under it
    # every window answers within 1.5 unloaded medians, on two seeds; above
    # it a window fails, by its median or by a queue's worth of batches
    limit = 1.5 * knee["unloaded_produce_p50_ms"]
    at_knee = str(knee["batches_per_s"])
    assert len(knee["windows"][at_knee]) == 2
    for r, read in knee["windows"].items():
        if float(r) <= knee["batches_per_s"]:
            assert all(ms <= limit for ms in read), r
    above = [r for r in knee["windows"] if float(r) > knee["batches_per_s"]]
    assert above and all(
        any(ms > limit for ms in knee["windows"][r])
        # by more than the two batches a retry at the very end costs
        or any(a < 0.95 * o for a, o in knee["acked_of_offered"][r]) for r in above)
    # the other part, 0.99 of the offered acknowledged inside the window,
    # does not tell rates apart and `derived` says so with the readings: a
    # window at the cell's own rate fails it too (a retried batch among
    # its last), so by the letter no rate holds on every seed
    at_cell = knee["acked_of_offered"][str(rate)]
    assert len(at_cell) >= 12 and all(o == 64 for _a, o in at_cell)
    assert any(a < 0.99 * o for a, o in at_cell)
    assert all(a >= 0.95 * o for a, o in at_cell)
    assert "by the letter" in own["derived"]["batches_per_s"]
    # the files that found it are named and are there
    said = json.dumps(own["derived"])
    for name in knee["files"]:
        assert name in said and os.path.exists(os.path.join(run.HERE, "tools", name))
    assert any(name == "sweep_omb_100_lz4.json" for name in knee["files"])


def test_the_sweeps_lay_over_the_cell_s_traffic():
    tools = os.path.join(run.HERE, "tools")
    sweeps = sorted(f for f in os.listdir(tools) if f.startswith("sweep_omb_100_lz4"))
    assert len(sweeps) >= 3
    for name in sweeps:
        with open(os.path.join(tools, name)) as f:
            own = json.load(f)
        assert own["base"] == "../traffic/half_random_0p8"
        assert set(own) <= {"base", "what", "schedule", "batches_per_s"}
        sweep = run.load_traffic(os.path.join(tools, name))
        assert sweep["producers"] == 16 and sweep["batch_records"] == 39
        assert sweep["templates"]["random_share"] == 0.5
        steps = own["schedule"]
        assert all(secs > 0 and 0 < rate <= 3.0 for secs, rate in steps)
        if "batches_per_s" in own:   # a window at one rate, to its close
            assert steps == [[WINDOW_S, own["batches_per_s"]]]
    staircase = run.load_traffic(os.path.join(tools, "sweep_omb_100_lz4.json"))
    rates = [r for _s, r in staircase["schedule"]]
    assert rates == sorted(rates) and rates[0] == 0.5 and rates[-1] == 3.0
    assert all(0.1 <= round(b - a, 1) <= 0.2 for a, b in zip(rates[1:], rates[2:]))


# ------------------------------------------------------------ the metrics
@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metrics_are_read_in_this_cell_and_in_no_other(loaded, name):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    unit, better, source, moves = NEW_METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": "byte kernels", "moves": moves, "workloads": [CELL]}
    # the three are adjacent and in order, wherever later PRs append theirs
    at = [m["name"] for m in manifest["per_layer"]].index("recompress_ms")
    assert [m["name"] for m in manifest["per_layer"][at:at + 3]] == [
        "recompress_ms", "lz4_roofline", "stored_bytes_per_sent_byte"]
    by_name = {m["name"]: m for m in loaded["per_layer"]}
    assert callable(run.resolve(by_name[name]["reader"], "readers"))
    for old in OLD_CELLS:
        assert name not in {m["name"] for m in run.load_cell(old)["per_layer"]}


def test_the_cell_reads_every_metric_without_a_list_and_no_listed_old_one(loaded):
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    unlisted = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    got = {m["name"] for m in loaded["per_layer"]}
    listed = ("follower_rtt_ms", "folds_per_acked_batch", "produce_open_mean")
    assert got - set(listed) == unlisted | set(NEW_METRICS)
    # the cell runs the layers of the three older listed metrics and reads
    # each from the day a `benchmark` issue lists it there (PERF.md section 7)
    for name in listed:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert (name in got) == (CELL in entry["workloads"])


def _span(name, start, dur, sid=0, parent=0, **tags):
    return [name, "run", start, dur, sid, parent, 1, tags or None]


def _recompress(start, dur, bytes_in=40287, bytes_out=21024, path="device", **more):
    return _span("produce.recompress", start, dur, codec=3, path=path,
                 bytes_in=bytes_in, bytes_out=bytes_out, **more)


def _ctx(spans, trace=None, dropped=0):
    return {"devplane": {"spans": spans, "spans_dropped": dropped}, "trace": trace,
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_stored_bytes_per_sent_byte_is_all_the_stored_over_all_the_sent():
    spans = [_recompress(0, 10, 40000, 20000), _recompress(20, 10, 20000, 16000),
             _recompress(40, 10, 1000, 1000, path="host"),
             _span("produce.recompress", 60, 10, codec=3),   # no byte tags
             _span("produce.dispatch", 0, 100)]
    got = codec.stored_bytes_per_sent_byte(_ctx(spans), _params("stored_bytes_per_sent_byte"))
    assert got == 37000 / 61000


def test_recompress_bytes_is_one_read_and_one_write_of_the_work():
    assert codecbytes.recompress_bytes(40287, 21024) == 61311
    assert "read once" in codecbytes.recompress_bytes.__doc__


MS = 1_000_000
OFFSET = 7_000_000_000_000   # the span clock ahead of the trace's


def _traced():
    """Three seconds of trace holding four executions of the fused
    program (400 ms each) and ticks between them, with the spans of the
    same calls on a clock `OFFSET` ahead: a recompression is 2 ms of
    staging, the dispatch, 1 ms of framing. The first execution began
    before the trace did, and the last recompression ends after it."""
    mods, ops, spans = [], [], []
    at = [100 * MS, 800 * MS, 1500 * MS, 2700 * MS]
    for i, t in enumerate(at):
        mods.append(["jit__fused(77)", t, 400.0 * MS])
        ops.append(["%fusion.10", t, 400.0 * MS])
        spans.append(_span("device.dispatch", t - MS / 2 + OFFSET, 401 * MS,
                           kernel="fused.crc_lz4"))
        spans.append(_recompress(t - 2 * MS + OFFSET, 404 * MS, 40000 + i, 21000))
        tick = t + 450 * MS
        mods.append(["jit_heartbeat_tick(3)", tick, 0.1 * MS])
        spans.append(_span("device.dispatch", tick - MS / 2 + OFFSET, MS,
                           kernel="quorum.heartbeat_tick"))
    mods.append(["jit__fused_snappy(78)", 2300 * MS, 50.0 * MS])   # another program
    spans.append(_recompress(9000 * MS + OFFSET, 404 * MS))        # after the trace
    spans.append(_recompress(600 * MS + OFFSET, 3 * MS, path="host"))
    trace = {"devices": {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}},
             "span_ns": [99 * MS, 3000 * MS]}
    return trace, spans


def test_lz4_roofline_counts_the_bytes_of_the_work_inside_the_trace():
    trace, spans = _traced()
    got = codec.lz4_roofline(_ctx(spans, trace), _params("lz4_roofline"))
    # the first span began before the trace and the last ends after it:
    # the two in the middle count, bytes and device time alike
    moved = (40001 + 21000) + (40002 + 21000)
    assert got == pytest.approx(100.0 * (moved / 819e9) / 0.8, rel=1e-9)
    assert 0 < got < 100


def test_lz4_roofline_does_not_follow_the_program_s_shapes_or_executions():
    """A rewrite that splits the program in two executions a batch is
    read against the same bytes: the share follows the device time."""
    trace, spans = _traced()
    whole = codec.lz4_roofline(_ctx(spans, trace), _params("lz4_roofline"))
    mods = trace["devices"]["/device:TPU:0"]["XLA Modules"]
    split = []
    for name, s, d in mods:
        if name.startswith("jit__fused("):
            split += [[name, s, d / 4], [name, s + d / 2, d / 4]]
        else:
            split.append([name, s, d])
    trace["devices"]["/device:TPU:0"]["XLA Modules"] = split
    halved = codec.lz4_roofline(_ctx(spans, trace), _params("lz4_roofline"))
    assert halved == pytest.approx(2 * whole, rel=1e-9)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
@pytest.mark.parametrize("case", ["no_spans", "the_parent_s_spans", "host_only",
                                  "no_devplane", "spans_dropped", "no_trace",
                                  "no_clock"])
def test_nothing_to_read_is_none_never_zero(name, case):
    trace, spans = _traced()
    others = [s for s in spans if s[hostspans.NAME] != "produce.recompress"]
    ctx = {
        "no_spans": _ctx([], trace),
        "the_parent_s_spans": _ctx(others, trace),
        "host_only": _ctx(others + [_recompress(600 * MS + OFFSET, 3 * MS, path="host")], trace),
        "no_devplane": {"devplane": None, "trace": trace},
        "spans_dropped": _ctx(spans, trace, dropped=1),
        "no_trace": _ctx(spans, None),
        "no_clock": _ctx([s for s in spans if s[hostspans.NAME] != "device.dispatch"], trace),
    }[case]
    spec = run.load_json(run.HERE, "metrics", name + ".json")
    reader = run.resolve(spec["reader"], "readers")
    got = reader(ctx, spec["params"])
    # a run that kept every raw record reads the span metrics without a
    # trace or a clock; the roofline share needs both, and device work
    reads = {
        "recompress_ms": {"host_only", "no_trace", "no_clock"},
        "stored_bytes_per_sent_byte": {"host_only", "no_trace", "no_clock"},
        "lz4_roofline": set(),
    }[name]
    assert (got is not None and got > 0) if case in reads else got is None
