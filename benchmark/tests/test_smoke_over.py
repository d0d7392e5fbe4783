"""What ISSUE 34 added to the benchmark, as far as the CPU can hold it:
the cell over saturation as `benchmark.run.load_cell` finds it by name
in its queued manifest (benchmark/queued/, laid over BENCHMARK.json: the
entries that make it a cell, which PR 34 measured and could not bring),
end-to-end metrics that name their cells, `sustained_mb_s` on hand-made
rows and the loop-share readers on hand-made aggregates. In-process and
without fixtures, so that tier-1 collects the cases by import
(tests/test_benchmark_reference.py through test_reference.py); the cell's
rehearsal, timed and traced, is test_dry_run.py's. Nothing here pins
where an entry stands in a list or that a list holds one name: the PR
that moves the entries into BENCHMARK.json changes `_load` and nothing
else."""

import json
import os

import pytest

from benchmark import run
from benchmark.readers import loopshare
from benchmark.reference import make_templates
from benchmark.run import reduce_records

CELL = "omb_100.smoke_over"
QUEUED = os.path.join(run.HERE, "queued", CELL + ".json")
ACCEPTED = ["rf3_1k.smoke_24", "single_1p.1p1kb_115", "omb_100.smoke_0p8",
            "omb_100_lz4.half_random_0p8"]
LATENCY = {"produce_mb_s", "produce_p50_ms", "e2e_p50_ms", "setup_s"}
SHARES = ["frontend_run_ms_per_batch", "replicate_run_ms_per_batch",
          "fold_run_ms_per_batch", "fetch_run_ms_per_batch",
          "heartbeat_run_ms_per_batch"]
NEW_METRICS = ["loop_run_ms_per_batch", *SHARES, "loop_unspanned_pct"]
#: the older readings the cell takes under a name of its own, since each
#: moves the one end-to-end metric the cell reports
SPLIT = ["device_idle_pct", "idle_attributed_pct", "h2d_bytes_per_acked_byte",
         "crc_roofline", "tick_roofline", "fetch_verify_ms", "loop_lag_p99_ms",
         "elections_in_window", "compiles_in_window"]


def _manifest() -> dict:
    """BENCHMARK.json as it stands once the cell is in it."""
    return run.laid_over(run.load_json(run.ROOT, "BENCHMARK.json"),
                         run.load_json(QUEUED))


def _load(cell: str) -> dict:
    return run.load_cell(cell, manifest_file=QUEUED)


def _own_traffic() -> dict:
    with open(os.path.join(run.HERE, "traffic", "smoke_over.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- the cell
def test_smoke_over_loads_with_omb_100_s_configuration_unchanged():
    loaded = _load(CELL)
    cell = loaded["cell"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("omb_100", "smoke_over", 1)
    assert 1 <= len(cell["why"]) <= 200
    assert loaded["config"] == _load("omb_100.smoke_0p8")["config"]
    assert [w["name"] for w in _manifest()["workloads"]].count(CELL) == 1


def test_smoke_over_reports_the_completed_rate_and_no_median():
    loaded = _load(CELL)
    assert {m["name"] for m in loaded["end_to_end"]} == {"sustained_mb_s", "setup_s"}
    entry = next(m for m in _manifest()["end_to_end"] if m["name"] == "sustained_mb_s")
    assert CELL in entry["workloads"] and entry["better"] == "higher"
    assert entry["unit"] == "MB/s" and entry["source"] == "host_clock"
    assert 0.03 <= entry["bound"] <= 0.10


@pytest.mark.parametrize("cell", ACCEPTED)
def test_an_accepted_cell_reports_the_four_it_did(cell):
    # as BENCHMARK.json has it today, and with the queued entries in it
    for loaded in (run.load_cell(cell), _load(cell)):
        assert {m["name"] for m in loaded["end_to_end"]} == LATENCY
        read = {m["name"] for m in loaded["per_layer"]}
        assert not read & {*NEW_METRICS, *(n + ".over" for n in SPLIT)}
        assert read == {m["name"] for m in run.load_cell(cell)["per_layer"]}
    for m in _manifest()["end_to_end"]:
        if m["name"] in LATENCY - {"setup_s"}:
            assert set(ACCEPTED) <= set(m["workloads"]) and CELL not in m["workloads"]
        if m["name"] == "setup_s":
            assert "workloads" not in m


def test_smoke_over_s_traffic_is_data_over_omb_client():
    traffic, own = _load(CELL)["traffic"], _own_traffic()
    assert own["base"] == "omb_client" and "schedule" not in own
    assert set(own) == {"base", "source", "producers", "consumers", "batch_records",
                        "batches_per_s", "derived", "reduced"}
    smoke = _load("omb_100.smoke_0p8")["traffic"]
    for key in smoke:   # smoke_0p8's client, producers, consumers and batch
        if key not in ("batches_per_s", "derived", "reduced"):
            assert traffic[key] == smoke[key], key
    assert (traffic["producers"], traffic["consumers"], traffic["batch_records"],
            traffic["linger_ms"], traffic["max_in_flight"], traffic["drain_s"]) \
        == (16, 8, 39, 1, 5, 60)
    assert "16,025" in json.dumps(own["reduced"])


def test_the_rate_is_the_stated_multiple_of_the_completed_rate_s():
    own = _own_traffic()
    found = own["derived"]["S"]
    rate = own["batches_per_s"]
    assert isinstance(rate, int) and not isinstance(rate, bool)
    assert found["multiple"] in (1.5, 1.25)
    assert rate == round(found["multiple"] * found["batches_per_s"])
    if found["multiple"] == 1.25:      # the one fallback, with its reason
        assert found["what_broke_at_1p5"]
    # the windows, two seeds a rate, as [offered, acknowledged inside the
    # window, share of the due]: ISSUE 34's eight came back as offered, so
    # eight more were run
    windows = found["windows"]
    rates = [150, 200, 300, 400, 600, 800, 1200, 1600]
    assert sorted(w[0] for w in windows) == sorted(rates * 2)
    assert all(share >= 0.9 for offered, _n, share in windows if offered <= 400)
    assert all(abs(n / (offered * 40) - share) < 1e-3 for offered, n, share in windows)
    assert found["files"] == [f"over_omb_100_{r}.json" for r in rates]
    over = sorted(w[1] / 40 for w in windows if w[2] < 0.9)
    assert over, "no window was over saturation"
    mid = len(over) // 2
    median = over[mid] if len(over) % 2 else (over[mid - 1] + over[mid]) / 2
    assert found["batches_per_s"] == pytest.approx(median, abs=0.5)
    for name in found["files"]:
        assert os.path.exists(os.path.join(run.HERE, "tools", name)), name


@pytest.mark.parametrize("rate", [150, 200, 300, 400, 600, 800, 1200, 1600])
def test_an_overlay_lays_one_rate_over_the_cell_s_traffic(rate):
    path = os.path.join(run.HERE, "tools", f"over_omb_100_{rate}.json")
    with open(path) as f:
        own = json.load(f)
    assert set(own) == {"base", "what", "batches_per_s"}
    assert own["base"] == "../traffic/smoke_over" and own["batches_per_s"] == rate
    laid = run.load_traffic(path)
    cell = _load(CELL)["traffic"]
    apart = ("base", "what", "batches_per_s")
    assert {k: v for k, v in laid.items() if k not in apart} \
        == {k: v for k, v in cell.items() if k not in apart}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_loop_share_is_read_in_this_cell(name):
    entry = next(m for m in _manifest()["per_layer"] if m["name"] == name)
    assert (entry["source"], entry["moves"], entry["better"]) == (
        "program_span", "sustained_mb_s", "lower")
    assert CELL in entry["workloads"] and not set(ACCEPTED) & set(entry["workloads"])
    assert entry["unit"] == ("%" if name == "loop_unspanned_pct" else "ms/batch")
    by_name = {m["name"]: m for m in _load(CELL)["per_layer"]}
    assert set(by_name) == {*NEW_METRICS, *(n + ".over" for n in SPLIT)}
    assert callable(run.resolve(by_name[name]["reader"], "readers"))
    assert by_name[name]["reader"].startswith("loopshare.")


@pytest.mark.parametrize("name", SPLIT)
def test_an_older_reading_is_taken_under_a_name_that_moves_the_completed_rate(name):
    # `device_idle_pct` moves `produce_mb_s`, which the cell does not
    # report: the same reader and parameters under `<name>.over`, which
    # moves `sustained_mb_s` (the contract's `dispatch_ms.train` / `.serve`)
    manifest = _manifest()
    old = next(m for m in manifest["per_layer"] if m["name"] == name)
    new = next(m for m in manifest["per_layer"] if m["name"] == name + ".over")
    apart = ("name", "moves", "workloads")
    assert {k: v for k, v in new.items() if k not in apart} \
        == {k: v for k, v in old.items() if k not in apart}
    assert new["moves"] == "sustained_mb_s" and CELL in new["workloads"]
    specs = [run.load_json(run.HERE, "metrics", n + ".json") for n in (name, name + ".over")]
    assert specs[0]["reader"] == specs[1]["reader"]
    assert specs[0]["params"] == specs[1]["params"]


def test_no_span_is_in_two_shares():
    spans = [s for n in SHARES
             for s in run.load_json(run.HERE, "metrics", n + ".json")["params"]["spans"]]
    assert len(spans) == len(set(spans)) == 18
    whole = run.load_json(run.HERE, "metrics", "loop_run_ms_per_batch.json")["params"]
    idle = run.load_json(run.HERE, "metrics", "loop_unspanned_pct.json")["params"]
    # a `run` span whose extent holds a wait is in no share and not in the whole
    assert "spans" not in whole and whole == idle == {"awaiting": ["produce.decode"]}
    assert "produce.decode" not in spans


# ------------------------------------------- a manifest laid over another
def test_a_manifest_is_laid_over_by_name():
    base = {"run_seconds": 40, "paths": ["benchmark"],
            "workloads": [{"name": "a", "chips": 1}, {"name": "b", "chips": 1}],
            "end_to_end": [{"name": "x", "bound": 0.1}, {"name": "y", "bound": 0.2}]}
    own = {"run_seconds": 5, "workloads": [{"name": "c", "chips": 1}],
           "end_to_end": [{"name": "x", "bound": 0.1, "workloads": ["a"]},
                          {"name": "z", "bound": 0.3, "workloads": ["c"]}]}
    got = run.laid_over(base, own)
    assert got["run_seconds"] == 5 and got["paths"] == ["benchmark"]
    assert [w["name"] for w in got["workloads"]] == ["a", "b", "c"]
    # `x` is replaced by the entry of its name, `y` stays, `z` is new
    assert {m["name"]: m.get("workloads") for m in got["end_to_end"]} \
        == {"y": None, "x": ["a"], "z": ["c"]}
    assert base["end_to_end"][0] == {"name": "x", "bound": 0.1}    # untouched


def test_the_queued_manifest_is_entries_alone():
    own = run.load_json(QUEUED)
    assert set(own) == {"what", "workloads", "end_to_end", "per_layer"}
    # a configuration or traffic file beside a manifest is found first;
    # this one has none and takes the benchmark's own
    assert os.listdir(os.path.dirname(QUEUED)) == [os.path.basename(QUEUED)]
    # it changes no entry's bound, unit or sense: it gives three entries
    # their lists and adds the rest
    today = {m["name"]: m for m in run.load_json(run.ROOT, "BENCHMARK.json")["end_to_end"]}
    for m in own["end_to_end"]:
        if m["name"] in today:
            assert {k: v for k, v in m.items() if k != "workloads"} == today[m["name"]]
    listed = {m["name"] for m in run.load_json(run.ROOT, "BENCHMARK.json")["per_layer"]}
    assert not listed & {m["name"] for m in own["per_layer"]}


# --------------------------------------------------------- sustained_mb_s
def _row(base, t_due, t_ack, in_request=1, err=None):
    # topic, partition, template, base, t_due, t_ack, error, t_sent,
    # tries, in_request, t_fetch, fetched_template
    return ["t", 0, 0, base, t_due, t_ack, err, t_due, 1, in_request,
            t_ack + 0.6 if base >= 0 else 0.0, 0 if base >= 0 else -2]


def test_sustained_mb_s_counts_an_ack_inside_the_window_and_none_after():
    # ten batches due in a 10 s window: six acknowledged inside it, three
    # during the drain, one never
    rows = [_row(i, 10.0 + i, 10.5 + 1.5 * i, in_request=1 + i % 3) for i in range(9)]
    rows.append(_row(-1, 19.0, 0.0, err="gave up"))
    assert sum(1 for r in rows if r[3] >= 0 and r[5] <= 20.0) == 7
    rec = {"t0": 10.0, "seconds": 10.0, "rows": rows, "payload_bytes": 39936,
           "fetch_error_count": 0}
    got = reduce_records(rec, drain_s=60)
    assert got["metrics"]["sustained_mb_s"] == 7 * 39936 / 1e6 / 10.0
    assert got["metrics"]["produce_mb_s"] == got["metrics"]["sustained_mb_s"]
    assert got["acked"] == 9 and got["failed"] == 1
    assert got["acked_payload_bytes"] == 7 * 39936
    # a batch is fetched 0.6 s after its ack: the seventh's ack falls
    # inside the window and its fetch after the close
    assert [r[10] <= 20.0 for r in rows[:7]] == [True] * 6 + [False]
    assert got["load"] == {
        "offered_batches_per_s": 1.0, "acked_share_in_window": 0.7,
        "due_not_acked_at_close": 3,
        "batches_a_request": round(sum(1 + i % 3 for i in range(9)) / 9, 3),
        "fetched_mb_s_in_window": round(6 * 39936 / 1e6 / 10.0, 4),
        "fetched_share_of_acked_in_window": round(6 / 7, 4)}
    # an ack exactly at the close is inside; the medians are still there
    rows[7][5] = 20.0
    assert reduce_records(rec, 60)["load"]["acked_share_in_window"] == 0.8
    assert got["metrics"]["produce_p50_ms"] > 0


# ------------------------------------------------------ the loop's shares
def _agg(kind, count, self_s, total_s=None):
    return {"kind": kind, "count": count, "self_s": self_s,
            "total_s": total_s if total_s is not None else self_s,
            "p50_ms": 1.0, "p99_ms": 2.0}


HOST = {
    "produce.decode": _agg("run", 500, 0.10),
    "produce.dispatch": _agg("run", 500, 0.40, 0.9),
    "raft.append": _agg("run", 900, 0.30, 0.5),
    "storage.append": _agg("run", 900, 0.20),
    "raft.follower_append": _agg("run", 1800, 0.50),
    "tick.fold": _agg("run", 1000, 0.25, 2.0),
    "tick.upload": _agg("run", 1000, 0.75),
    "tick.readback": _agg("run", 1000, 0.50),
    "device.dispatch": _agg("run", 1100, 0.60),
    "fetch.read": _agg("run", 700, 0.35),
    "fetch.verify": _agg("run", 700, 1.05, 1.25),
    "hb.build": _agg("run", 800, 0.04),
    "hb.follower": _agg("run", 1600, 0.06),
    "devplane.frame": _agg("run", 1000, 0.30),       # in no share
    "kafka.produce": _agg("wait", 500, 90.0),
    "raft.quorum_wait": _agg("wait", 1000, 40.0),
    "raft.flush": _agg("wait", 900, 0.0),
}
RUN_S = 5.30     # every `run` aggregate's self time, devplane.frame's too,
#                  less `produce.decode`'s, whose extent holds a wait


def _ctx(host=HOST, batches=1000, dropped=0, seconds=10.0):
    templates = make_templates(5, 2, 3, 64)
    return {"devplane": {"host": host, "spans": [], "spans_dropped": dropped},
            "devplane_s": seconds, "templates": templates,
            "acked_payload_bytes": batches * templates[0].payload_bytes}


def _read(name, ctx):
    spec = run.load_json(run.HERE, "metrics", name + ".json")
    return run.resolve(spec["reader"], "readers")(ctx, spec["params"])


def test_the_shares_and_what_is_in_none_add_up_to_the_loop_s_run_time():
    ctx = _ctx()
    whole = _read("loop_run_ms_per_batch", ctx)
    assert whole == pytest.approx(1e3 * RUN_S / 1000)
    shares = {n: _read(n, ctx) for n in SHARES}
    assert shares == pytest.approx({
        "frontend_run_ms_per_batch": 0.40, "replicate_run_ms_per_batch": 1.00,
        "fold_run_ms_per_batch": 2.10, "fetch_run_ms_per_batch": 1.40,
        "heartbeat_run_ms_per_batch": 0.10})
    in_none = 1e3 * HOST["devplane.frame"]["self_s"] / 1000
    assert sum(shares.values()) + in_none == pytest.approx(whole)
    assert _read("loop_unspanned_pct", ctx) == pytest.approx(100 * (1 - RUN_S / 10.0))


def test_wait_spans_count_for_nothing():
    quiet = {n: a for n, a in HOST.items() if a["kind"] == "run"}
    for name in NEW_METRICS:
        assert _read(name, _ctx(quiet)) == _read(name, _ctx())


def test_a_run_span_whose_extent_holds_a_wait_counts_for_nothing():
    # `produce.decode` runs from the frame's arrival, so over saturation
    # it holds the request's wait for the loop: 22 ms a batch in a window
    # whose other `run` spans summed to 1.0
    slow = {**HOST, "produce.decode": _agg("run", 500, 640.0)}
    for name in NEW_METRICS:
        assert _read(name, _ctx(slow)) == _read(name, _ctx())
    # a reader whose metric names no such span counts it
    assert loopshare.run_ms_per_acked_batch(_ctx(), {}) == pytest.approx(5.4)


def test_dropped_raw_records_change_nothing():
    for name in NEW_METRICS:
        assert _read(name, _ctx(dropped=123456)) == _read(name, _ctx())


def test_self_time_is_what_is_read_never_the_total():
    # `tick.fold` spans 2.0 s of which its children cover 1.75
    only = {"tick.fold": HOST["tick.fold"]}
    assert _read("fold_run_ms_per_batch", _ctx(only)) == pytest.approx(0.25)


@pytest.mark.parametrize("name", NEW_METRICS)
@pytest.mark.parametrize("case", ["no_devplane", "no_host_key", "no_aggregate",
                                  "wait_spans_alone", "no_acked_batch"])
def test_nothing_to_read_is_none_never_zero(name, case):
    ctx = {
        "no_devplane": {**_ctx(), "devplane": None},
        "no_host_key": {**_ctx(), "devplane": {"spans": []}},
        "no_aggregate": _ctx({}),
        "wait_spans_alone": _ctx({"kafka.produce": HOST["kafka.produce"]}),
        "no_acked_batch": _ctx(batches=0),
    }[case]
    got = _read(name, ctx)
    # the loop's idle share needs no acknowledged batch: it is over seconds
    if name == "loop_unspanned_pct" and case == "no_acked_batch":
        assert got == pytest.approx(100 * (1 - RUN_S / 10.0))
    else:
        assert got is None


def test_a_share_none_of_whose_spans_ran_is_none():
    ctx = _ctx({"fetch.read": HOST["fetch.read"]})
    assert _read("fetch_run_ms_per_batch", ctx) == pytest.approx(0.35)
    assert _read("heartbeat_run_ms_per_batch", ctx) is None
    assert loopshare.unspanned_pct({**ctx, "devplane_s": 0.0}, {}) is None


#: what test_reference.py takes by `import *`, for tier-1 to collect
__all__ = [_n for _n in dir() if _n.startswith("test_")]
