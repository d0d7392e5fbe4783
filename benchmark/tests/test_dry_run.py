"""A --cpu-dry-run of each cell at toy size prints a last line with the
contract's keys; the controls and the planted faults come out as not
correct. Each case boots brokers in a fresh interpreter: about half a
minute apiece."""

import json
import os

import pytest

from benchmark.tests import faults
from benchmark.tests.conftest import RESULT_KEYS, ROOT, dry_run

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = {m["name"] for m in MANIFEST["end_to_end"]}
LAYERS = {m["name"] for m in MANIFEST["per_layer"]}
#: the cell over saturation, in its queued manifest (PR 34): judged on
#: what it completes, no median
OVER = ("omb_100.smoke_over", "--manifest",
        os.path.join(ROOT, "benchmark", "queued", "omb_100.smoke_over.json"))
with open(OVER[2]) as _f:
    OVER_LAYERS = {m["name"] for m in json.load(_f)["per_layer"]}


def _well_formed(line: dict, trace: int) -> None:
    assert RESULT_KEYS <= set(line)
    assert list(line)[-1] == "checks"
    assert line["dry_run"] is True
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["attempted"] > 0
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert name in (LAYERS | OVER_LAYERS if trace else E2E | {"sustained_mb_s"})
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def _says_where_it_stood(line: dict) -> None:
    """Every cell prints its medians and where the window stood against
    its load under `detail`, whatever its `metrics` hold."""
    assert {"produce_p50_ms", "e2e_p50_ms", "produce_p50_ms_by_quarter",
            "offered_batches_per_s", "acked_share_in_window",
            "due_not_acked_at_close", "batches_a_request",
            "fetched_mb_s_in_window", "fetched_share_of_acked_in_window",
            "generator_handed_late_p95_ms", "generator_cpu_share",
            "compiles_in_window", "spans_dropped", "span_self_s"} <= set(line["detail"])


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_reports_every_end_to_end_metric(cell):
    line = dry_run(cell, seed=2**31 + 11)
    _well_formed(line, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == E2E
    assert all(m["value"] > 0 for m in line["metrics"].values())
    _says_where_it_stood(line)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell):
    line = dry_run(cell, seed=2**31 + 12, trace=1)
    _well_formed(line, 1)
    assert line["correct"] is True
    # the counters read on any platform; the trace's readers find no
    # device plane on the CPU and say nothing rather than 0
    assert {"tick_dispatch_ms", "h2d_bytes_per_acked_byte",
            "elections_in_window"} <= set(line["metrics"])
    assert not {"tick_roofline", "crc_roofline", "device_idle_pct"} & set(line["metrics"])
    assert not OVER_LAYERS & set(line["metrics"])
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_the_queued_cell_over_saturation_reports_what_it_completes():
    line = dry_run(*OVER, seed=2**31 + 19)
    _well_formed(line, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"sustained_mb_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    _says_where_it_stood(line)


def test_the_queued_cell_over_saturation_reads_the_loop_s_shares():
    line = dry_run(*OVER, seed=2**31 + 20, trace=1)
    _well_formed(line, 1)
    assert line["correct"] is True
    # what a batch costs the loop, from the span store's aggregates
    got = {n: m["value"] for n, m in line["metrics"].items()}
    shares = {n for n in OVER_LAYERS if n.endswith("_run_ms_per_batch")}
    assert shares | {"loop_unspanned_pct"} <= set(got) and not set(got) & LAYERS
    parts = sum(got[n] for n in shares - {"loop_run_ms_per_batch"})
    assert 0 < parts <= got["loop_run_ms_per_batch"] * (1 + 1e-9)
    assert 0 < got["loop_unspanned_pct"] < 100
    # the older readings under names that move `sustained_mb_s`: the
    # counters read on any platform, the trace's readers not on the CPU
    assert {"h2d_bytes_per_acked_byte.over", "elections_in_window.over",
            "compiles_in_window.over"} <= set(got)
    assert not {"tick_roofline.over", "crc_roofline.over",
                "device_idle_pct.over"} & set(got)


@pytest.mark.parametrize("cell", CELLS)
def test_control_device_off_is_not_correct(cell):
    line = dry_run(cell, "--control", "device_off", seed=2**31 + 13)
    assert line["correct"] is False and line["control"] == "device_off"
    assert line["checks"]["dispatched.quorum.heartbeat_tick"]["value"] == 0
    assert line["checks"]["dispatched.crc32c.device"]["value"] == 0


REPLICATED = next(w["name"] for w in MANIFEST["workloads"] if w["config"] == "rf3_1k")


def test_control_rf1_is_not_correct():
    line = dry_run(REPLICATED, "--control", "rf1", seed=2**31 + 14)
    assert line["correct"] is False
    assert line["checks"]["replicas_missing"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_flush_lagged_is_not_correct(cell):
    """Acknowledgements that run ahead of the flush: the acks read as
    they arrive find fewer than a majority of the logs flushed."""
    line = dry_run(cell, "--control", "flush_lagged", seed=2**31 + 16)
    assert line["correct"] is False and line["control"] == "flush_lagged"
    assert line["checks"]["not_flushed_at_ack"]["value"] > 0
    others = {k: v for k, v in line["checks"].items() if k != "not_flushed_at_ack"}
    assert all(
        v["value"] >= 1 if v["limit"] == ">=1" else v["value"] == v["limit"]
        for v in others.values()
    ), others


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(fault):
    line = dry_run(REPLICATED, seed=2**31 + 15, plant=fault)
    assert line["correct"] is False, line["checks"]
    failing = {
        "tick_frozen": "acked",
        "replica_left_out": "replicas_missing",
        "answer_altered": "fetched_wrong",
    }[fault]
    c = line["checks"][failing]
    assert c["value"] != c["limit"] and (failing != "acked" or c["value"] == 0)


RECOMPRESS = ("omb_100_lz4.lz4_smoke", "--manifest",
              os.path.join(ROOT, "benchmark", "tests", "recompress", "manifest.json"))


def test_a_recompressing_topic_runs_from_a_manifest_and_is_correct():
    """A topic with compression.type=lz4 through set-up, generator,
    comparison and readers, its cell in a manifest of the test's own:
    the device's cell-parse LZ4 (here on the CPU backend) passes the
    reference's decoder, and the stored batches are shorter than sent."""
    line = dry_run(*RECOMPRESS, seed=2**31 + 17)
    _well_formed(line, 0)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert set(line["metrics"]) == E2E
    assert line["checks"]["dispatched.fused.crc_lz4"]["value"] >= 1
    assert line["checks"]["fetched_wrong"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("fault", faults.CODEC_FAULTS)
def test_planted_codec_fault_is_not_correct(fault):
    line = dry_run(*RECOMPRESS, seed=2**31 + 18, plant=fault)
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["fetched_wrong"]["value"] > 0
    assert line["checks"]["replicas_missing"]["value"] > 0
    assert line["checks"]["acked"]["value"] > 0


def test_no_accelerator_no_result():
    """Without --cpu-dry-run, on a machine with no TPU: exit 5, nothing
    on standard output."""
    import subprocess
    import sys

    got = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert got.returncode == 5 and got.stdout.strip() == ""
