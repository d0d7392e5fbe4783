"""The codec deployment's cell, `omb_100_lz4.half_random_0p8`, rehearsed
on the CPU at toy size: it runs from BENCHMARK.json under its own name,
prints the four end-to-end metrics and, traced, the two new metrics that
read the `produce.recompress` span alone (`lz4_roofline` needs a device
trace and says nothing here); the control and the planted fault that are
its own come out as not correct. test_dry_run.py's cases over every cell
take it too. About half a minute a case."""

import json
import os

from benchmark.tests.conftest import ROOT, dry_run

CELL = "omb_100_lz4.half_random_0p8"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    E2E = {m["name"] for m in json.load(_f)["end_to_end"]}


def test_untraced_reports_the_four_end_to_end_metrics_and_is_correct():
    line = dry_run(CELL, seed=2**31 + 321)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert set(line["metrics"]) == E2E == {
        "produce_mb_s", "produce_p50_ms", "e2e_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["checks"]["dispatched.fused.crc_lz4"]["value"] >= 1
    assert line["checks"]["fetched_wrong"] == {"value": 0, "limit": 0}
    assert line["checks"]["replicas_missing"] == {"value": 0, "limit": 0}
    sent, stored = (line["detail"]["batch_bytes"][k] for k in ("sent", "stored_p50"))
    assert 0.4 * sent < stored < 0.6 * sent


def test_traced_reports_the_span_s_metrics():
    line = dry_run(CELL, seed=2**31 + 322, trace=1)
    assert line["correct"] is True, line["checks"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["recompress_ms"] > 0
    assert 0.4 < metrics["stored_bytes_per_sent_byte"] < 0.6
    # the front end's time holds the recompression on this cell (two
    # medians, of the requests and of the batches: near, not ordered)
    assert metrics["frontend_ms"] > 0.8 * metrics["recompress_ms"]
    assert "lz4_roofline" not in metrics and "crc_roofline" not in metrics


def test_control_device_off_dispatches_no_fused_program():
    line = dry_run(CELL, "--control", "device_off", seed=2**31 + 323)
    assert line["correct"] is False and line["control"] == "device_off"
    assert line["checks"]["dispatched.fused.crc_lz4"]["value"] == 0
    # the host's codec still stored what the reference reads back
    assert line["checks"]["fetched_wrong"]["value"] == 0


def test_planted_recompress_skipped_is_not_correct():
    line = dry_run(CELL, seed=2**31 + 324, plant="recompress_skipped")
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["fetched_wrong"]["value"] > 0
    assert line["checks"]["replicas_missing"]["value"] > 0
    assert line["checks"]["acked"]["value"] > 0
