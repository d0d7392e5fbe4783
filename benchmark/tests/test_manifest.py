"""BENCHMARK.json and every data file parse and cross-reference by name;
so does BENCHMARK.json with each manifest of benchmark/queued/ laid over
it, which is what it becomes when a later PR brings that manifest's cell."""

import json
import os
import re

import pytest

from benchmark import run
from benchmark.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


QUEUED = os.path.join(ROOT, "benchmark", "queued")
LAID_OVER = [None, *sorted(os.listdir(QUEUED))]


@pytest.fixture(scope="module", params=LAID_OVER, ids=lambda q: q or "BENCHMARK.json")
def queued(request):
    """None, then each queued manifest's path: what `--manifest` takes."""
    return request.param and os.path.join(QUEUED, request.param)


@pytest.fixture(scope="module")
def manifest(queued):
    found = run.load_json(ROOT, "BENCHMARK.json")
    if queued:
        own = run.load_json(queued)
        own.pop("what")
        found = run.laid_over(found, own)
    return found


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_configs(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        for key in ("brokers", "layout", "topics", "record_bytes", "acks",
                    "guarantees", "env", "device_kernels", "warm", "assumed",
                    "lane_capacity", "toy"):
            assert key in body, (c["name"], key)
        assert all(callable(run.resolve(w, "warmers")) for w in body["warm"])
        assert all(1 <= len(c[k]) <= 200 for k in ("source", "why"))
        assert all(NAME.match(k) for k in c["reduced"])


def test_workloads(manifest, queued):
    configs = {c["name"] for c in manifest["configs"]}
    seen = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert 1 <= len(w["why"]) <= 200
        loaded = run.load_cell(w["name"], manifest_file=queued)
        traffic = loaded["traffic"]
        assert callable(run.resolve(traffic["generator"], "generators"))
        assert callable(run.resolve(traffic["templates"]["maker"], "templates"))
        assert traffic["source"] and traffic["batches_per_s"] > 0
        assert {m["name"] for m in loaded["end_to_end"]} >= {"setup_s"}
        assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]


def test_metrics(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    assert "setup_s" in e2e
    names = set()
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells and m.get("workloads", cells)
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        # every cell a metric lists reports the end-to-end metric it moves
        moved = next(e for e in manifest["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", [])) <= set(moved.get("workloads", cells))
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["name"] == m["name"]
        assert callable(run.resolve(spec["reader"], "readers"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])


def test_every_metric_file_is_listed():
    listed = {m["name"] for m in run.load_json(ROOT, "BENCHMARK.json")["per_layer"]}
    for queued in LAID_OVER[1:]:
        listed |= {m["name"] for m in run.load_json(QUEUED, queued)["per_layer"]}
    on_disk = {
        f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmark", "metrics"))
    }
    assert on_disk == listed


def test_peaks_name_their_source():
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"]
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
