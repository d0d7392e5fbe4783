#!/usr/bin/env python3
"""bench_gate: grade a fresh bench summary against the BENCH_r*.json
trajectory.

The driver archives every round's bench run as BENCH_r<NN>.json
({n, cmd, rc, tail, parsed}); the repo promises monotone-ish perf, but
until now nothing *mechanical* compared a new run to the trajectory —
regressions were caught by a human reading two JSON blobs. This tool
closes that:

    python bench.py --only replicated > /tmp/bench.out
    python tools/bench_gate.py --summary /tmp/bench.out

It extracts every `{"metric": ..., "value": ..., "unit": ...}` object
from the fresh summary (the bench's machine-readable last line, or a
file that IS that object), finds the most recent trajectory round
carrying the same metric, and fails (exit 1) when the fresh value
regresses past --tolerance in the unit's bad direction (throughput
units regress down, latency units regress up).

Older rounds need salvage: r03+ archives have `parsed: null` with the
real summary as the last line of a 2000-char `tail` — truncated at the
FRONT, so `json.loads(last_line)` fails. The gate rescues every
balanced sub-object that survived the window instead of parsing the
line wholesale, which recovers the per-bench extras even when the
headline was cut.

`--selftest` exercises the whole path without running a bench and
without any archived round: it writes its own two-round fixture
trajectory (made-up values, one round front-truncated), then a summary
matching it must pass and a degraded copy must fail — that's the
verify.sh smoke leg. The repo keeps no BENCH_r*.json today (the seed
rounds' records were removed in PR 21; see PERF.md).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# units where bigger is better; anything matching _LAT_RE is
# smaller-is-better ("skew" is the placement layer's cross-shard load
# skew index, 1.0 = balanced — a rebalance that leaves the fleet MORE
# skewed than the trajectory is a regression the same way a latency
# bump is; "x_wall_*" is a flatness ratio — per-tick wall growth for
# an NX group-count step, e.g. the replicated_tick and mesh_flat
# steady ratios — where growing past the trajectory means the plane
# got LESS flat); other units are reported but not graded
_THROUGHPUT_RE = re.compile(r"/s$|bps$", re.IGNORECASE)
_LAT_RE = re.compile(r"^(ns|us|ms|s|skew)$|^x_wall|^ratio", re.IGNORECASE)


def _direction(unit: str) -> int:
    """+1 higher-better, -1 lower-better, 0 ungraded."""
    if _THROUGHPUT_RE.search(unit or ""):
        return 1
    if _LAT_RE.match(unit or ""):
        return -1
    return 0


def _balanced_objects(text: str):
    """Yield every parseable top-level-balanced {...} span in `text`.

    Tolerates truncated fronts (the BENCH tail window): scanning from
    each '{' and bracket-matching recovers complete sub-objects even
    when the enclosing object lost its opening brace to the window.
    """
    i, n = 0, len(text)
    while i < n:
        if text[i] != "{":
            i += 1
            continue
        depth, j, in_str, esc = 0, i, False, False
        while j < n:
            c = text[j]
            if in_str:
                if esc:
                    esc = False
                elif c == "\\":
                    esc = True
                elif c == '"':
                    in_str = False
            elif c == '"':
                in_str = True
            elif c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
                if depth == 0:
                    break
            j += 1
        if depth == 0 and j < n:
            span = text[i : j + 1]
            try:
                yield json.loads(span)
            except ValueError:
                pass
            i = j + 1
        else:
            i += 1


def _collect_metrics(obj, out: dict) -> None:
    """Flatten: every sub-dict carrying metric+value becomes one row.
    First writer wins so the outermost (headline) context sticks."""
    if not isinstance(obj, dict):
        return
    name = obj.get("metric")
    if isinstance(name, str) and isinstance(obj.get("value"), (int, float)):
        out.setdefault(
            name, {"value": float(obj["value"]), "unit": str(obj.get("unit", ""))}
        )
    for v in obj.values():
        if isinstance(v, dict):
            _collect_metrics(v, out)


def load_round(path: str) -> tuple[int, dict]:
    with open(path) as f:
        doc = json.load(f)
    rnd = int(doc.get("n", 0))
    metrics: dict = {}
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        _collect_metrics(parsed, metrics)
    else:
        tail = doc.get("tail") or ""
        lines = [ln for ln in tail.strip().splitlines() if ln.strip()]
        if lines:
            for sub in _balanced_objects(lines[-1]):
                _collect_metrics(sub, metrics)
    return rnd, metrics


def load_history(pattern: str) -> list[tuple[int, str, dict]]:
    rounds = []
    for path in sorted(glob.glob(pattern)):
        try:
            rnd, metrics = load_round(path)
        except (OSError, ValueError) as e:
            print(f"# bench_gate: skipping unreadable {path}: {e}",
                  file=sys.stderr)
            continue
        if metrics:
            rounds.append((rnd, os.path.basename(path), metrics))
    rounds.sort(key=lambda r: r[0])
    return rounds


def load_summary(path: str) -> dict:
    """Fresh summary: a JSON file, or raw bench stdout whose TRUE final
    line is the summary (bench.py's _emit_summary contract)."""
    with open(path) as f:
        text = f.read()
    metrics: dict = {}
    try:
        _collect_metrics(json.loads(text), metrics)
        return metrics
    except ValueError:
        pass
    lines = [ln for ln in text.strip().splitlines() if ln.strip()]
    if lines:
        for sub in _balanced_objects(lines[-1]):
            _collect_metrics(sub, metrics)
    return metrics


def gate(fresh: dict, history: list, tolerance: float) -> tuple[list, list]:
    """Returns (rows, failures); a row is a human-readable verdict."""
    rows, failures = [], []
    for name, cur in sorted(fresh.items()):
        # compile-discipline metrics are graded ABSOLUTE, not against
        # the trajectory: the steady-state recompile count must be
        # exactly zero (a ratio vs a zero baseline is meaningless, and
        # "only a few recompiles" is still a mid-traffic XLA stall)
        if cur["unit"] == "recompiles":
            if cur["value"] > 0:
                line = (f"FAIL  {name}: {cur['value']:g} steady-state "
                        "recompile(s) — must be exactly 0")
                rows.append(line)
                failures.append(line)
            else:
                rows.append(f"OK    {name}: 0 recompiles (absolute gate)")
            continue
        ref = None
        for rnd, fname, metrics in reversed(history):
            if name in metrics:
                ref = (rnd, fname, metrics[name])
                break
        if ref is None:
            rows.append(f"NEW   {name} = {cur['value']} {cur['unit']} "
                        "(no trajectory reference)")
            continue
        rnd, fname, prev = ref
        d = _direction(cur["unit"] or prev["unit"])
        base = prev["value"]
        if d == 0 or base == 0:
            rows.append(f"INFO  {name}: {cur['value']} vs r{rnd:02d} {base} "
                        f"{cur['unit']} (ungraded unit)")
            continue
        ratio = cur["value"] / base
        regressed = ratio < (1.0 - tolerance) if d > 0 else ratio > (1.0 + tolerance)
        tag = "FAIL " if regressed else "OK   "
        line = (f"{tag} {name}: {cur['value']:g} vs r{rnd:02d}={base:g} "
                f"{cur['unit']} ({'higher' if d > 0 else 'lower'}-better, "
                f"x{ratio:.3f}, tol {tolerance:.0%})")
        rows.append(line)
        if regressed:
            failures.append(line)
    return rows, failures


def _write_fixture_trajectory(dirpath: str) -> None:
    """The selftest's own two-round trajectory, in the archive format
    ({n, cmd, rc, tail, parsed}) with made-up values: round 1 parsed,
    round 2 with `parsed: null` and a summary line whose front was cut
    off (the salvage path), one metric per graded unit family."""
    r1 = {
        "metric": "fixture_sweep_p99", "value": 0.5, "unit": "ms",
        "extra": {
            "crc": {"metric": "fixture_crc_gbps", "value": 10.0,
                    "unit": "GB/s"},
        },
    }
    r2_line = json.dumps({
        "metric": "fixture_sweep_p99", "value": 0.4, "unit": "ms",
        "extra": {
            "crc": {"metric": "fixture_crc_gbps", "value": 12.0,
                    "unit": "GB/s"},
            "placement": {"metric": "fixture_skew", "value": 1.1,
                          "unit": "skew"},
        },
    })
    rounds = [
        {"n": 1, "cmd": "fixture", "rc": 0, "tail": "", "parsed": r1},
        # front-truncated: the headline's opening brace is gone, the
        # sub-objects survive
        {"n": 2, "cmd": "fixture", "rc": 0, "parsed": None,
         "tail": "noise\n" + r2_line[25:]},
    ]
    for doc in rounds:
        with open(os.path.join(dirpath, f"BENCH_r{doc['n']:02d}.json"), "w") as f:
            json.dump(doc, f)


def selftest(tolerance: float) -> int:
    # unit-direction contract first: the mesh_flat block grades three
    # lower-better families (x_wall_* flatness ratios, fold µs, lane
    # skew) next to the existing throughput/latency units
    unit_cases = {
        "GB/s": 1, "records/s": 1, "mbps": 1,
        "ns": -1, "us": -1, "ms": -1, "skew": -1,
        "x_wall_for_10x_groups": -1, "x_wall_for_20x_groups": -1,
        "ratio": -1, "ratio_vs_host": -1,
        "count": 0, "": 0,
        # graded absolutely in gate(), not by direction
        "recompiles": 0,
    }
    for unit, want in unit_cases.items():
        if _direction(unit) != want:
            print(f"bench_gate selftest: unit '{unit}' graded "
                  f"{_direction(unit)}, want {want}", file=sys.stderr)
            return 2
    # synthetic mesh_flat round: grading must hold even before the
    # trajectory carries the mesh metrics
    mesh_round = {
        "mesh_flat_steady_ratio_1000000_partitions":
            {"value": 1.5, "unit": "x_wall_for_10x_groups"},
        "mesh_full_fold_us_1000000_partitions":
            {"value": 600000.0, "unit": "us"},
        "mesh_lane_balance_skew_1000000_partitions":
            {"value": 1.0, "unit": "skew"},
        # PR 14 device-zstd units, graded before the trajectory
        # carries them: compression ratios regress UP (a bigger
        # stored/logical or device/host ratio means the codec got
        # worse), throughput down
        "zstd_compress_device_gbps": {"value": 5.0, "unit": "GB/s"},
        "zstd_ratio_vs_host": {"value": 1.05, "unit": "ratio_vs_host"},
        "tiered_archive_ratio": {"value": 0.55, "unit": "ratio"},
    }
    mesh_hist = [(0, "synthetic-mesh", mesh_round)]
    _, failures = gate(dict(mesh_round), mesh_hist, tolerance)
    if failures:
        print("bench_gate selftest: identical mesh summary failed:\n"
              + "\n".join(failures), file=sys.stderr)
        return 2
    # degrade each metric in ITS bad direction (the synthetic block
    # now mixes higher-better throughput with lower-better ratios)
    worse = {
        k: {**m, "value": m["value"] * (
            (1 - 2 * tolerance) if _direction(m["unit"]) > 0
            else (1 + 2 * tolerance)
        )}
        for k, m in mesh_round.items()
    }
    _, failures = gate(worse, mesh_hist, tolerance)
    if len(failures) != len(mesh_round):
        print(f"bench_gate selftest: only {len(failures)}/"
              f"{len(mesh_round)} degraded mesh metrics caught",
              file=sys.stderr)
        return 2

    # absolute recompile gate: zero passes with NO trajectory
    # reference; any positive count fails even though a ratio against
    # the zero baseline would be undefined
    clean = {"steady_recompiles_100000_groups":
             {"value": 0.0, "unit": "recompiles"}}
    _, failures = gate(dict(clean), [], tolerance)
    if failures:
        print("bench_gate selftest: zero-recompile summary failed",
              file=sys.stderr)
        return 2
    dirty = {"steady_recompiles_100000_groups":
             {"value": 2.0, "unit": "recompiles"}}
    _, failures = gate(dirty, [], tolerance)
    if len(failures) != 1:
        print("bench_gate selftest: steady-state recompiles slipped "
              "through the absolute gate", file=sys.stderr)
        return 2

    with tempfile.TemporaryDirectory() as tmp:
        _write_fixture_trajectory(tmp)
        history = load_history(os.path.join(tmp, "BENCH_r*.json"))
    if len(history) != 2:
        print("bench_gate selftest: fixture trajectory did not load",
              file=sys.stderr)
        return 2
    latest = history[-1][2]
    graded = {n: m for n, m in latest.items() if _direction(m["unit"])}
    if not graded:
        print("bench_gate selftest: trajectory has no gradeable metric",
              file=sys.stderr)
        return 2
    # a run matching the latest round must pass...
    _, failures = gate(dict(latest), history, tolerance)
    if failures:
        print("bench_gate selftest: identical summary failed the gate:\n"
              + "\n".join(failures), file=sys.stderr)
        return 2
    # ...and a regression far past tolerance must fail — one probe per
    # distinct unit, so every graded unit family in the trajectory is
    # exercised in its bad direction
    probes = {}
    for name, m in sorted(graded.items()):
        probes.setdefault(m["unit"], (name, m))
    caught = []
    for unit, (name, m) in sorted(probes.items()):
        factor = (
            (1 - 2 * tolerance) if _direction(unit) > 0
            else (1 + 2 * tolerance)
        )
        bad = {**latest, name: {**m, "value": m["value"] * factor}}
        _, failures = gate(bad, history, tolerance)
        if not failures:
            print(f"bench_gate selftest: regressed '{name}' ({unit}) "
                  "slipped through", file=sys.stderr)
            return 2
        caught.append(name)
    print(f"bench_gate selftest: ok ({len(history)} rounds, "
          f"{len(graded)} graded metrics, {len(mesh_round)} synthetic "
          f"mesh metrics, absolute recompile gate exercised, "
          f"regressions caught on {len(caught)} unit "
          f"probes: {', '.join(caught)})")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--summary", help="fresh summary: JSON file or raw "
                    "bench stdout (summary = last line)")
    ap.add_argument("--history", default=os.path.join(REPO_ROOT, "BENCH_r*.json"),
                    help="trajectory glob (default: repo BENCH_r*.json)")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional regression (default 0.25 — "
                    "single-run benches on shared hardware are noisy)")
    ap.add_argument("--selftest", action="store_true",
                    help="validate extraction+grading against a "
                    "built-in fixture trajectory; no bench run needed")
    args = ap.parse_args()

    if args.selftest:
        return selftest(args.tolerance)
    if not args.summary:
        ap.error("--summary FILE required (or --selftest)")

    history = load_history(args.history)
    fresh = load_summary(args.summary)
    if not fresh:
        print(f"bench_gate: no metrics found in {args.summary}", file=sys.stderr)
        return 2
    rows, failures = gate(fresh, history, args.tolerance)
    print("\n".join(rows))
    if failures:
        print(f"\nbench_gate: {len(failures)} regression(s) past "
              f"{args.tolerance:.0%} tolerance", file=sys.stderr)
        return 1
    print(f"\nbench_gate: pass ({len(rows)} metrics vs "
          f"{len(history)} trajectory rounds)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
