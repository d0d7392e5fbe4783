"""One run of one cell: `python3 -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`, from the root of a checkout.

This process holds the chip. It boots the configuration's brokers
in-process (real Kafka TCP listeners, LoopbackNetwork RPC) with the
configuration's device switches on, warms the shapes this cell's traffic
dispatches, creates the topics and waits for one acknowledged batch on
every partition: all of that is set-up. Then it starts the load generator
in a process of its own (benchmark/loadgen.py), keeps serving while that
drives the window, and afterwards holds what was acknowledged to the
plain reference (benchmark/compare.py), reduces the records to the
end-to-end metrics (`--trace 0`) or the counters and the profiler trace
to the per-layer metrics (`--trace 1`), and prints the result as the last
line of its standard output.

It refuses any platform but `tpu`: exit 5 and no result. `--cpu-dry-run`
walks the same control flow at toy sizes on the CPU for rehearsal; its
line says `"dry_run": true` and its numbers are not measurements.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python lets us

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from benchmark import log  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")

EXIT_BAD_CELL = 2
EXIT_NOT_A_CHECKOUT = 4
EXIT_NO_ACCELERATOR = 5

#: the program's device counters are armed in every run, so that each
#: can show which kernels dispatched on the TPU. Only the traced run
#: makes the probe wait for every dispatch (`RP_DEVPLANE_SAMPLE=1`); the
#: timed run leaves the program's own sampling, one dispatch in 16, so
#: the yardstick does not serialise what a later PR makes asynchronous
DEVPLANE_ENV = {"RP_DEVPLANE": "1"}
DEVPLANE_TRACED_ENV = {"RP_DEVPLANE_SAMPLE": "1"}
#: switches a run starts without, whatever the caller's environment
#: holds; the configuration's `env` then turns on what it serves with
SWITCHES = (
    "RP_QUORUM_BACKEND", "RP_CRC_BACKEND", "RP_CODEC_BACKEND",
    "RP_FETCH_VERIFY", "RP_ZSTD_BACKEND", "RP_MESH_FULL", "RP_MESH_DEVICES",
    "RP_NATIVE",
)
#: the control runs, for the builder and the tests, never the driver:
#: each breaks one guarantee the configuration states
CONTROLS = ("device_off", "rf1", "flush_lagged")
TRACE_SECONDS = 3.0


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_traffic(path: str) -> dict:
    """A traffic file, laid over the file its `base` names (beside it),
    if it names one."""
    traffic = load_json(path)
    if "base" in traffic:
        base = load_traffic(os.path.join(os.path.dirname(path), traffic["base"] + ".json"))
        traffic = {**base, **traffic}
    return traffic


def laid_over(manifest: dict, own: dict) -> dict:
    """A manifest of the builder's own over BENCHMARK.json: a list of
    entries is merged by `name` (an entry takes the place of the one of
    its name, a new one is appended), any other key is replaced. So a
    manifest that holds a cell, the end-to-end entries that name their
    cells for it and its per-layer entries is what a later PR adds to
    BENCHMARK.json, as it stands (benchmark/queued/)."""
    out = {**manifest}
    for key, value in own.items():
        if isinstance(value, list) and isinstance(manifest.get(key), list) \
                and all(isinstance(e, dict) and "name" in e for e in value):
            names = {e["name"] for e in value}
            out[key] = [e for e in manifest[key] if e["name"] not in names] + value
        else:
            out[key] = value
    return out


def load_cell(
    workload: str, traffic_file: str | None = None, manifest_file: str | None = None
) -> dict:
    """The cell's entry with its configuration, traffic and per-layer
    metric files, all found by the names in BENCHMARK.json. With a
    manifest of the builder's own (`--manifest`), its entries are laid
    over BENCHMARK.json's by name (`laid_over`), and the cell's
    configuration and traffic files are the ones beside it, or the
    benchmark's own where none is."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    beside = None
    if manifest_file:
        manifest = laid_over(manifest, load_json(manifest_file))
        beside = os.path.dirname(os.path.abspath(manifest_file))

    def find(kind: str, name: str) -> str:
        own = beside and os.path.join(beside, name + ".json")
        return own if own and os.path.exists(own) else os.path.join(HERE, kind, name + ".json")

    cell = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise KeyError(
            f"no workload {workload!r} in {manifest_file or 'BENCHMARK.json'}")
    config = load_json(find("configs", cell["config"]))
    traffic = load_traffic(traffic_file or find("traffic", cell["traffic"]))

    def reports(m: dict) -> bool:
        return workload in m["workloads"] if "workloads" in m else True

    e2e = [m for m in manifest["end_to_end"] if reports(m)]
    names = {m["name"] for m in e2e}
    layers = [
        {**m, **load_json(HERE, "metrics", m["name"] + ".json")}
        for m in manifest["per_layer"]
        if reports(m) and m["moves"] in names
    ]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layers}


def resolve(dotted: str, package: str):
    """`module.function` under benchmark/<package>/."""
    module, _, fn = dotted.partition(".")
    return getattr(importlib.import_module(f"benchmark.{package}.{module}"), fn)


def fs_type(path: str) -> str:
    """Filesystem type of the mount holding `path` (/proc/mounts)."""
    best, fstype = "", "unknown"
    real = os.path.realpath(path)
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mnt, typ = line.split()[:3]
                if (real == mnt or real.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best):
                    best, fstype = mnt, typ
    except OSError:
        pass
    return fstype


def require_platform(dry_run: bool, chips: int) -> dict:
    """The default backend must be the TPU, with the chips the cell
    asks for (the CPU, and only the CPU, under --cpu-dry-run)."""
    import jax

    want = "cpu" if dry_run else "tpu"
    try:
        found = jax.default_backend()
        devs = jax.devices()
    except RuntimeError as e:
        log(f"no accelerator: JAX could not start a backend: {e}")
        sys.exit(EXIT_NO_ACCELERATOR)
    if found != want:
        log(f"no accelerator: JAX's default backend is {found!r} (devices "
            f"{devs}, JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
            f"this benchmark runs on {want!r} only")
        sys.exit(EXIT_NO_ACCELERATOR)
    if not dry_run and len(devs) < chips:
        log(f"the cell asks for {chips} chips, JAX found {len(devs)}")
        sys.exit(EXIT_NO_ACCELERATOR)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s) - 1e-9) - 1)]


# columns of a record row (generators/open_loop.py)
TPL, BASE, T_DUE, T_ACK, ERR, IN_REQUEST, T_FETCH, GOT = 2, 3, 4, 5, 6, 9, 10, 11
GOT_BYTES = 12


def reduce_records(rec: dict, drain_s: float) -> dict:
    """The end-to-end metrics from the load generator's records: a rate
    over all the work and all the time of the window, medians over every
    batch that was due in the window, each counted from the time it was
    due. A batch that failed or was never answered counts as having
    taken the whole drain time."""
    t0, seconds = rec["t0"], rec["seconds"]
    t1 = t0 + seconds
    missed_ms = drain_s * 1e3
    produce_ms, e2e_ms = [], []
    acked_bytes = 0
    acked = failed = in_window = in_requests = fetched_in_window = 0
    for row in rec["rows"]:
        ok = row[BASE] >= 0 and row[ERR] is None
        produce_ms.append((row[T_ACK] - row[T_DUE]) * 1e3 if ok else missed_ms)
        if not ok:
            failed += 1
            continue
        acked += 1
        in_requests += row[IN_REQUEST]
        if row[T_ACK] <= t1:
            in_window += 1
            acked_bytes += rec["payload_bytes"]
        if row[GOT] == row[TPL]:
            e2e_ms.append((row[T_FETCH] - row[T_DUE]) * 1e3)
            fetched_in_window += row[T_FETCH] <= t1
        else:
            e2e_ms.append(missed_ms)
            failed += 1
    out = {
        "attempted": len(rec["rows"]),
        "failed": failed + rec["fetch_error_count"],
        "acked": acked,
        "acked_payload_bytes": acked_bytes,
        "metrics": {},
        # where the window stood against what was offered: a cell under
        # its knee reads a share of 1 less what was in flight at the
        # close, a cell over saturation the share it completes
        "load": {
            "offered_batches_per_s": round(len(rec["rows"]) / seconds, 3),
            "acked_share_in_window": round(in_window / max(1, len(rec["rows"])), 4),
            "due_not_acked_at_close": len(rec["rows"]) - in_window,
            "batches_a_request": round(in_requests / max(1, acked), 3),
            # what the consumers had in their hands at the close: over
            # saturation they trail the producers, and a change that
            # starves fetches would read as a gain in acks alone
            "fetched_mb_s_in_window": round(
                fetched_in_window * rec["payload_bytes"] / 1e6 / seconds, 4),
            "fetched_share_of_acked_in_window": round(
                fetched_in_window / max(1, in_window), 4),
        },
    }
    if acked:
        mb_s = acked_bytes / 1e6 / seconds
        out["metrics"] = {
            # one reading under two names: `produce_mb_s` where the cell
            # offers less than the system completes and the rate comes
            # back as offered, `sustained_mb_s` where it offers more and
            # the rate is what the system completed (the manifest says
            # which a cell reports)
            "produce_mb_s": mb_s,
            "sustained_mb_s": mb_s,
            "produce_p50_ms": percentile(produce_ms, 0.5),
            "e2e_p50_ms": percentile(e2e_ms, 0.5),
        }
        # for whoever reads a run by hand; not metrics: they swing by a
        # quarter from run to run (PERF.md section 2)
        out["tails"] = {
            "produce_p95_ms": round(percentile(produce_ms, 0.95), 3),
            "produce_p99_ms": round(percentile(produce_ms, 0.99), 3),
            "e2e_p95_ms": round(percentile(e2e_ms, 0.95), 3),
        }
    return out


def quarters(rec: dict) -> list[float]:
    """The median send-to-ack time of the batches due in each quarter
    of the window."""
    out = []
    for q in range(4):
        lo = rec["t0"] + rec["seconds"] * q / 4
        ms = [(r[T_ACK] - r[T_DUE]) * 1e3 for r in rec["rows"]
              if r[BASE] >= 0 and lo <= r[T_DUE] < lo + rec["seconds"] / 4]
        out.append(round(percentile(ms, 0.5), 1) if ms else None)
    return out


def by_step(rec: dict, drain_s: float) -> list[dict]:
    """For a staircase of rates (a sweep, never a cell): what each step
    offered, what it got acknowledged before it ended, and the tails of
    the batches that were due in it."""
    out, start = [], rec["t0"]
    for secs, rate in rec["steps"]:
        rows = [r for r in rec["rows"] if start <= r[T_DUE] < start + secs]
        ok = [r for r in rows if r[BASE] >= 0 and r[ERR] is None]
        ms = sorted((r[T_ACK] - r[T_DUE]) * 1e3 for r in ok) or [drain_s * 1e3]
        out.append({
            "offered_batches_per_s": rate, "due": len(rows),
            "acked_in_step_per_s": sum(
                1 for r in rec["rows"]
                if r[BASE] >= 0 and start <= r[T_ACK] < start + secs) / secs,
            "produce_p50_ms": round(percentile(ms, 0.5), 1),
            "produce_p95_ms": round(percentile(ms, 0.95), 1),
            "batches_a_request": round(
                sum(r[IN_REQUEST] for r in ok) / max(1, len(ok)), 2),
        })
        start += secs
    return out


class LoadGenerator:
    """The load generator in a fresh interpreter that can never take
    the chip, started before set-up so that its imports cost the run
    nothing, and given its spec on standard input when set-up is done."""

    def __init__(self) -> None:
        self.proc = None

    async def spawn(self) -> None:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        for k in (*SWITCHES, *DEVPLANE_ENV, *DEVPLANE_TRACED_ENV, "XLA_FLAGS"):
            env.pop(k, None)
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "benchmark.loadgen", cwd=ROOT, env=env,
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
        )

    async def drive(self, spec: dict, on_line, limit_s: float) -> int:
        """Hand over the spec, call `on_line` for each line the
        generator prints, and return its exit code (-1 past `limit_s`)."""
        proc = self.proc
        proc.stdin.write(json.dumps(spec).encode() + b"\n")
        await proc.stdin.drain()

        async def pump() -> None:
            async for raw in proc.stdout:
                await on_line(raw.decode("utf-8", "replace").strip())

        try:
            await asyncio.wait_for(pump(), limit_s)
            return await asyncio.wait_for(proc.wait(), 30)
        except asyncio.TimeoutError:
            log("load generator overran its limit")
            return -1

    async def close(self) -> None:
        if self.proc is not None and self.proc.returncode is None:
            self.proc.kill()
            await self.proc.wait()


async def run_cell(args, loaded: dict, device: dict) -> dict:
    from benchmark import cluster, compare
    from redpanda_tpu.observability import devplane

    cell, config, traffic = loaded["cell"], loaded["config"], loaded["traffic"]
    dry = args.cpu_dry_run
    config = cluster.sized(config, dry, args.control)
    traffic = cluster.toy_traffic(traffic, dry)
    tpl = resolve(traffic["templates"]["maker"], "templates")(args.seed, traffic, config)
    out_dir = os.path.join(HERE, "out")
    os.makedirs(out_dir, exist_ok=True)
    data_dir = os.path.join(ROOT, ".bench_data", cell["name"])
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    fstype = fs_type(data_dir)
    log(f"data directory {data_dir} on {fstype}")
    if fstype in ("tmpfs", "ramfs"):
        raise SystemExit(
            "benchmark: the data directory is memory-backed: fsync would be "
            "free and acks=all would prove nothing"
        )
    rec_path = os.path.join(out_dir, cell["name"] + ".records.json")
    trace_dir = os.path.join(out_dir, cell["name"] + ".trace")
    shutil.rmtree(trace_dir, ignore_errors=True)

    loadgen = LoadGenerator()
    await loadgen.spawn()
    brokers = cluster.make_brokers(config, data_dir)
    if args.control == "flush_lagged":
        cluster.lag_flushes()
    laps = {"imports_s": args.t_imported - T_START}
    window: dict = {"flush": compare.FlushWitness(brokers, config)}
    trace_task = None
    try:
        t = time.monotonic()
        cluster.reserve(brokers, config)
        for name in config["warm"]:
            resolve(name, "warmers")(brokers, config, traffic, tpl)
        laps["warm_s"] = time.monotonic() - t
        t = time.monotonic()
        bootstrap = await cluster.start(brokers, config)
        laps["boot_and_create_s"] = time.monotonic() - t
        t = time.monotonic()
        await cluster.first_ack_everywhere(bootstrap, config, tpl)
        laps["first_ack_everywhere_s"] = time.monotonic() - t
        laps["elections_until_first_ack"] = cluster.elections(brokers)

        async def traced(t0: float, seconds: float) -> None:
            import jax

            span = min(TRACE_SECONDS, seconds / 4)
            await asyncio.sleep(max(0.0, t0 + (seconds - span) / 2 - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            await asyncio.to_thread(
                jax.profiler.start_trace, trace_dir, profiler_options=opts
            )
            window["traced"] = [time.monotonic(), 0.0]
            await asyncio.sleep(span)
            window["traced"][1] = time.monotonic()
            await asyncio.to_thread(jax.profiler.stop_trace)

        async def on_line(line: str) -> None:
            nonlocal trace_task
            word, _, rest = line.partition(" ")
            if word == "acked":
                window["flush"].on_ack(*json.loads(rest))
            elif word == "window_start":
                devplane.reset()
                window["reset_at"] = time.monotonic()
                window["t0"] = float(rest)
                window["elections0"] = cluster.elections(brokers)
                if args.trace:
                    trace_task = asyncio.ensure_future(
                        traced(window["t0"], args.seconds))
            elif word == "window_end":
                window["devplane"] = devplane.status()
                # the seconds the span store and the counters cover
                window["devplane_s"] = time.monotonic() - window["reset_at"]
                window["elections1"] = cluster.elections(brokers)
                window["memory_peak_bytes"] = memory_peak_bytes()

        spec = {
            "bootstrap": bootstrap, "topics": config["topics"],
            "config": config, "traffic": traffic, "seed": args.seed,
            "seconds": args.seconds, "out": rec_path,
        }
        drain = float(traffic["drain_s"])
        t = time.monotonic()
        rc = await loadgen.drive(
            spec, on_line, 60 + args.seconds + 3 * drain + 30
        )
        if trace_task is not None:
            await trace_task
        if rc != 0 or "devplane" not in window:
            raise RuntimeError(f"load generator exited {rc}")
        laps["loadgen_arm_s"] = window["t0"] - t
        rec = load_json(rec_path)
        reduced = reduce_records(rec, drain)
        t = time.monotonic()
        checks = await compare.after_window(
            brokers, config, rec, tpl, window, device, args.seed, dry,
        )
        laps["compare_s"] = time.monotonic() - t
    finally:
        if trace_task is not None and not trace_task.done():
            trace_task.cancel()
        await loadgen.close()
        await cluster.stop(brokers)
        shutil.rmtree(data_dir, ignore_errors=True)
        if os.path.exists(rec_path):
            os.remove(rec_path)

    facts = {
        "laps": laps, "window": window, "rec": rec, "reduced": reduced,
        "checks": checks, "lanes": cluster.lanes(brokers), "templates": tpl,
        "fstype": fstype, "trace_dir": trace_dir, "config": config,
        "traffic": traffic,
    }
    return build_result(args, loaded, device, facts)


def build_result(args, loaded: dict, device: dict, facts: dict) -> dict:
    """The run's last line: the contract's keys first, then `detail` for
    whoever reads a run by hand, then `checks`, last."""
    from benchmark.readers import hostspans

    window, rec, reduced = facts["window"], facts["rec"], facts["reduced"]
    elections = window["elections1"] - window["elections0"]
    result = {
        "correct": all(c["ok"] for c in facts["checks"]),
        "attempted": reduced["attempted"],
        "failed": reduced["failed"],
        "metrics": {},
        "device": {**device, "memory_peak_bytes": window["memory_peak_bytes"]},
    }
    setup_s = window["t0"] - T_START
    if not args.trace:
        values = {**reduced["metrics"], "setup_s": setup_s}
        for m in loaded["end_to_end"]:
            if m["name"] in values:
                result["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
    else:
        read_layers(args, loaded, device, facts, result)
    if args.cpu_dry_run:
        result["dry_run"] = True
    if args.control:
        result["control"] = args.control
    result["detail"] = {
        **{k: round(v, 3) for k, v in facts["laps"].items()},
        "setup_s": round(setup_s, 3),
        "data_dir_fstype": facts["fstype"],
        "elections_in_window": elections,
        "unanswered": rec["unanswered"], "requests": rec["requests"],
        "fetches": rec["fetches"], "fetch_errors": rec["fetch_errors"][:5],
        "retried": sum(1 for r in rec["rows"] if r[8] > 1),
        **reduced.get("tails", {}),
        # the medians of every cell, also of one whose `metrics` leave
        # them out (over saturation they are the queue's length)
        **{k: round(v, 4) for k, v in reduced["metrics"].items()
           if k.endswith("_p50_ms")},
        **reduced["load"],
        # a rate the system sustains reads alike in every quarter
        "produce_p50_ms_by_quarter": quarters(rec),
        "never_fetched": [
            [r[1], r[BASE], round(r[T_DUE] - rec["t0"], 3),
             round(r[T_ACK] - rec["t0"], 3), r[8]]
            for r in rec["rows"] if r[BASE] >= 0 and r[ERR] is None and r[GOT] == -2
        ][:5],
        # how late a closed batch was first sent (over saturation: the
        # wait for one of the connection's requests in flight), how late
        # the generator handed it to its sender, and the generator's CPU
        "generator_late_p95_ms": round(
            1e3 * percentile(rec["late_s"] or [0.0], 0.95), 3),
        "generator_handed_late_p95_ms": round(
            1e3 * percentile(rec.get("handed_late_s") or [0.0], 0.95), 3),
        "generator_cpu_share": round(rec.get("generator_cpu_share", 0.0), 3),
        "sampled_dispatches": {
            k: v["count"] for k, v in window["devplane"].get("kernels", {}).items()},
        "dispatch_p50_ms": {
            k: v["p50_ms"] for k, v in window["devplane"].get("kernels", {}).items()
            if v["count"]},
        "sample_every": window["devplane"].get("sample_every"),
        "compiles_in_window": hostspans.compiles_in_window(
            {"devplane": window["devplane"]}, {}),
        "spans_dropped": window["devplane"].get("spans_dropped"),
        # the span store's aggregates, which every run keeps: seconds of
        # self time by span name and kind over the window (the timed run's
        # are the loop's shares without the probe waiting on a dispatch)
        "span_self_s": {
            name: [a["kind"], a["count"], round(a["self_s"], 4)]
            for name, a in (window["devplane"].get("host") or {}).items()},
        "transfer_bytes": window["devplane"].get("transfer_bytes"),
        "clients": rec["clients"],
        # a batch as sent, and as the median fetch returned it: apart
        # where the broker recompresses
        "batch_bytes": {
            "sent": len(facts["templates"][0].wire),
            "stored_p50": percentile(
                [r[GOT_BYTES] for r in rec["rows"] if r[GOT] >= 0] or [0], 0.5)},
    }
    if len(rec["steps"]) > 1:
        result["detail"]["steps"] = by_step(rec, float(facts["traffic"]["drain_s"]))
    # what was compared, each number beside its limit: last in the line
    result["checks"] = {
        c["name"]: {"value": c["value"], "limit": c["limit"]}
        for c in facts["checks"]
    }
    return result


def read_layers(args, loaded: dict, device: dict, facts: dict, result: dict) -> None:
    """The traced run's per-layer metrics, each from its own reader,
    with the device's busy seconds and the breakdown."""
    from benchmark import trace as tr

    window = facts["window"]
    peaks = load_json(HERE, "peaks.json")
    if device["kind"] not in peaks and not args.cpu_dry_run:
        raise KeyError(f"no peaks for device kind {device['kind']!r}")
    traced = None
    try:
        traced = tr.load_xplane(facts["trace_dir"])
    except FileNotFoundError as e:
        log(f"no trace to read: {e}")
    finally:
        shutil.rmtree(facts["trace_dir"], ignore_errors=True)
    if args.dump_trace and traced is not None:
        with open(args.dump_trace, "w") as f:
            json.dump(tr.sample(traced), f)
    t_a, t_b = window.get("traced", (0.0, 0.0))
    ctx = {
        "devplane": window["devplane"],
        "devplane_s": window["devplane_s"],
        "acked_payload_bytes": facts["reduced"]["acked_payload_bytes"],
        "elections_in_window": window["elections1"] - window["elections0"],
        "trace": traced,
        "lanes": facts["lanes"],
        "peaks": peaks.get(device["kind"]),
        "config": facts["config"],
        "traffic": facts["traffic"],
        "templates": facts["templates"],
        # the length, as stored, of every batch that a fetch returned in
        # the traced seconds and that came back
        "fetched_in_trace": [
            r[GOT_BYTES] for r in facts["rec"]["rows"]
            if r[GOT] >= 0 and t_a <= r[T_FETCH] <= t_b
        ],
    }
    for m in loaded["per_layer"]:
        v = resolve(m["reader"], "readers")(ctx, m.get("params", {}))
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    if traced is not None:
        busy, win = tr.busy_and_window(traced)
        result["device"]["busy_s"] = busy
        result["device"]["window_s"] = win
        result["breakdown"] = {
            "device_ops": tr.top_ops(traced),
            "idle_gaps": tr.idle_gaps(traced),
        }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="rehearsal only: toy sizes on the CPU")
    ap.add_argument("--dump-trace", metavar="FILE",
                    help="with --trace 1: also write the trace's planes, "
                    "lines and device events to FILE, for reading by hand")
    ap.add_argument("--traffic-file", metavar="FILE",
                    help="for a sweep, never a cell: drive this traffic file "
                    "in place of the cell's")
    ap.add_argument("--manifest", metavar="FILE",
                    help="for the builder and the tests, never the driver: "
                    "find the cell in FILE, laid over BENCHMARK.json, and its "
                    "configuration and traffic files beside FILE")
    ap.add_argument("--control", choices=CONTROLS,
                    help="run with one stated guarantee broken; must come "
                    "out as not correct")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "redpanda_tpu")):
        log(f"{ROOT} holds no redpanda_tpu/: there is no system to measure")
        return EXIT_NOT_A_CHECKOUT
    try:
        loaded = load_cell(args.workload, args.traffic_file, args.manifest)
    except (KeyError, OSError, ValueError) as e:
        log(f"cannot load the cell: {e!r}")
        return EXIT_BAD_CELL

    for k in SWITCHES:
        os.environ.pop(k, None)
    if args.control != "device_off":
        os.environ.update(loaded["config"]["env"])
    os.environ.pop("RP_DEVPLANE_SAMPLE", None)
    os.environ.update(DEVPLANE_ENV)
    if args.trace:
        os.environ.update(DEVPLANE_TRACED_ENV)
    if args.cpu_dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax

    import redpanda_tpu  # noqa: F401  (places the compile cache)
    from redpanda_tpu.utils import native

    # also the small programs: nothing compiles twice in a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = require_platform(args.cpu_dry_run, loaded["cell"]["chips"])
    if native.load() is None:
        log("the native library did not load: this benchmark never runs on "
            "the pure-Python degradation")
        return EXIT_NOT_A_CHECKOUT
    args.t_imported = time.monotonic()
    result = asyncio.run(run_cell(args, loaded, device))
    sys.stdout.flush()
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    log(f"correct: {result['correct']}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
