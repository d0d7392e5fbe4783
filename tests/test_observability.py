"""Observability layer: Prometheus /metrics scrape shape, histogram
bucket semantics, and flight-recorder slow-request capture.

Reference test model: the reference asserts its probe wiring the same
way — scrape the endpoint and parse the exposition text (application.cc
/metrics), then drive load and check the latency families moved
(raft/probe.cc, kafka latency_probe.h). The flight recorder has no
reference twin (SURVEY §5.1); its tests pin the ring/freezer contract
directly and end-to-end under an injected NemesisNet delay.
"""

import asyncio
import contextlib
import json
import os
import re

import pytest

from redpanda_tpu.app import Broker, BrokerConfig
from redpanda_tpu.kafka.client import KafkaClient
from redpanda_tpu.metrics import _BOUNDS, HistogramChild, MetricsRegistry
from redpanda_tpu.observability import trace
from redpanda_tpu.observability.trace import FlightRecorder, span
from redpanda_tpu.rpc.loopback import LoopbackNetwork, NemesisSchedule, NetRule

# the recorder tests exercise live span capture; under RP_TRACE=0 the
# whole layer is a no-op BY CONTRACT (verify.sh runs this module both
# ways — the /metrics tests must pass with tracing killed)
needs_trace = pytest.mark.skipif(
    not trace.ENABLED, reason="RP_TRACE=0: flight recorder disabled"
)

from test_admin_server import http  # shared minimal HTTP client


@contextlib.asynccontextmanager
async def cluster(tmp_path, n=3):
    net = LoopbackNetwork()
    members = list(range(n))
    brokers = [
        Broker(
            BrokerConfig(
                node_id=i,
                data_dir=str(tmp_path / f"n{i}"),
                members=members,
                election_timeout_s=0.15,
                heartbeat_interval_s=0.03,
            ),
            loopback=net,
        )
        for i in members
    ]
    for b in brokers:
        await b.start()
    addrs = {b.node_id: b.kafka_advertised for b in brokers}
    for b in brokers:
        b.config.peer_kafka_addresses = addrs
    try:
        await brokers[0].wait_controller_leader()
        yield net, brokers
    finally:
        for b in brokers:
            await b.stop()


# -- exposition-text parsing ------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$"
)


def parse_prometheus(text: str):
    """(types, samples): metric name -> TYPE, and a list of
    (name, labels_dict, float_value). Raises on malformed lines —
    the test doubles as an exposition-format lint."""
    types: dict[str, str] = {}
    samples: list[tuple[str, dict, float]] = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed exposition line: {line!r}"
        labels = {}
        if m.group(2):
            for part in re.findall(r'(\w+)="([^"]*)"', m.group(2)):
                labels[part[0]] = part[1]
        value = float("inf") if m.group(3) == "+Inf" else float(m.group(3))
        samples.append((m.group(1), labels, value))
    return types, samples


def _bucket_series(samples, family):
    """label-set (minus le) -> [(le_float, cum_count)] sorted by le."""
    out: dict[tuple, list[tuple[float, float]]] = {}
    for name, labels, value in samples:
        if name != family + "_bucket":
            continue
        le = labels["le"]
        key = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
        out.setdefault(key, []).append(
            (float("inf") if le == "+Inf" else float(le), value)
        )
    for series in out.values():
        series.sort(key=lambda p: p[0])
    return out


# -- /metrics end-to-end ----------------------------------------------


async def _scrape_after_load(tmp_path):
    """One /metrics text per broker. The kafka stage probe only moves
    on the broker that served the request, and partition leadership is
    election-order dependent — scraping every broker keeps the
    "histograms moved" assertions deterministic."""
    async with cluster(tmp_path) as (_net, brokers):
        client = KafkaClient([b.kafka_advertised for b in brokers])
        try:
            await client.create_topic("obs", partitions=2, replication_factor=3)
            for i in range(10):
                await client.produce("obs", i % 2, [(None, b"v%d" % i)])
            assert await client.fetch("obs", 0, 0) != []
        finally:
            await client.close()
        texts = []
        for b in brokers:
            st, text = await http(b.admin.address, "GET", "/metrics")
            assert st == 200
            texts.append(text.decode() if isinstance(text, bytes) else text)
        return texts


def test_metrics_scrape_parses_and_histograms_move(tmp_path):
    texts = asyncio.run(_scrape_after_load(tmp_path))
    types: dict = {}
    samples: list = []
    for text in texts:
        t, s = parse_prometheus(text)
        types.update(t)
        samples.extend(s)

    # the new probe families are present and typed histogram
    for family in (
        "redpanda_tpu_kafka_request_stage_seconds",
        "redpanda_tpu_raft_append_seconds",
        "redpanda_tpu_raft_commit_seconds",
        "redpanda_tpu_storage_segment_append_seconds",
        "redpanda_tpu_storage_flush_wait_seconds",
    ):
        assert types.get(family) == "histogram", family
        counts = [
            v for n, l, v in samples if n == family + "_count"
        ]
        assert counts and sum(counts) > 0, f"{family} never observed"

    # labeled kafka stage family: produce went through decode,
    # dispatch and done, with a concrete path label
    stage_labels = {
        (l.get("api"), l.get("stage"))
        for n, l, _ in samples
        if n == "redpanda_tpu_kafka_request_stage_seconds_count"
        and l.get("api") == "produce"
    }
    assert {"decode", "dispatch", "done"} <= {s for _, s in stage_labels}
    paths = {
        l.get("path")
        for n, l, _ in samples
        if n == "redpanda_tpu_kafka_request_stage_seconds_count"
    }
    assert paths <= {"native", "python"} and paths


def test_metrics_bucket_monotonicity(tmp_path):
    texts = asyncio.run(_scrape_after_load(tmp_path))
    checked = 0
    for text in texts:  # each registry is internally consistent
        types, samples = parse_prometheus(text)
        for family, kind in types.items():
            if kind != "histogram":
                continue
            series = _bucket_series(samples, family)
            for key, buckets in series.items():
                # cumulative counts never decrease, +Inf terminates
                cums = [c for _, c in buckets]
                assert cums == sorted(cums), (family, key)
                assert buckets[-1][0] == float("inf"), (family, key)
                # _count agrees with the +Inf bucket
                label_dict = dict(key)
                count = [
                    v
                    for n, l, v in samples
                    if n == family + "_count" and l == label_dict
                ]
                assert count == [buckets[-1][1]], (family, key)
                checked += 1
    assert checked > 0


# -- histogram unit semantics -----------------------------------------


def test_histogram_observe_bucket_placement():
    # each sample must land in the bucket whose (prev, bound] range
    # contains it — the octave arithmetic off-by-one regression test
    for s in (1e-5, 1e-3, 0.0017, 0.1, 1.0, 7.5):
        c = HistogramChild()
        c.observe(s)
        (i,) = [j for j, n in enumerate(c._buckets) if n]
        assert s <= _BOUNDS[i], (s, i)
        if i > 0:
            # lower edge is the previous bucket's bound (inclusive:
            # an exact power of two opens its octave's first bucket)
            assert s >= _BOUNDS[i - 1], (s, i)


def test_histogram_quantile_upper_bound_convention():
    # HdrHistogram convention: the quantile is the containing bucket's
    # upper bound, so observed <= quantile(1.0) always holds
    c = HistogramChild()
    samples = [0.0012, 0.0031, 0.0155, 0.0508]
    for s in samples:
        c.observe(s)
    assert c.quantile(1.0) >= max(samples)
    assert c.quantile(0.25) >= min(samples)
    # quantiles are monotone in q
    qs = [c.quantile(q) for q in (0.1, 0.5, 0.9, 0.99, 1.0)]
    assert qs == sorted(qs)


def test_histogram_labeled_children_merge():
    m = MetricsRegistry(prefix="t")
    h = m.histogram("lat_seconds", "x")
    h.labels(path="native").observe(0.001)
    h.labels(path="python").observe(0.004)
    snap = h.snapshot()
    assert snap["count"] == 2
    assert set(snap["series"]) == {'{path="native"}', '{path="python"}'}
    # render: one _bucket family per label set plus merged default
    out = "\n".join(h.render())
    assert 'path="native"' in out and 'path="python"' in out


# -- flight recorder: unit contract -----------------------------------


@needs_trace
def test_flight_recorder_ring_and_freezer():
    rec = FlightRecorder(ring_capacity=4, slow_ms=5.0, node_id=7)
    for i in range(6):
        with rec.span("req", idx=i):
            pass
    tail = rec.ring_tail()
    assert len(tail) == 4  # ring wrapped: only the last 4 trees
    assert rec.trees_total == 6
    assert rec.frozen() == []  # nothing crossed 5ms

    rec.slow_ns = 0  # everything is now "slow"
    with rec.span("slow-req") as root:
        with span("child", parent=root):
            pass
    frozen = rec.frozen()
    assert len(frozen) == 1 and rec.frozen_total == 1
    tree = frozen[0]
    assert tree["root"] == "slow-req"
    names = {s["name"] for s in tree["spans"]}
    assert names == {"slow-req", "child"}
    child = next(s for s in tree["spans"] if s["name"] == "child")
    root_span = next(s for s in tree["spans"] if s["name"] == "slow-req")
    assert child["parent"] == root_span["id"]


@needs_trace
def test_flight_recorder_dump_is_json_ready():
    rec = FlightRecorder(ring_capacity=2, slow_ms=1000.0)
    with rec.span("a", k="v"):
        pass
    rec.record_event("nemesis", action="delay", src=0, dst=1)
    dump = rec.dump()
    json.dumps(dump)  # must serialize as-is for /v1/debug/traces
    assert dump["trees_total"] == 1
    assert [e["name"] for e in dump["events"]] == ["nemesis"]


# -- flight recorder: slow capture under injected delay ---------------


async def _slow_capture(tmp_path):
    async with cluster(tmp_path) as (net, brokers):
        client = KafkaClient([b.kafka_advertised for b in brokers])
        try:
            await client.create_topic("slow", partitions=1, replication_factor=3)
            await client.produce("slow", 0, [(None, b"warm")])

            # freeze anything over 20ms, then make raft RPC slow enough
            # that an acks=-1 produce must cross the threshold
            for b in brokers:
                b.recorder.slow_ns = int(20e6)
            net.install_nemesis(
                NemesisSchedule(
                    rules=[NetRule(action="delay", delay_s=0.05, count=200)],
                    seed=3,
                )
            )
            await client.produce("slow", 0, [(None, b"slowed")])
            net.clear_nemesis()
        finally:
            await client.close()

        # one of the brokers (the partition leader) froze the produce
        dumps = []
        for b in brokers:
            st, body = await http(
                b.admin.address, "GET", "/v1/debug/traces?tail=10"
            )
            assert st == 200
            dumps.append(body)
        return dumps


@needs_trace
def test_debug_traces_freezes_slow_produce(tmp_path):
    dumps = asyncio.run(_slow_capture(tmp_path))
    frozen = [t for d in dumps for t in d["frozen"]]
    produce_trees = [t for t in frozen if t["root"] == "kafka.produce"]
    assert produce_trees, "no slow produce tree frozen by any broker"
    tree = produce_trees[-1]
    assert tree["dur_ns"] >= 20e6
    names = {s["name"] for s in tree["spans"]}
    assert "kafka.produce" in names
    # the nemesis firing is visible in the fault-event log
    events = [e for d in dumps for e in d["events"]]
    assert any(e["name"] == "nemesis" for e in events)
    # ring tail always returns trees, frozen or not
    assert any(d["ring"] for d in dumps)


@needs_trace
def test_log_viewer_renders_trace_dump(tmp_path):
    import io

    from tools.log_viewer import dump_traces

    rec = FlightRecorder(ring_capacity=4, slow_ms=0.0)
    with rec.span("kafka.produce", path="native") as root:
        with span("produce.dispatch", parent=root):
            pass
    path = tmp_path / "traces.json"
    path.write_text(json.dumps(rec.dump()))
    buf = io.StringIO()
    dump_traces(str(path), out=buf)
    text = buf.getvalue()
    assert "kafka.produce" in text and "produce.dispatch" in text
    assert "[SLOW]" in text  # slow_ms=0 froze it
    # aligned waterfall: every span row carries a bar column
    rows = [ln for ln in text.splitlines() if "|" in ln]
    assert len(rows) >= 2
    assert len({ln.index("|") for ln in rows}) == 1


# -- fleet plane: snapshots, merged scrape, stitched traces ------------


def _loaded_registry(shard_tag: str) -> MetricsRegistry:
    reg = MetricsRegistry()
    c = reg.counter("fleet_reqs_total", "requests")
    c.inc(3, api="produce")
    c.inc(1, api="fetch")
    reg.counter("fleet_idle_total", "never incremented")
    reg.gauge("fleet_depth", lambda: 7.0, "queue depth")
    h = reg.histogram("fleet_lat_seconds", "latency")
    h.labels(path=shard_tag).observe(0.002)
    h.labels(path=shard_tag).observe(0.04)
    return reg


def test_fleet_snapshot_serde_round_trip():
    from redpanda_tpu.observability import fleet

    reg = _loaded_registry("a")
    snap = fleet.snapshot_registry(reg, shard=1, node=0)
    back = fleet.RegistrySnapshot.decode(snap.encode())
    assert back.shard == 1 and back.node == 0
    # the decoded snapshot renders byte-identically to the original
    assert fleet.render_snapshot(back) == fleet.render_snapshot(snap)
    # an empty counter still ships a zero sample (shard visibility)
    idle = next(
        f for f in back.families
        if f.name == "redpanda_tpu_fleet_idle_total"
    )
    assert [(dict(s.labels), s.value) for s in idle.samples] == [({}, 0.0)]
    # histograms ship raw buckets, not quantiles
    hist = next(
        h for h in back.hists if h.name == "redpanda_tpu_fleet_lat_seconds"
    )
    assert sum(hist.series[0].buckets) == hist.series[0].count == 2


def test_fleet_render_labels_every_sample_with_shard():
    from redpanda_tpu.observability import fleet

    snaps = [
        fleet.snapshot_registry(_loaded_registry("x"), shard=0, node=0),
        fleet.snapshot_registry(_loaded_registry("y"), shard=1, node=0),
    ]
    text = fleet.render_fleet(snaps)
    types, samples = parse_prometheus(text)
    assert samples
    for name, labels, _value in samples:
        assert "shard" in labels, name
    shards = {l["shard"] for _n, l, _v in samples}
    assert shards == {"0", "1"}
    # HELP/TYPE once per family even though both shards carry it
    assert text.count("# TYPE redpanda_tpu_fleet_reqs_total counter") == 1
    # exposition stays parseable/monotone through the fleet merge path
    series = _bucket_series(samples, "redpanda_tpu_fleet_lat_seconds")
    assert len(series) == 2  # one per (path, shard)
    for _key, buckets in series.items():
        cums = [cnt for _le, cnt in buckets]
        assert cums == sorted(cums)


def test_fleet_merged_hist_equals_direct_merge():
    from redpanda_tpu.observability import fleet

    regs = [MetricsRegistry(), MetricsRegistry()]
    direct = HistogramChild()
    vals = [0.0011, 0.003, 0.0092, 0.017, 0.25, 0.0007, 0.08]
    for i, v in enumerate(vals):
        h = regs[i % 2].histogram("m_lat_seconds", "x")
        h.labels(path="p%d" % (i % 3)).observe(v)
        direct.observe(v)
    snaps = [
        fleet.snapshot_registry(r, shard=i) for i, r in enumerate(regs)
    ]
    merged = fleet.merged_hist(snaps, "redpanda_tpu_m_lat_seconds")
    assert merged is not None and merged._count == len(vals)
    for q in (0.5, 0.9, 0.99, 0.999):
        assert merged.quantile(q) == direct.quantile(q)
    assert fleet.merged_hist(snaps, "redpanda_tpu_nope") is None


@needs_trace
def test_trace_dump_envelope_round_trip():
    from redpanda_tpu.observability import fleet

    rec = FlightRecorder(ring_capacity=4, slow_ms=0.0, node_id=3, shard=2)
    with rec.span("kafka.produce", topic="t") as root:
        with span("raft.append", parent=root):
            pass
    rec.record_event("nemesis", action="delay")
    dump = rec.dump()
    td = fleet.dump_to_envelope(dump)
    back = fleet.envelope_to_dump(fleet.TraceDump.decode(td.encode()))
    assert back["node_id"] == 3 and back["shard"] == 2
    assert back["trees_total"] == dump["trees_total"]
    # slow_ms=0 froze the tree: the frozen/ring split survives the wire
    assert len(back["frozen"]) == 1 and len(back["ring"]) == 1
    spans = {s["name"]: s for s in back["ring"][0]["spans"]}
    assert set(spans) == {"kafka.produce", "raft.append"}
    assert spans["raft.append"]["parent"] == spans["kafka.produce"]["id"]
    assert spans["kafka.produce"]["tags"] == {"topic": "t"}
    assert [e["name"] for e in back["events"]] == ["nemesis"]
    json.dumps(back)  # /v1/debug/traces ships it as-is


@needs_trace
def test_stitch_trees_merges_cross_process_parts():
    import contextvars

    from redpanda_tpu.observability import fleet

    r0 = FlightRecorder(node_id=0, shard=0)
    r1 = FlightRecorder(node_id=0, shard=1)

    def remote_side(tid, sid):
        # an empty Context stands in for the worker process
        tok = trace.set_remote_parent(tid, sid, "shard0")
        try:
            with trace.span("ssx.dispatch", recorder=r1):
                with trace.span("raft.append", recorder=r1):
                    pass
        finally:
            trace.reset_remote_parent(tok)

    with trace.span("kafka.produce", recorder=r0):
        with trace.span("shard.forward", recorder=r0):
            tid, sid = trace.propagation_ctx()
            contextvars.Context().run(remote_side, tid, sid)

    trees = r0.dump()["ring"] + r1.dump()["ring"]
    stitched = fleet.stitch_trees(trees)
    assert len(stitched) == 1
    tree = stitched[0]
    assert tree["stitched"] and tree["parts"] == 2
    assert tree["root"] == "kafka.produce" and not tree["orphaned"]
    assert tree["shards"] == [0, 1]
    by_name = {s["name"]: s for s in tree["spans"]}
    assert by_name["raft.append"]["shard"] == 1
    assert by_name["kafka.produce"]["shard"] == 0
    # the continuation root resolves its parent inside the merged tree
    assert by_name["ssx.dispatch"]["parent"] == by_name["shard.forward"]["id"]
    assert by_name["ssx.dispatch"]["origin"] == "shard0"
    # single-part groups never stitch; trace_id 0 never groups
    assert fleet.stitch_trees(r0.dump()["ring"]) == []
    json.dumps(stitched)


async def _two_shard_fleet(tmp_path):
    from redpanda_tpu.ssx.sharded_broker import ShardedBroker

    cfg = BrokerConfig(
        node_id=0,
        data_dir=str(tmp_path / "n0"),
        members=[0],
        election_timeout_s=0.3,
        heartbeat_interval_s=0.05,
        enable_admin=True,
    )
    sb = ShardedBroker(cfg, n_shards=2)
    await sb.start()
    try:
        assert sb.active, f"unexpected stand-down: {sb.standdown}"
        c = KafkaClient([("127.0.0.1", sb.kafka_port)])
        try:
            deadline = asyncio.get_event_loop().time() + 30.0

            async def retry(fn):
                while True:
                    try:
                        return await fn()
                    except Exception:
                        if asyncio.get_event_loop().time() > deadline:
                            raise
                        await asyncio.sleep(0.2)

            await retry(
                lambda: c.create_topic("f", partitions=4, replication_factor=1)
            )
            while not sb.broker.shard_table.counts().get(1, 0):
                if asyncio.get_event_loop().time() > deadline:
                    raise TimeoutError("no partitions routed to shard 1")
                await asyncio.sleep(0.1)
            for p in range(4):
                await retry(
                    lambda p=p: c.produce("f", p, [(b"k", b"v%d" % p)])
                )
        finally:
            await c.close()

        addr = sb.broker.admin.address
        st, metrics_text = await http(addr, "GET", "/metrics")
        assert st == 200
        st, shard1_text = await http(addr, "GET", "/v1/shards/1/metrics")
        assert st == 200
        st404, _ = await http(addr, "GET", "/v1/shards/9/metrics")
        st_probes, probes = await http(addr, "GET", "/v1/debug/probes")
        assert st_probes == 200
        st_traces, traces = await http(addr, "GET", "/v1/debug/traces")
        assert st_traces == 200
        return metrics_text, shard1_text, st404, probes, traces
    finally:
        await sb.stop()


def test_two_shard_fleet_scrape_and_stitched_traces(tmp_path):
    """ISSUE 6 acceptance: under 2 shards, one /metrics scrape at shard
    0 returns merged samples with a `shard` label for every shard, the
    per-shard raw view serves, probes report liveness, and (tracing on)
    a forwarded produce stitches into one tree spanning 2 processes."""
    metrics_text, shard1_text, st404, probes, traces = asyncio.run(
        _two_shard_fleet(tmp_path)
    )
    if isinstance(metrics_text, bytes):
        metrics_text = metrics_text.decode()
    if isinstance(shard1_text, bytes):
        shard1_text = shard1_text.decode()

    _types, samples = parse_prometheus(metrics_text)
    shards_seen = {l.get("shard") for _n, l, _v in samples}
    assert {"0", "1"} <= shards_seen
    for _name, labels, _v in samples:
        assert "shard" in labels
    # the worker's kafka stage histogram is part of the merged view
    # only when its frontend took connections; its raft/storage
    # families always are
    worker_families = {
        n for n, l, _v in samples if l.get("shard") == "1"
    }
    assert any("raft" in n or "storage" in n for n in worker_families)

    # raw per-shard view: no shard label, families present
    _t1, s1_samples = parse_prometheus(shard1_text)
    assert s1_samples
    assert all("shard" not in l for _n, l, _v in s1_samples)
    assert st404 == 404

    # probes liveness block
    sh = probes["shards"]
    assert sh["n_shards"] == 2
    assert "1" in {str(k) for k in sh["alive"]}
    assert sh["failed"] is False

    # stitched cross-process produce (tracing on only)
    assert "node_id" in traces and "ring" in traces  # pre-PR6 keys stay
    if trace.ENABLED:
        assert str(1) in {str(k) for k in traces["shards"]}
        stitched = traces["stitched"]
        multi = [t for t in stitched if len(t.get("shards", [])) >= 2]
        assert multi, f"no stitched multi-process tree: {stitched!r}"
        spans = multi[-1]["spans"]
        assert {s.get("shard") for s in spans} >= {0, 1}


def test_cloud_probe_families_move_under_tiered_load(tmp_path):
    """The tiered read path's /metrics surface: drive produce ->
    archive -> evict -> cold fetch (with one injected transient store
    error so the retry counter moves) and require the cloud families
    to show up typed and non-zero."""
    from redpanda_tpu.cloud import (
        MemoryObjectStore,
        NemesisObjectStore,
        StoreFaultSchedule,
        StoreRule,
    )

    async def main():
        nem = NemesisObjectStore(MemoryObjectStore())
        b = Broker(
            BrokerConfig(
                node_id=0,
                data_dir=str(tmp_path / "n0"),
                members=[0],
                election_timeout_s=0.15,
                heartbeat_interval_s=0.03,
                housekeeping_interval_s=0,
                archival_interval_s=0,
            ),
            loopback=LoopbackNetwork(),
            object_store=nem,
        )
        await b.start()
        b.config.peer_kafka_addresses = {0: b.kafka_advertised}
        try:
            await b.wait_controller_leader()
            client = KafkaClient([b.kafka_advertised])
            await client.create_topic(
                "ct",
                partitions=1,
                replication_factor=1,
                configs={
                    "redpanda.remote.write": "true",
                    "redpanda.remote.read": "true",
                    "segment.bytes": "400",
                    "retention.bytes": "400",
                },
            )
            for i in range(12):
                await client.produce("ct", 0, [(b"k%d" % i, b"v%d" % i)])
            from redpanda_tpu.models.fundamental import kafka_ntp

            p = b.partition_manager.get(kafka_ntp("ct", 0))
            p.log.flush()
            await b.archival.run_once()
            b.storage.log_mgr.housekeeping()
            # one transient range-read error: the RetryingStore retry
            # loop fires on_retry -> the counter must move
            nem.install(
                StoreFaultSchedule(
                    rules=[StoreRule(op="get_range", action="error", count=1)],
                    seed=11,
                )
            )
            got = await client.fetch("ct", 0, 0, max_bytes=1 << 22)
            assert len(got) == 12
            await client.close()

            st, text = await http(b.admin.address, "GET", "/metrics")
            assert st == 200
            types, samples = parse_prometheus(
                text.decode() if isinstance(text, bytes) else text
            )
            assert types.get("redpanda_tpu_cloud_read_seconds") == "histogram"
            cold = [
                v
                for n, l, v in samples
                if n == "redpanda_tpu_cloud_read_seconds_count"
                and l.get("path") == "cold"
            ]
            assert cold and sum(cold) > 0, "cold read never observed"
            retries = [
                v
                for n, l, v in samples
                if n == "redpanda_tpu_cloud_op_retries_total"
            ]
            assert retries and sum(retries) > 0, "retry counter never moved"
            hyd = [
                v
                for n, _l, v in samples
                if n == "redpanda_tpu_cloud_hydrations_total"
            ]
            assert hyd and hyd[0] > 0
            for fam in (
                "redpanda_tpu_cloud_cache_bytes",
                "redpanda_tpu_cloud_cache_hits_total",
                "redpanda_tpu_cloud_cache_misses_total",
                "redpanda_tpu_cloud_degradation_events_total",
            ):
                assert fam in types, f"{fam} missing from /metrics"
        finally:
            await b.stop()

    asyncio.run(main())


# -- fork hygiene (PR-17 shard re-fork seam) ---------------------------
#
# spawn_shard (and the per-shard crash-restart respawn) forks the
# broker process; the span-id counter and the module-default recorder
# are copied by fork, so without _after_fork_child a child's stitched
# spans could collide with the parent's ids and its /v1/debug/traces
# would serve the parent's inherited trees as its own. The hook is
# registered via os.register_at_fork, so any fork — multiprocessing
# included — must come up clean.


def _fork_probe(q):
    r = trace._default_recorder
    inherited = {
        "trees_total": r.trees_total,
        "frozen": len(r._frozen),
        "ring": sum(1 for t in r._ring if t is not None),
        "events": len(r._events),
    }
    ids = []
    for _ in range(3):
        with span("child.work") as s:
            ids.append(s.span_id)
    q.put(
        {
            "pid": os.getpid(),
            "inherited": inherited,
            "ids": ids,
            "trees_after": r.trees_total,
        }
    )


@needs_trace
def test_fork_child_drops_inherited_trees_and_reseeds_ids():
    import multiprocessing as mp

    if not hasattr(os, "register_at_fork"):
        pytest.skip("platform without register_at_fork")
    with span("parent.seed"):
        pass
    with span("parent.marker") as s:
        parent_id = s.span_id
    assert trace._default_recorder.trees_total >= 2

    ctx = mp.get_context("fork")
    q = ctx.SimpleQueue()
    p = ctx.Process(target=_fork_probe, args=(q,))
    p.start()
    out = q.get()
    p.join(10)
    assert p.exitcode == 0

    # the child saw NONE of the parent's trees/events at startup
    assert out["inherited"] == {
        "trees_total": 0, "frozen": 0, "ring": 0, "events": 0,
    }
    # ...but its own recorder works: 3 fresh root trees recorded
    assert out["trees_after"] == 3
    # ids reseeded into the pid-disjoint range: (pid & 0x3FFFFF) << 40
    base = (out["pid"] & 0x3FFFFF) << 40
    for sid in out["ids"]:
        assert base < sid < base + (1 << 40), (hex(sid), hex(base))
    # and therefore cannot collide with the parent's counter
    assert parent_id not in out["ids"]


@needs_trace
def test_refork_children_span_ids_pairwise_disjoint():
    """Two successive forks (the crash-restart respawn shape): each
    child's id space is keyed on its OWN pid, so stitched trees
    collected from parent + both generations never collide."""
    import multiprocessing as mp

    if not hasattr(os, "register_at_fork"):
        pytest.skip("platform without register_at_fork")
    ctx = mp.get_context("fork")
    outs = []
    for _ in range(2):  # second fork = the respawned shard
        q = ctx.SimpleQueue()
        p = ctx.Process(target=_fork_probe, args=(q,))
        p.start()
        outs.append(q.get())
        p.join(10)
        assert p.exitcode == 0
    with span("parent.after") as s:
        parent_id = s.span_id

    a, b = (set(o["ids"]) for o in outs)
    assert outs[0]["pid"] != outs[1]["pid"]
    assert not a & b, "re-forked shard reused span ids"
    assert parent_id not in a | b
    # the parent counter stays in the low range (seeded at 1), the
    # children in their pid-shifted ranges — three disjoint id planes
    assert parent_id < (1 << 40)


# -- one span system: kinds, self time, the window store ----------------


@pytest.fixture
def window():
    """The process-global span store, emptied and keeping raw records
    for the test, left as it was found."""
    w = trace.WINDOW
    keep = w.keep_raw
    w.keep_raw = True
    w.reset()
    yield w
    w.keep_raw = keep
    w.reset()


def _raw(window, name):
    return [s for s in window.status()["spans"] if s[0] == name]


@needs_trace
def test_self_time_is_duration_less_what_children_cover(window):
    # hand-built tree on a made-up clock: parent [1000, 2000) with
    # children [1100, 1300), [1200, 1500) (overlapping), [1900, 2100)
    # (runs past the parent: clipped) and a grandchild that must not
    # count twice. Covered: 1100-1500 and 1900-2000 = 500 of 1000.
    with span("t.parent", "wait").begin(1000) as parent:
        with span("t.child").begin(1200) as child:
            trace.record("t.grandchild", "run", 1250, 1400)
            child.finish(end_ns=1500)
        trace.record("t.child", "run", 1100, 1300)
        trace.record("t.child", "run", 1900, 2100)
        parent.finish(end_ns=2000)
    host = window.status()["host"]
    assert host["t.parent"]["total_s"] == pytest.approx(1000e-9)
    assert host["t.parent"]["self_s"] == pytest.approx(500e-9)
    assert host["t.child"]["count"] == 3
    # 300 + 200 + 200 in all, less the grandchild's 150
    assert host["t.child"]["total_s"] == pytest.approx(700e-9)
    assert host["t.child"]["self_s"] == pytest.approx(550e-9)
    assert host["t.grandchild"]["self_s"] == pytest.approx(150e-9)


@needs_trace
def test_span_kinds_run_and_wait(window):
    rec = FlightRecorder(ring_capacity=2)
    with rec.span("t.root", "wait") as root:
        with span("t.default"):
            pass
        trace.record("t.posthoc", "wait", root.start_ns, root.start_ns + 10)
    kinds = {s["name"]: s["kind"] for s in rec.ring_tail()[-1]["spans"]}
    assert kinds == {"t.root": "wait", "t.default": "run", "t.posthoc": "wait"}
    host = window.status()["host"]
    assert {n: host[n]["kind"] for n in kinds} == kinds
    assert {s[0]: s[1] for s in window.status()["spans"]} == kinds


@needs_trace
def test_window_raw_records_cap_and_dropped(window, monkeypatch):
    monkeypatch.setattr(trace.WindowStore, "RAW_CAP", 5)
    with span("t.root") as root:
        for _ in range(8):
            with span("t.leaf", n=1):
                pass
    st = window.status()
    assert len(st["spans"]) == 5 and st["spans_dropped"] == 4
    # the aggregates never drop
    assert st["host"]["t.leaf"]["count"] == 8
    # [name, kind, start_ns, dur_ns, id, parent, trace_id, tags]
    leaf = st["spans"][0]
    assert leaf[:2] == ["t.leaf", "run"] and leaf[7] == {"n": 1}
    assert leaf[5] == root.span_id and leaf[6] == root.trace_id
    json.dumps(st)
    # reset empties everything; without keep_raw only aggregates stay
    window.keep_raw = False
    window.reset()
    with span("t.leaf"):
        pass
    st = window.status()
    assert st["spans"] == [] and st["spans_dropped"] == 0
    assert st["host"]["t.leaf"]["count"] == 1


@needs_trace
def test_finished_span_in_a_task_context_adopts_no_children(window):
    """A task keeps the context it was created under: a span opened in
    it after that context's span finished is a root of its own."""

    async def main():
        with span("t.request") as req:
            task = asyncio.ensure_future(later())
        await task
        return req

    async def later():
        await asyncio.sleep(0)
        with span("t.later") as s:
            return s

    req = asyncio.run(main())
    (later_row,) = _raw(window, "t.later")
    assert req.dur_ns >= 0
    assert later_row[5] == 0 and later_row[6] != req.trace_id


@needs_trace
def test_phases_are_consecutive_children(window):
    with span("t.tick", "wait") as tick:
        ph = trace.phases()
        ph.next("t.build")
        ph.next("t.send", "wait")
        with span("t.inner"):
            pass
        ph.tag(peers=2)
        ph.next("t.scan")
        ph.end()
        assert trace.current_span() is tick
    rows = {s[0]: s for s in window.status()["spans"]}
    assert [rows[n][5] for n in ("t.build", "t.send", "t.scan")] == [
        tick.span_id] * 3
    assert rows["t.inner"][5] == rows["t.send"][4]
    assert rows["t.send"][1] == "wait" and rows["t.send"][7] == {"peers": 2}
    assert rows["t.build"][2] + rows["t.build"][3] <= rows["t.send"][2]


def test_rp_trace_off_every_site_gets_the_shared_noop(monkeypatch):
    monkeypatch.setattr(trace, "ENABLED", False)
    w = trace.WINDOW
    before = w.status()
    noop = trace._NOOP
    rec = FlightRecorder()
    assert span("x") is noop and span("x", "wait", k=1) is noop
    assert rec.span("x", "wait") is noop
    assert trace.phases() is noop
    assert span("x").begin(5) is noop
    assert trace.current_span() is None and trace.handoff_span() is None
    trace.record("x", "run", 1, 2)
    with span("x") as s:
        s.tag(a=1)
    ph = trace.phases()
    ph.next("x")
    ph.tag(a=1)
    ph.end()
    noop.finish(end_ns=3)

    async def probe():
        trace.LoopLagProbe.acquire()
        assert trace.LoopLagProbe._by_loop == {}
        trace.LoopLagProbe.release()

    asyncio.run(probe())
    assert w.status() == before and rec.trees_total == 0


@needs_trace
def test_loop_lag_probe_sees_a_blocked_loop_and_is_shared(tmp_path, window):
    import time as _time

    async def main():
        async with cluster(tmp_path, n=2):
            loop = asyncio.get_running_loop()
            probe = trace.LoopLagProbe._by_loop[loop]
            # two brokers on one loop share one probe
            assert len(trace.LoopLagProbe._by_loop) == 1
            assert probe._refs == 2
            window.reset()
            # two timers of a quiet loop, however long a loaded core
            # takes to run them
            deadline = loop.time() + 5.0
            while (
                window.status()["loop"]["samples"] < 2
                and loop.time() < deadline
            ):
                await asyncio.sleep(0.01)
            quiet = window.status()["loop"]
            _time.sleep(0.05)  # hold the loop: every timer runs late
            await asyncio.sleep(0.03)
            return quiet, window.status()["loop"], probe, loop

    quiet, loud, probe, loop = asyncio.run(main())
    assert quiet["samples"] >= 2
    assert loud["samples"] > quiet["samples"]
    assert loud["lag_max_ms"] >= 40.0  # due 10 ms in, ran after 50
    assert loud["lag_p99_ms"] >= 40.0 > quiet["lag_p50_ms"]
    # both brokers stopped: the probe is gone with its timer
    assert loop not in trace.LoopLagProbe._by_loop and probe._refs == 0


# -- the loop traced from inside: the probe's selector hook (PR 37) -------


def _quiet_probe(loop):
    """Acquire the loop's probe and cancel its own 10 ms timer, so that
    the only timers a case sees are its own."""
    trace.LoopLagProbe.acquire()
    trace.LoopLagProbe._by_loop[loop]._handle.cancel()


@needs_trace
def test_loop_probe_counts_work_as_awake_and_a_sleep_as_asleep(window):
    import time as _time

    async def main():
        loop = asyncio.get_running_loop()
        _quiet_probe(loop)
        try:
            window.reset()
            loop.call_soon(_time.sleep, 0.02)  # a callback holds the loop
            await asyncio.sleep(0.001)
            held = dict(window.status()["loop"])
            window.reset()
            await asyncio.sleep(0.05)
            await asyncio.sleep(0)  # one more pass: its select's entry
            return held, window.status()["loop"]
        finally:
            trace.LoopLagProbe.release()

    held, slept = asyncio.run(main())
    assert held["awake_s"] >= 0.02 and held["asleep_s"] < held["awake_s"]
    assert slept["asleep_s"] >= 0.05 and slept["awake_s"] < slept["asleep_s"]
    assert held["passes"] >= 1 and slept["passes"] >= 1


@needs_trace
@pytest.mark.skipif(not hasattr(__import__("selectors"), "EpollSelector"),
                    reason="epoll is Linux's")
def test_a_timer_under_a_millisecond_wakes_a_whole_millisecond_late_under_epoll(window):
    import selectors

    async def main():
        loop = asyncio.get_running_loop()
        assert isinstance(loop._selector, selectors.EpollSelector)
        _quiet_probe(loop)
        try:
            window.reset()
            for _ in range(5):
                due = loop.create_future()
                loop.call_later(0.0003, due.set_result, None)
                await due
            return window.status()["loop"]
        finally:
            trace.LoopLagProbe.release()

    with asyncio.Runner(loop_factory=lambda: asyncio.SelectorEventLoop(
            selectors.EpollSelector())) as runner:
        got = runner.run(main())
    # epoll waits whole milliseconds: 0.3 ms asked for is 1 ms slept
    assert got["wake_late_count"] >= 1
    assert got["wake_late_p50_ms"] >= 0.6
    # what is past the whole millisecond is the rest of the lateness
    assert got["wake_late_rest_p50_ms"] <= got["wake_late_p50_ms"]


@needs_trace
def test_the_probe_shadows_select_once_and_the_last_release_restores_it(window):
    async def main():
        sel = asyncio.get_running_loop()._selector
        original = sel.select
        trace.LoopLagProbe.acquire()
        trace.LoopLagProbe.acquire()  # two brokers on one loop: one hook
        hooked = sel.select
        assert "select" in vars(sel) and hooked != original
        trace.LoopLagProbe.release()
        assert sel.select is hooked
        trace.LoopLagProbe.release()
        assert "select" not in vars(sel) and sel.select == original

    asyncio.run(main())


def test_rp_trace_off_installs_no_selector_hook(monkeypatch):
    monkeypatch.setattr(trace, "ENABLED", False)

    async def main():
        sel = asyncio.get_running_loop()._selector
        before = trace.WINDOW.status()["loop"]["passes"]
        trace.LoopLagProbe.acquire()
        assert "select" not in vars(sel)
        await asyncio.sleep(0.01)
        assert trace.WINDOW.status()["loop"]["passes"] == before
        trace.LoopLagProbe.release()

    asyncio.run(main())


@needs_trace
def test_sleeps_are_kept_in_a_traced_window_alone_and_never_as_spans(window, monkeypatch):
    async def main():
        _quiet_probe(asyncio.get_running_loop())
        try:
            window.keep_raw = False
            window.reset()
            await asyncio.sleep(0.02)
            untraced = window.status()
            window.keep_raw = True
            window.reset()
            with span("t.request", "wait"):
                await asyncio.sleep(0.02)
            return untraced, window.status()
        finally:
            trace.LoopLagProbe.release()

    untraced, traced = asyncio.run(main())
    assert "sleeps" not in untraced["loop"] and untraced["spans"] == []
    assert untraced["loop"]["asleep_s"] >= 0.015
    loop = traced["loop"]
    pairs = list(zip(loop["sleeps"][0::2], loop["sleeps"][1::2]))
    assert pairs and loop["sleeps_dropped"] == 0
    assert all(a <= b for a, b in pairs)
    assert sum(b - a for a, b in pairs) == round(loop["asleep_s"] * 1e9)
    # on the spans' clock, and never among them
    (req,) = traced["spans"]
    assert req[0] == "t.request"
    assert any(req[2] <= a and b <= req[2] + req[3] for a, b in pairs)
    json.dumps(traced)

    # past the cap a traced window counts what it dropped
    monkeypatch.setattr(trace.WindowStore, "SLEEPS_CAP", 1)

    async def capped():
        _quiet_probe(asyncio.get_running_loop())
        try:
            window.reset()
            for _ in range(3):
                await asyncio.sleep(0.002)
            return window.status()["loop"]
        finally:
            trace.LoopLagProbe.release()

    loop = asyncio.run(capped())
    assert len(loop["sleeps"]) == 2 and loop["sleeps_dropped"] >= 2


@needs_trace
def test_a_produce_that_waits_for_a_held_loop_waits_under_rx_wait(
        tmp_path, window, monkeypatch):
    """The frame's bytes are stamped on arrival, then another callback
    holds the loop 50 ms before the connection's reader runs: that is
    `produce.rx_wait`, and `produce.decode` is the decode alone."""
    import time as _time

    from redpanda_tpu.kafka import server as kserver

    armed = []
    inner = kserver._RxStampProtocol.data_received

    def data_received(self, data):
        if armed and data[4:6] == b"\x00\x00":  # a Produce request
            armed.clear()
            asyncio.get_running_loop().call_soon(_time.sleep, 0.05)
        inner(self, data)

    monkeypatch.setattr(kserver._RxStampProtocol, "data_received", data_received)
    (rows,) = asyncio.run(_one_broker_produces(
        tmp_path, window, [1], before=lambda: armed.append(1)))
    by_name = {s[0]: s for s in rows}
    root, rx = by_name["kafka.produce"], by_name["produce.rx_wait"]
    decode = by_name["produce.decode"]
    assert not armed
    assert rx[1] == "wait" and decode[1] == "run"
    assert rx[3] >= 50e6 and decode[3] < 5e6
    # together they cover what the old decode span did: arrival to decoded
    assert rx[2] == root[2] and decode[2] == rx[2] + rx[3]


@needs_trace
@pytest.mark.parametrize("path", ["inline", "executor"])
def test_storage_fsync_is_recorded_on_the_loop_on_both_paths(
        tmp_path, window, monkeypatch, path):
    import threading

    from redpanda_tpu.storage.flush_coalescer import FlushCoalescer

    monkeypatch.setattr(FlushCoalescer, "INLINE_THRESHOLD_S",
                        1.0 if path == "inline" else 0.0)
    monkeypatch.setattr(FlushCoalescer, "_ewma_s", 0.0)
    threads = []
    add = window.add

    def spy(s, self_ns):
        if s.name == "storage.fsync":
            threads.append(threading.get_ident())
        add(s, self_ns)

    monkeypatch.setattr(window, "add", spy)

    async def main():
        fds = [os.open(tmp_path / f"seg{i}", os.O_CREAT | os.O_WRONLY)
               for i in range(2)]
        try:
            with span("t.flush", "wait") as parent:
                await asyncio.gather(
                    *(FlushCoalescer.get().fsync(fd) for fd in fds))
            return parent, threading.get_ident()
        finally:
            for fd in fds:
                os.close(fd)

    parent, loop_thread = asyncio.run(main())
    rows = _raw(window, "storage.fsync")
    if path == "inline":
        assert [(s[1], s[7]) for s in rows] == [
            ("run", {"path": "inline", "fds": 1})] * 2
    else:  # one executor round for both descriptors
        assert [(s[1], s[7]) for s in rows] == [
            ("wait", {"path": "executor", "fds": 2})]
    assert all(s[5] == parent.span_id and s[3] >= 0 for s in rows)
    # the span store is touched from the loop's thread alone
    assert threads == [loop_thread] * len(rows)
    # the EWMA that picks the path reads the same stamps
    assert FlushCoalescer._ewma_s > 0.0


async def _one_broker_produces(tmp_path, window, counts, before=None):
    """The raw spans of one produce each of `counts` records through a
    one-broker cluster, by trace id (`before()` is called ahead of
    each)."""
    from redpanda_tpu.models.record import RecordBatchBuilder

    out = []
    async with cluster(tmp_path, n=1) as (_net, brokers):
        client = KafkaClient([brokers[0].kafka_advertised])
        try:
            await client.create_topic("spans", partitions=1, replication_factor=1)
            await client.produce("spans", 0, [(None, b"warm")])
            for n in counts:
                b = RecordBatchBuilder()
                for i in range(n):
                    b.add(b"v" * 64, key=b"k%d" % i)
                wire = b.build().to_kafka_wire()
                window.reset()
                if before is not None:
                    before()
                await client.produce_wire("spans", 0, wire, acks=-1)
                await asyncio.sleep(0.02)  # on_written runs after the ack
                rows = window.status()["spans"]
                (root,) = [s for s in rows if s[0] == "kafka.produce"]
                out.append([s for s in rows if s[6] == root[6]])
        finally:
            await client.close()
    return out


@needs_trace
def test_produce_is_one_tree_across_the_layer_boundaries(tmp_path, window):
    (rows,) = asyncio.run(_one_broker_produces(tmp_path, window, [1]))
    by_name = {s[0]: s for s in rows}
    assert len(by_name) == len(rows), "a span name twice in one produce"
    parent = {s[0]: next((p[0] for p in rows if p[4] == s[5]), None)
              for s in rows}
    assert parent == {
        "kafka.produce": None,
        "produce.rx_wait": "kafka.produce",
        "produce.decode": "kafka.produce",
        "produce.dispatch": "kafka.produce",
        "produce.ack_wait": "kafka.produce",
        "raft.coalesce": "produce.ack_wait",
        "raft.append": "produce.ack_wait",
        "storage.append": "raft.append",
        "raft.flush": "produce.ack_wait",
        "storage.fsync": "raft.flush",
        "storage.flush": "raft.flush",
        "raft.quorum_wait": "produce.ack_wait",
    }
    assert len({s[6] for s in rows}) == 1  # one trace id
    kinds = {s[0]: s[1] for s in rows}
    # the fsync holds the loop where it runs inline, else it is a wait
    fsync = by_name["storage.fsync"]
    assert kinds.pop("storage.fsync") == (
        "run" if fsync[7]["path"] == "inline" else "wait")
    assert [n for n, k in kinds.items() if k == "wait"] == [
        n for n in kinds if n in (
            "kafka.produce", "produce.rx_wait", "produce.ack_wait",
            "raft.coalesce", "raft.flush", "storage.flush",
            "raft.quorum_wait")]
    # the root runs from the frame's arrival: the wait for the loop
    # starts with it and the decode where the handler starts
    root = by_name["kafka.produce"]
    rx, decode = by_name["produce.rx_wait"], by_name["produce.decode"]
    assert rx[2] == root[2] and decode[2] == rx[2] + rx[3]
    for s in rows:
        assert s[2] >= root[2] and s[2] + s[3] <= root[2] + root[3]


@needs_trace
def test_span_count_does_not_grow_with_records(tmp_path, window):
    one, many = asyncio.run(_one_broker_produces(tmp_path, window, [1, 500]))
    assert sorted(s[0] for s in one) == sorted(s[0] for s in many)


# -- the transaction path's spans and counters (PR 35) -------------------


async def _one_broker_transacts(tmp_path, window):
    """A commit, a read_committed fetch parked behind an open
    transaction, its abort, and a batch sent twice, through a
    one-broker cluster; returns the raw spans and the devplane digest."""
    from redpanda_tpu.kafka.client import TransactionalProducer
    from redpanda_tpu.observability import devplane

    async with cluster(tmp_path, n=1) as (_net, brokers):
        client = KafkaClient([brokers[0].kafka_advertised])
        try:
            await client.create_topic("tx-spans", partitions=1, replication_factor=1)
            tx = TransactionalProducer(client, "tx-spans-1")
            await tx.init()
            devplane.reset()   # also says whether raw records are kept
            window.keep_raw = True
            window.reset()
            tx.begin()
            await tx.produce("tx-spans", 0, [(b"k", b"committed")])
            await tx.commit()
            tx.begin()
            await tx.produce("tx-spans", 0, [(b"k", b"aborted")])
            # offsets 0 and 1 are the commit and its marker; the high
            # watermark is past offset 2, the LSO is not: parks
            # (on a connection of its own: a connection's requests are
            # served in turn, and the abort would queue behind the fetch)
            consumer = KafkaClient([brokers[0].kafka_advertised])
            try:
                parked = asyncio.ensure_future(consumer.fetch(
                    "tx-spans", 0, 2, read_committed=True, max_wait_ms=4000))
                await asyncio.sleep(0.06)
                await tx.abort()
                assert await parked == []   # the aborted record is filtered
            finally:
                await consumer.close()
            # the same sequence again: answered with its first offset
            tx._seqs[("tx-spans", 0)] -= 1
            tx.begin()
            again = await tx.produce("tx-spans", 0, [(b"k", b"aborted")])
            await tx.abort()
            assert again == 2
            await asyncio.sleep(0.02)
            return window.status()["spans"], devplane.merged_status(
                [devplane.snapshot()])
        finally:
            await client.close()


@needs_trace
def test_the_transaction_path_s_spans_and_counters(tmp_path, window, monkeypatch):
    from redpanda_tpu.observability import devplane

    monkeypatch.setattr(devplane, "ENABLED", True)
    rows, digest = asyncio.run(_one_broker_transacts(tmp_path, window))
    devplane.reset()
    named = {}
    for s in rows:
        named.setdefault(s[0], []).append(s)
    by_id = {s[4]: s for s in rows}

    def parent_of(s):
        return by_id[s[5]][0] if s[5] in by_id else None

    kinds = {name: {s[1] for s in spans} for name, spans in named.items()}
    for name in ("tx.add_partitions", "tx.end", "tx.prepare", "tx.markers",
                 "tx.complete", "fetch.lso_wait"):
        assert kinds[name] == {"wait"}, name
    assert kinds["tx.marker_append"] == {"run"}
    # roots at the coordinator, with what the request was about
    assert all(s[5] == 0 for s in named["tx.add_partitions"] + named["tx.end"])
    assert all(s[7]["partitions"] == 1 for s in named["tx.add_partitions"])
    assert sorted(s[7]["commit"] for s in named["tx.end"]) == [0, 0, 1]
    assert all(s[7]["partitions"] == 1 for s in named["tx.end"])
    for name in ("tx.prepare", "tx.markers", "tx.complete"):
        assert len(named[name]) == 3
        assert {parent_of(s) for s in named[name]} == {"tx.end"}, name
    # one broker: the marker is written by a local call, under the wait
    # for its partition, which hangs under the markers' wait
    assert {parent_of(s) for s in named["tx.marker_append"]} == {"tx.marker"}
    assert {parent_of(s) for s in named["tx.marker"]} == {"tx.markers"}
    assert [s[7]["partitions"] for s in named["tx.markers"]] == [1, 1, 1]
    # a transaction of one partition: one marker span, local, first time
    assert [(s[7]["remote"], s[7]["retries"]) for s in named["tx.marker"]] == [(0, 0)] * 3
    assert digest["tx_markers"] == {"data": 3, "group": 0, "remote": 0, "retried": 0}
    # the third transaction stored nothing (its batch was a duplicate):
    # there is nothing open for its marker to close, and none is written
    assert sorted(s[7]["commit"] for s in named["tx.marker_append"]) == [0, 1]
    # a coordinator write replicates under its stage
    under = {parent_of(s) for s in named["raft.append"]}
    assert {"tx.add_partitions", "tx.prepare", "tx.marker", "tx.complete",
            "produce.ack_wait"} <= under
    assert all(s[7]["batches"] == s[7]["items"] for s in named["raft.append"])
    # the parked fetch: one pass that parks it, one after the abort's
    # marker woke it
    (wait,) = named["fetch.lso_wait"]
    fetch = by_id[wait[5]]
    assert fetch[0] == "kafka.fetch"
    assert (fetch[7]["reads"], fetch[7]["wakes"]) == (2, 1)
    assert wait[3] > 20e6 and wait[2] >= fetch[2]
    assert all("reads" in s[7] and "wakes" in s[7] for s in named["kafka.fetch"])
    # rm_stm's check: two batches and the one sent twice (a marker and
    # a coordinator's write carry no sequence)
    assert digest["producer_sequences"] == {"checked": 3, "duplicate": 1}


@needs_trace
def test_a_pass_through_produce_and_fetch_open_no_transaction_span(tmp_path, window):
    from redpanda_tpu.observability import devplane

    async def drive():
        async with cluster(tmp_path, n=1) as (_net, brokers):
            client = KafkaClient([brokers[0].kafka_advertised])
            try:
                await client.create_topic("plain", partitions=1, replication_factor=1)
                window.reset()
                await client.produce("plain", 0, [(None, b"v")])
                assert len(await client.fetch("plain", 0, 0)) == 1
                await asyncio.sleep(0.02)
                return window.status()["spans"]
            finally:
                await client.close()

    rows = asyncio.run(drive())
    names = {s[0] for s in rows}
    assert not {n for n in names if n.startswith("tx.")} and "fetch.lso_wait" not in names
    # a fetch that finds its bytes answers from its first pass
    assert [s[7]["reads"] for s in rows if s[0] == "kafka.fetch"] == [1]
    assert "producer_sequences" in devplane.merged_status([]) \
        and devplane.merged_status([])["producer_sequences"] == {}


async def _three_brokers_transact(tmp_path, window):
    """One transaction over three partitions led by three brokers, and
    one over the same three that also commits a group's offset; returns
    the raw spans, the devplane digest, each partition's leader and the
    transaction coordinator's broker."""
    from redpanda_tpu.kafka.client import TransactionalProducer
    from redpanda_tpu.kafka.protocol import Msg
    from redpanda_tpu.kafka.protocol.group_apis import FIND_COORDINATOR
    from redpanda_tpu.observability import devplane

    async def coordinator_of(key: str, key_type: int) -> int:
        conn = await client.any_conn()
        found = await conn.request(FIND_COORDINATOR, Msg(key=key, key_type=key_type),
                                   conn.pick_version(FIND_COORDINATOR, 1))
        return found.node_id

    async with cluster(tmp_path, n=3) as (_net, brokers):
        client = KafkaClient([b.kafka_advertised for b in brokers])
        try:
            await client.create_topic("fan", partitions=3, replication_factor=1)
            for p in range(3):   # elected: the metadata names every leader
                await client.produce("fan", p, [(None, b"first")])
            md = await client.metadata(["fan"])
            leaders = {q.partition_index: q.leader_id for q in md.topics[0].partitions}
            tx = TransactionalProducer(client, "fan-1")
            await tx.init()
            await client.group("fan-group").coordinator()   # made and led
            coordinators = (await coordinator_of("fan-1", 1),
                            await coordinator_of("fan-group", 0))
            devplane.reset()
            window.keep_raw = True
            window.reset()
            tx.begin()
            await tx.produce_batches("fan", {p: [(b"k", b"v%d" % p)] for p in range(3)})
            await tx.commit()
            tx.begin()
            await tx.produce_batches("fan", {p: [(b"k", b"w%d" % p)] for p in range(3)})
            await tx.send_offsets("fan-group", {("fan", 0): 1})
            await tx.abort()
            await asyncio.sleep(0.02)
            return (window.status()["spans"], devplane.merged_status([devplane.snapshot()]),
                    leaders, coordinators)
        finally:
            await client.close()


@needs_trace
def test_each_data_marker_is_a_span_of_its_own_under_the_markers(
        tmp_path, window, monkeypatch):
    from redpanda_tpu.observability import devplane

    monkeypatch.setattr(devplane, "ENABLED", True)
    rows, digest, leaders, (coordinator, group_coordinator) = asyncio.run(
        _three_brokers_transact(tmp_path, window))
    devplane.reset()
    named = {}
    for s in rows:
        named.setdefault(s[0], []).append(s)
    by_id = {s[4]: s for s in rows}
    markers = named["tx.markers"]
    assert [s[7]["partitions"] for s in markers] == [3, 3]
    assert {s[1] for s in named["tx.marker"]} == {"wait"}
    remote = sum(leader != coordinator for leader in leaders.values())
    for whole in markers:
        kids = sorted((s for s in named["tx.marker"] if s[5] == whole[4]),
                      key=lambda s: s[2])
        # one a data partition, one after another, inside their parent
        assert len(kids) == 3
        assert sum(s[7]["remote"] for s in kids) == remote
        assert all(s[7]["retries"] == 0 for s in kids)
        for a, b in zip(kids, kids[1:]):
            assert a[2] + a[3] <= b[2]
        assert whole[2] <= kids[0][2] and kids[-1][2] + kids[-1][3] <= whole[2] + whole[3]
        assert sum(s[3] for s in kids) <= whole[3]
    # the group's marker has a span of its own and no `tx.marker`
    (group,) = named["tx.group_marker"]
    assert by_id[group[5]][0] == "tx.markers"
    assert len(named["tx.marker"]) == 6
    # placed over the three brokers: the coordinator's own and the others'
    assert len(set(leaders.values())) == 3 and remote == 2
    assert digest["tx_markers"] == {
        "data": 6, "group": 1,
        "remote": 2 * remote + (group_coordinator != coordinator), "retried": 0}


@needs_trace
def test_a_plain_produce_and_fetch_count_no_marker(tmp_path, window, monkeypatch):
    from redpanda_tpu.observability import devplane

    monkeypatch.setattr(devplane, "ENABLED", True)

    async def drive():
        async with cluster(tmp_path, n=1) as (_net, brokers):
            client = KafkaClient([brokers[0].kafka_advertised])
            try:
                await client.create_topic("plain", partitions=2, replication_factor=1)
                devplane.reset()
                window.keep_raw = True
                window.reset()
                for p in range(2):
                    await client.produce("plain", p, [(None, b"v")])
                    assert len(await client.fetch("plain", p, 0)) == 1
                await asyncio.sleep(0.02)
                return window.status()["spans"], devplane.merged_status(
                    [devplane.snapshot()])
            finally:
                await client.close()

    rows, digest = asyncio.run(drive())
    devplane.reset()
    assert not {s[0] for s in rows} & {"tx.markers", "tx.marker", "tx.group_marker"}
    assert digest["tx_markers"] == {"data": 0, "group": 0, "remote": 0, "retried": 0}


# -- the group coordinator's spans and counters ---------------------------

GROUP_SPANS = ("tx.add_offsets", "group.txn_offset_commit", "tx.group_marker",
               "group.offset_fetch", "group.join", "group.sync")


async def _one_broker_copies(tmp_path, window):
    """A member joins and syncs, copies under a transaction that commits
    its input offset, copies again under one that aborts, is refused a
    stale generation's offsets, reads the pending offset as unstable and
    rewinds; returns the raw spans and the devplane digest."""
    import types

    from redpanda_tpu.kafka.client import TransactionalProducer
    from redpanda_tpu.observability import devplane

    async with cluster(tmp_path, n=1) as (_net, brokers):
        client = KafkaClient([brokers[0].kafka_advertised])
        try:
            await client.create_topic("src", partitions=1, replication_factor=1)
            await client.create_topic("dst", partitions=1, replication_factor=1)
            tx = TransactionalProducer(client, "ctp-1")
            await tx.init()
            devplane.reset()
            window.keep_raw = True
            window.reset()
            member = client.group("ctp")
            await member.join([("range", b"")])
            await member.sync([(member.member_id, b"")])
            tx.begin()
            await tx.produce("dst", 0, [(b"k", b"copy")])
            await tx.send_offsets("ctp", {("src", 0): 1}, member=member)
            await tx.commit()
            tx.begin()
            await tx.produce("dst", 0, [(b"k", b"aborted copy")])
            await tx.send_offsets("ctp", {("src", 0): 2}, member=member)
            stale = types.SimpleNamespace(generation=member.generation - 1,
                                          member_id=member.member_id,
                                          group_instance_id=None)
            with pytest.raises(Exception):
                await tx.send_offsets("ctp", {("src", 0): 2}, member=stale)
            with pytest.raises(Exception):
                await member.fetch_offsets({"src": [0]}, require_stable=True)
            await tx.abort()
            assert await member.fetch_offsets({"src": [0]}, require_stable=True) == {
                ("src", 0): 1}
            await asyncio.sleep(0.02)
            return window.status()["spans"], devplane.merged_status(
                [devplane.snapshot()])
        finally:
            await client.close()


@needs_trace
def test_the_group_path_s_spans_and_counters(tmp_path, window, monkeypatch):
    from redpanda_tpu.observability import devplane

    monkeypatch.setattr(devplane, "ENABLED", True)
    rows, digest = asyncio.run(_one_broker_copies(tmp_path, window))
    named = {}
    for s in rows:
        named.setdefault(s[0], []).append(s)
    by_id = {s[4]: s for s in rows}

    def parent_of(s):
        return by_id[s[5]][0] if s[5] in by_id else None

    for name in ("tx.add_offsets", "group.txn_offset_commit", "tx.group_marker",
                 "group.join", "group.sync"):
        assert {s[1] for s in named[name]} == {"wait"}, name
    assert {s[1] for s in named["group.offset_fetch"]} == {"run"}
    # roots at their coordinators, with what the request was about
    # (the refused send_offsets was added to its transaction first)
    assert [s[7]["group"] for s in named["tx.add_offsets"]] == ["ctp"] * 3
    assert all(s[5] == 0 for s in named["tx.add_offsets"] + named["group.txn_offset_commit"])
    commits = named["group.txn_offset_commit"]
    assert [s[7]["partitions"] for s in commits] == [1, 1, 1]
    generation = named["group.join"][0][7]["generation"]
    assert [s[7]["generation"] for s in commits] == [generation, generation, generation - 1]
    # one group marker a transaction, under its markers
    assert len(named["tx.group_marker"]) == 2
    assert {parent_of(s) for s in named["tx.group_marker"]} == {"tx.markers"}
    # the pending offset read as unstable once; the rewind read it settled
    assert [s[7]["unstable"] for s in named["group.offset_fetch"]] == [1, 0]
    assert len(named["group.join"]) == len(named["group.sync"]) == 1
    assert digest["group_coordinator"] == {
        "rebalances": 1, "tx_offsets_staged": 2, "tx_offsets_committed": 1,
        "tx_offsets_dropped": 1, "txn_offset_commits_fenced": 1,
        "unstable_offset_fetches": 1}
    # zeroed in place by the window's reset
    devplane.reset()
    assert set(devplane.merged_status([devplane.snapshot()])["group_coordinator"].values()) \
        == {0}


@needs_trace
def test_a_transaction_with_no_group_and_a_plain_produce_open_no_group_span(
        tmp_path, window, monkeypatch):
    """omb_100_tx's transaction (one partition, no offsets) and a plain
    produce pay nothing of the group path."""
    from redpanda_tpu.observability import devplane

    monkeypatch.setattr(devplane, "ENABLED", True)
    rows, digest = asyncio.run(_one_broker_transacts(tmp_path, window))

    async def plain():
        async with cluster(tmp_path / "plain", n=1) as (_net, brokers):
            client = KafkaClient([brokers[0].kafka_advertised])
            try:
                await client.create_topic("plain", partitions=1, replication_factor=1)
                window.reset()
                await client.produce("plain", 0, [(None, b"v")])
                await asyncio.sleep(0.02)
                return window.status()["spans"]
            finally:
                await client.close()

    rows += asyncio.run(plain())
    devplane.reset()
    assert "tx.markers" in {s[0] for s in rows}
    assert not {s[0] for s in rows} & set(GROUP_SPANS)
    assert set(digest["group_coordinator"].values()) == {0}
