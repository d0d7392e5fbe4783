"""The re-keying generator: a Kafka Streams application under
`processing.guarantee=exactly_once_v2` (KIP-447) whose topology is
`stream(source).selectKey((k, v) -> v's key field).to(sink)`, the first
step of every Streams aggregation or join that changes the key, between
idempotent source producers that send at fixed times and polling
`read_committed` consumers of its output.

Source producers: open loop, as generators/ctp.py's: an idempotent
producer's id from InitProducerId with no transactional id, a batch a
due time on a partition drawn from the seed, acks=all, one request
outstanding, a not-leader answer retried inside the latency with the
same sequence. The batch is a template of templates/streams.py, stamped
with the producer's id, epoch and the partition's next sequence, and
every record's event id (partition, producer id, sequence) written into
its value. Every client of the generator is connected to every broker in
set-up, as a running producer, member or consumer is.

Members: `members` threads of the application, one member each of the
group `group`, joined and synced in set-up with the range assignment of
generators/ctp.py. A member is one `transactional.id` and one producer
(redpanda_tpu's TransactionalProducer), one client for the coordinators
and its produce requests and another for its fetches, which park. It
fetches its source partitions `read_committed`; for each poll that
returned records, at most one every `commit_interval_ms`, it re-keys
every record it holds (`rekey`: the value's first 16 bytes become the
key, and Kafka's default partitioner, murmur2 of the key, names the
sink partition), and writes them in one transaction: every sink
partition the records name in one AddPartitionsToTxn, one batch a sink
partition with that partition's next sequence, one ProduceRequest to
each leader with every partition it leads
(`TransactionalProducer.produce_batches`), the positions past what it
read with the group's metadata (TxnOffsetCommit v3), then EndTxn. A
source batch of 39 records names about 32 of 100 sink partitions, led by
all three brokers, so each EndTxn has the coordinator deliver about 32
data markers and one group marker. For a share `abort_share` of the
transactions, drawn from the seed, it aborts instead, rewinds to the
group's committed offsets (OffsetFetch v7, `require_stable`), reads the
source again and writes again. It heartbeats every
`heartbeat_interval_ms`; a rebalance makes it rejoin, sync and rewind.

Sink consumers: `consumers` consumers tail their share of the sink's
partitions `read_committed` from where set-up left them and learn
nothing out of band: what they hold is what the Kafka consumer's own
filter (txreplay.Filter) leaves of each response.

The record has `open_loop.run`'s keys and columns, one row a source
batch due in the window. `t_ack` is the source's ack; `t_fetch` is when
the last of its records' committed copies was in a sink consumer's
hands, and `fetched_bytes` its records' share of the sink batches that
hold them. After the drain every partition of both topics is read
`read_uncommitted` from where the window began, the group's committed
offsets are read, and all of it is held to the plain reference
(benchmark/rekeyreplay.py): a row whose records a departure touches is
`fetched_wrong`, one with a record that has no committed copy, or whose
copy no consumer was handed, `never_fetched`, and `fetch_errors` names
the rule. `clients` carries the fan-out: the sink partitions each
transaction wrote to.

It runs in the load generator's own process and imports of the program
its Kafka client, its record decoder and its partitioner.
"""

from __future__ import annotations

import asyncio
import json
import statistics
import time

import numpy as np

from benchmark import rekeyreplay
from benchmark.generators.ctp import END_RETRY, SETUP_S, UNKNOWN_MEMBER_ID, \
    UNSTABLE_OFFSET_COMMIT, range_assign
from benchmark.generators.open_loop import NOT_LEADER, due_times, steps_of
from benchmark.generators.transactional import FETCH_MAX_BYTES, RETRY_S
from benchmark.reference import split_batches
from benchmark.run import resolve
from benchmark.templates.streams import KEY_FIELD
from benchmark.txreplay import Filter, head_of, replay

#: seconds the members' and the sink consumers' fetches run before the
#: window opens
POLLING_S = 1.0


def rekey(batch: bytes, partitions: int) -> dict[int, list[tuple[bytes, bytes]]]:
    """The records of one source batch, re-keyed by their value's key
    field, by the sink partition Kafka's default partitioner names for
    the new key, each partition's in source order."""
    from redpanda_tpu.kafka.client import decode_record_set
    from redpanda_tpu.utils.hash import kafka_partition_for_key

    out: dict[int, list[tuple[bytes, bytes]]] = {}
    for _offset, _key, value in decode_record_set(batch):
        key = value[KEY_FIELD]
        out.setdefault(kafka_partition_for_key(key, partitions), []).append((key, value))
    return out


async def run(spec: dict, say) -> dict:
    """Drive one window. `spec` is what loadgen.py was handed; `say`
    prints one line to the harness. Returns the record file's content:
    one row a source batch."""
    from redpanda_tpu.kafka.client import (
        KafkaClient, KafkaClientError, TransactionalProducer,
    )
    from redpanda_tpu.kafka.protocol import FETCH, PRODUCE, Msg
    from redpanda_tpu.kafka.protocol.group_apis import INIT_PRODUCER_ID

    traffic, config = spec["traffic"], spec["config"]
    n_src, n_members = int(traffic["producers"]), int(traffic["members"])
    n_cons = int(traffic["consumers"])
    seed, seconds = int(spec["seed"]), float(spec["seconds"])
    steps = steps_of(traffic, seconds)
    seconds = sum(s for s, _r in steps)
    bootstrap = [tuple(a) for a in spec["bootstrap"]]
    tpl = resolve(traffic["templates"]["maker"], "templates")(seed, traffic, config)
    linger = float(traffic["linger_ms"]) / 1e3
    timeout_ms = int(traffic["request_timeout_ms"])
    fetch_bytes = int(traffic["fetch_max_bytes"])
    fetch_wait = int(traffic["fetch_max_wait_ms"])
    fetch_min = int(traffic["fetch_min_bytes"])
    abort_share = float(traffic["abort_share"])
    tx_timeout_ms = int(traffic["transaction_timeout_ms"])
    interval = float(traffic["commit_interval_ms"]) / 1e3
    heartbeat_s = float(traffic["heartbeat_interval_ms"]) / 1e3
    session_ms = int(traffic["session_timeout_ms"])
    group_id = traffic["group"]
    ack_sample_s = float(traffic["ack_sample_s"])
    drain = float(traffic["drain_s"])
    acks = int(config["acks"])
    source, sink = spec["topics"][0]["name"], spec["topics"][1]["name"]
    n_parts = int(spec["topics"][0]["partitions"])
    n_out = int(spec["topics"][1]["partitions"])
    parts, outs = list(range(n_parts)), list(range(n_out))

    # one row a source batch:
    # [topic, p, tpl, base, t_due, t_ack, err, t_sent, tries, in_request]
    rows: list[list] = []
    acked_end: dict[int, int] = {}      # source partition -> end of what was acknowledged
    committed_to: dict[int, int] = {}   # source partition -> offset the group committed
    committed_out: dict[int, set] = {q: set() for q in outs}   # bases of committed batches
    fetch_errors: list[str] = []
    counts = {"fetches": 0, "requests": 0, "transactions": 0, "aborted": 0,
              "rewinds": 0, "rejoins": 0, "member_fetches": 0}
    fanout: list[int] = []              # sink partitions a transaction wrote to
    late: list[float] = []
    handed: list[float] = []
    last_sample = 0.0
    # sink (partition, base) -> (when, bytes, records)
    delivered: dict[tuple[int, int], tuple[float, int, int]] = {}
    served_under: dict[tuple[int, int], int] = {}
    in_order: dict[int, list[int]] = {q: [] for q in outs}
    # sink (partition, base) of a batch -> when its EndTxn was sent
    end_sent: dict[tuple[int, int], float] = {}
    give_up = float("inf")

    def fail(row: list, what: str) -> None:
        row[5], row[6] = time.monotonic(), what[:200]

    def fetch_request(asked: dict, topic: str, isolation: int, wait_ms: int,
                      min_bytes: int) -> Msg:
        return Msg(
            rack_id="", replica_id=-1, max_wait_ms=wait_ms, min_bytes=min_bytes,
            max_bytes=FETCH_MAX_BYTES, isolation_level=isolation, session_id=0,
            session_epoch=-1, forgotten_topics_data=[],
            topics=[Msg(topic=topic, partitions=[
                Msg(partition=p, current_leader_epoch=-1, fetch_offset=at,
                    log_start_offset=0, partition_max_bytes=fetch_bytes)
                for p, at in sorted(asked.items())])],
        )

    async def leader_of(client, topic: str, p: int, refresh: bool):
        while True:
            try:
                return await client.leader_conn(topic, p, refresh=refresh)
            except (OSError, KafkaClientError) as e:
                fetch_errors.append(f"{topic}/{p}: {e!r}"[:200])
                refresh = True
                await asyncio.sleep(RETRY_S)

    async def whole_log(client, topic: str, p: int, at: int) -> list[bytes]:
        """Everything partition `p` of `topic` holds from `at`, read
        `read_uncommitted`."""
        out, refresh = [], False
        while time.monotonic() < give_up + drain:
            conn = await leader_of(client, topic, p, refresh)
            resp = await conn.request(FETCH, fetch_request({p: at}, topic, 0, 0, 0),
                                      conn.pick_version(FETCH, 11))
            pr = resp.responses[0].partitions[0]
            if pr.error_code in NOT_LEADER:
                refresh = True
                await asyncio.sleep(RETRY_S)
                continue
            if pr.error_code:
                raise RuntimeError(f"{topic}/{p} at {at}: error_code {pr.error_code}")
            for _base, batch in split_batches(bytes(pr.records or b"")):
                last = head_of(batch).last
                if last >= at:
                    out.append(batch)
                    at = last + 1
            if at >= pr.high_watermark:
                return out
        raise RuntimeError(f"{topic}/{p}: the log was not read to its end")

    async def end_of(client, topic: str, p: int, until: float) -> int:
        while True:
            try:
                return await client.list_offset(topic, p, -1)
            except KafkaClientError:   # the leadership moved: ask who leads it now
                if time.monotonic() >= until:
                    raise
                await asyncio.sleep(RETRY_S)
                await client.leader_conn(topic, p, refresh=True)

    # ------------------------------------------------------ source producers
    class Source:
        def __init__(self) -> None:
            self.client = KafkaClient(bootstrap)
            self.producer_id = self.epoch = -1
            self.sequence: dict[int, int] = {}

        async def init(self, until: float) -> None:
            """InitProducerId with no transactional id: an idempotent
            producer's id (Kafka's default since 3.0)."""
            while True:
                try:
                    conn = await self.client.any_conn()
                    resp = await conn.request(
                        INIT_PRODUCER_ID,
                        Msg(transactional_id=None, transaction_timeout_ms=tx_timeout_ms),
                        conn.pick_version(INIT_PRODUCER_ID, 1))
                    if resp.error_code == 0:
                        self.producer_id, self.epoch = resp.producer_id, resp.producer_epoch
                        return
                    what = f"error_code {resp.error_code}"
                except (OSError, KafkaClientError) as e:
                    what = repr(e)
                if time.monotonic() >= until:
                    raise RuntimeError(f"init_producer_id: {what}")
                await asyncio.sleep(RETRY_S)

        async def send(self, row: list) -> None:
            """One batch; fills the row."""
            p, t = row[1], tpl[row[2]]
            sequence = self.sequence.get(p, 0)
            req = Msg(transactional_id=None, acks=acks, timeout_ms=timeout_ms,
                      topics=[Msg(name=source, partitions=[Msg(
                          index=p, records=t.stamp_event(
                              self.producer_id, self.epoch, sequence, p))])])
            row[7] = time.monotonic()
            late.append(row[7] - (row[4] + linger))
            refresh = False
            while True:
                row[8] += 1
                row[9] = 1
                counts["requests"] += 1
                try:
                    conn = await self.client.leader_conn(source, p, refresh=refresh)
                    v = conn.pick_version(PRODUCE, 7)
                    resp = PRODUCE.decode_response(await conn.request_body(
                        PRODUCE, PRODUCE.encode_request(req, v), v), v)
                    pr = resp.responses[0].partition_responses[0]
                    code, base = pr.error_code, pr.base_offset
                except Exception as e:  # the connection failed: the batch did
                    return fail(row, repr(e))
                now = time.monotonic()
                if code == 0:
                    self.sequence[p] = sequence + t.records
                    return acked(row, base, now)
                if code not in NOT_LEADER or now >= give_up:
                    return fail(row, f"error_code {code}")
                await asyncio.sleep(RETRY_S)
                refresh = True

    def acked(row: list, base: int, now: float) -> None:
        nonlocal last_sample
        row[3], row[5] = base, now
        end = base + tpl[row[2]].records
        acked_end[row[1]] = max(acked_end.get(row[1], end), end)
        if now - last_sample >= ack_sample_s:
            # the harness reads, as this ack arrives, how many replicas
            # have flushed it
            last_sample = now
            say("acked " + json.dumps([source, row[1], end]))

    async def producer(i: int, me: Source) -> None:
        due = t0 + due_times(steps, n_src, i)
        prng = np.random.default_rng([seed, i])
        order = np.concatenate([
            prng.permutation(n_parts) for _ in range(len(due) // n_parts + 1)])
        free_at = 0.0
        for k, t_due in enumerate(due):
            ready = max(t_due + linger, free_at)
            wait = ready - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            handed.append(time.monotonic() - ready)
            row = [source, int(order[k]), (k + i) % len(tpl), -1, float(t_due), 0.0,
                   None, 0.0, 0, 0]
            rows.append(row)
            await me.send(row)
            free_at = time.monotonic()

    class Tailing:
        """A client that tails partitions of `topic` with one fetch in
        flight a broker, as a Kafka consumer has: `assign` hands a
        partition to the fetcher of the broker that leads it."""

        def __init__(self, topic: str) -> None:
            self.topic = topic
            self.feed = KafkaClient(bootstrap)
            self.fetchers: dict = {}   # connection -> {tps, more, task}

        async def assign(self, p: int, refresh: bool = False) -> None:
            if refresh:
                await asyncio.sleep(RETRY_S)
            conn = await leader_of(self.feed, self.topic, p, refresh)
            if conn not in self.fetchers:
                f = {"tps": set(), "more": asyncio.Event()}
                f["task"] = asyncio.ensure_future(self.fetcher(conn, f))
                self.fetchers[conn] = f
            self.fetchers[conn]["tps"].add(p)
            self.fetchers[conn]["more"].set()

    # --------------------------------------------------------------- members
    class Member(Tailing):
        def __init__(self, i: int) -> None:
            super().__init__(source)   # `feed` for its fetches, which park
            self.i = i
            self.app = KafkaClient(bootstrap)     # coordinators and produce
            self.group = self.app.group(group_id)
            self.tx = TransactionalProducer(self.app, f"{group_id}-{i}", tx_timeout_ms)
            self.mine: list[int] = []
            self.position: dict[int, int] = {}
            self.buffer: list[tuple[int, int, bytes]] = []
            self.more = asyncio.Event()
            self.era = 0           # a rewind drops the answers of fetches sent before it
            self.aborts = aborts_of(i)
            self.lock = asyncio.Lock()  # a transaction, or a rejoin
            self.tasks: list = []

        async def join(self, until: float) -> None:
            """JoinGroup and SyncGroup, the leader assigning, until one
            generation answers both."""
            while True:
                try:
                    resp = await self.group.join(
                        [("range", source.encode())], session_timeout_ms=session_ms,
                        rebalance_timeout_ms=session_ms)
                    assignments = []
                    if resp.leader == resp.member_id:
                        split = range_assign([m.member_id for m in resp.members], n_parts)
                        assignments = [(m, json.dumps(ps).encode())
                                       for m, ps in split.items()]
                    self.mine = json.loads(await self.group.sync(assignments) or b"[]")
                    return
                except KafkaClientError as e:
                    if time.monotonic() >= until:
                        raise
                    if e.code == UNKNOWN_MEMBER_ID:
                        self.group.member_id = ""
                    await asyncio.sleep(RETRY_S)

        async def rewind(self, until: float) -> None:
            """Positions back to the group's committed offsets, once no
            transaction holds any unsettled (OffsetFetch v7)."""
            self.era += 1
            self.buffer = []
            self.more.clear()
            while True:
                try:
                    got = await self.group.fetch_offsets(
                        {source: self.mine}, require_stable=True)
                    break
                except (OSError, KafkaClientError) as e:
                    if time.monotonic() >= until:
                        raise RuntimeError(f"member {self.i}: rewind: {e!r}") from e
                    if getattr(e, "code", None) != UNSTABLE_OFFSET_COMMIT:
                        fetch_errors.append(f"member {self.i}: rewind: {e!r}"[:200])
                    await asyncio.sleep(RETRY_S)
            self.position = {p: got.get((source, p), start_src[p]) for p in self.mine}
            for p, at in self.position.items():
                committed_to[p] = max(committed_to.get(p, at), at)
            # and the answers of fetches sent while the offsets were asked
            # for, from the positions before
            self.era += 1
            self.buffer = []
            self.more.clear()
            for f in self.fetchers.values():
                f["more"].set()

        async def fetcher(self, conn, mine: dict) -> None:
            """This member's fetches from one broker, one in flight."""
            tps, more = mine["tps"], mine["more"]
            while True:
                era = self.era
                asked = {p: self.position[p] for p in tps if p in self.position}
                if not asked:
                    more.clear()
                    await more.wait()
                    continue
                answers: dict = {}
                try:
                    resp = await conn.request(
                        FETCH, fetch_request(asked, source, 1, fetch_wait, fetch_min),
                        conn.pick_version(FETCH, 11))
                    answers = {pr.partition_index: pr for t in resp.responses
                               for pr in t.partitions}
                except Exception as e:
                    fetch_errors.append(f"member fetch of {len(asked)}: {e!r}"[:200])
                    await asyncio.sleep(RETRY_S)
                counts["member_fetches"] += 1
                if era != self.era:
                    continue
                for p, at in asked.items():
                    pr = answers.get(p)
                    if pr is None or pr.error_code in NOT_LEADER:
                        tps.discard(p)
                        helpers.append(asyncio.ensure_future(self.assign(p, refresh=True)))
                    elif pr.error_code:
                        fetch_errors.append(f"{source}/{p} at {at}: error_code {pr.error_code}")
                    else:
                        for base, batch in split_batches(bytes(pr.records or b"")):
                            last = head_of(batch).last
                            if last >= at:
                                self.buffer.append((p, base, batch))
                                at = last + 1
                        self.position[p] = at
                if self.buffer:
                    self.more.set()

        async def processor(self) -> None:
            """One transaction for each poll that returned records, at
            most one every `commit_interval_ms`."""
            began = 0.0
            while True:
                await self.more.wait()
                wait = began + interval - time.monotonic()
                if wait > 0:
                    await asyncio.sleep(wait)
                async with self.lock:
                    taken, self.buffer = self.buffer, []
                    self.more.clear()
                    if not taken:
                        continue
                    began = time.monotonic()
                    try:
                        await self.transact(taken)
                    except Exception as e:   # recorded; the member goes on
                        fetch_errors.append(f"member {self.i}: {e!r}"[:200])

        async def transact(self, taken: list) -> None:
            abort = next(self.aborts)
            tx = self.tx
            out: dict[int, list[tuple[bytes, bytes]]] = {}
            for _p, _base, batch in taken:
                for q, records in rekey(batch, n_out).items():
                    out.setdefault(q, []).extend(records)
            offsets = {(source, p): head_of(batch).last + 1 for p, _b, batch in taken}
            bases: dict[int, int] = {}
            try:
                tx.begin()
                bases = await tx.produce_batches(sink, out)
                await tx.send_offsets(group_id, offsets, member=self.group)
            except (OSError, RuntimeError, KafkaClientError) as e:
                fetch_errors.append(f"member {self.i}: {e!r}"[:200])
                abort = True
            sent = time.monotonic()
            answered = await self.end(not abort)
            for q, base in bases.items():
                end_sent[(q, base)] = sent
            counts["transactions"] += 1
            counts["aborted"] += abort
            fanout.append(len(out))
            if abort or not answered:
                # where EndTxn got no answer the group's offsets say how
                # the transaction ended
                counts["rewinds"] += 1
                await self.rewind(give_up)
                return
            for (_topic, p), at in offsets.items():
                committed_to[p] = max(committed_to.get(p, at), at)
            for q, base in bases.items():
                committed_out[q].add(base)

        async def end(self, commit: bool) -> bool:
            """EndTxn, asked again while its answer is one a client
            retries; whether it was answered."""
            while True:
                try:
                    await (self.tx.commit() if commit else self.tx.abort())
                    return True
                except (OSError, KafkaClientError) as e:
                    if getattr(e, "code", None) not in END_RETRY \
                            or time.monotonic() >= give_up:
                        fetch_errors.append(f"member {self.i}: end_txn: {e!r}"[:200])
                        return False
                    await asyncio.sleep(RETRY_S)

        async def heartbeat(self) -> None:
            while True:
                await asyncio.sleep(heartbeat_s)
                try:
                    code = await self.group.heartbeat()
                    if code:
                        await self.rejoin(code)
                except (OSError, RuntimeError, KafkaClientError) as e:
                    fetch_errors.append(f"member {self.i}: heartbeat: {e!r}"[:200])

        async def rejoin(self, code: int) -> None:
            """A rebalance: once no transaction is open, join and sync
            again, take the partitions the leader gave, and rewind."""
            async with self.lock:
                counts["rejoins"] += 1
                fetch_errors.append(f"member {self.i}: heartbeat answered {code}: rejoined")
                self.era += 1
                for f in self.fetchers.values():
                    f["tps"].clear()
                await self.join(give_up)
                await self.rewind(give_up)
                for p in self.mine:
                    await self.assign(p)

    def aborts_of(i: int):
        """Which of member i's transactions abort: `abort_share` of each
        run of transactions, to the nearest whole one, at places drawn
        from the seed (generators/ctp.py's rule)."""
        prng = np.random.default_rng([seed, n_src + i])
        run_of = max(1, round(1 / abort_share)) if abort_share > 0 else 1
        while True:
            flags = np.zeros(run_of, bool)
            flags[: int(round(abort_share * run_of))] = True
            yield from prng.permutation(flags).tolist()

    # --------------------------------------------------------- sink consumers
    rng = np.random.default_rng(seed)
    owner = {q: k % n_cons for k, q in enumerate(rng.permutation(n_out).tolist())}

    class Consumer(Tailing):
        def __init__(self, j: int) -> None:
            super().__init__(sink)
            self.mine = [q for q in outs if owner[q] == j]
            self.position: dict[int, int] = {}

        async def fetcher(self, conn, mine: dict) -> None:
            tps, more = mine["tps"], mine["more"]
            while True:
                if not tps:
                    more.clear()
                    await more.wait()
                asked = {q: self.position[q] for q in tps}
                answers: dict = {}
                try:
                    resp = await conn.request(
                        FETCH, fetch_request(asked, sink, 1, fetch_wait, fetch_min),
                        conn.pick_version(FETCH, 11))
                    answers = {pr.partition_index: pr for t in resp.responses
                               for pr in t.partitions}
                except Exception as e:
                    fetch_errors.append(f"fetch of {len(asked)} partitions: {e!r}"[:200])
                    await asyncio.sleep(RETRY_S)
                now = time.monotonic()
                counts["fetches"] += 1
                for q, at in asked.items():
                    pr = answers.get(q)
                    if pr is None or pr.error_code in NOT_LEADER:
                        tps.discard(q)
                        helpers.append(asyncio.ensure_future(self.assign(q, refresh=True)))
                    elif pr.error_code:
                        fetch_errors.append(f"{sink}/{q} at {at}: error_code {pr.error_code}")
                        await asyncio.sleep(RETRY_S)
                    else:
                        self.hand_on(q, at, pr, now)

        def hand_on(self, q: int, at: int, pr, now: float) -> None:
            """What the consumer's filter leaves of one partition's
            answer; the position moves past everything it read."""
            flt = Filter([(a.producer_id, a.first_offset)
                          for a in pr.aborted_transactions or []])
            for base, batch in split_batches(bytes(pr.records or b"")):
                head = head_of(batch)
                if head.last < at:
                    continue   # a batch may begin before the offset asked for
                served_under.setdefault((q, base), pr.high_watermark)
                if flt.take(batch) == "deliver":
                    in_order[q].append(base)
                    delivered.setdefault((q, base), (now, len(batch), head.records))
                at = head.last + 1
            self.position[q] = at

    # --------------------------------------------------------------- set-up
    sources = [Source() for _ in range(n_src)]
    members = [Member(i) for i in range(n_members)]
    consumers = [Consumer(j) for j in range(n_cons)]
    helpers: list = []
    everyone = [s.client for s in sources] + [c.feed for c in consumers] + [
        c for m in members for c in (m.app, m.feed)]
    for c in everyone:
        # a running client holds a connection to every broker it talks
        # to: opened here, so that no send in the window pays for a
        # connect and its ApiVersions (a source producer sends two or
        # three batches a window, each to a leader drawn from the seed)
        for b in (await c.metadata([source, sink])).brokers:
            await c._connect_addr((b.host, b.port))
    ready_by = time.monotonic() + SETUP_S
    start_src = {p: await end_of(sources[0].client, source, p, ready_by) for p in parts}
    start_sink = {q: await end_of(sources[0].client, sink, q, ready_by) for q in outs}
    # producer ids; the first InitProducerId makes the transaction
    # coordinator's topic, the first FindCoordinator of the group makes
    # __consumer_offsets, and both elect their leaders here
    await asyncio.gather(*(s.init(ready_by) for s in sources),
                         *(m.tx.init() for m in members))

    async def settle() -> None:
        """Every member in one generation, every partition assigned."""
        while True:
            await asyncio.gather(*(m.join(ready_by) for m in members))
            generations = {m.group.generation for m in members}
            if len(generations) == 1 and sorted(
                    p for m in members for p in m.mine) == parts:
                return
            if time.monotonic() >= ready_by:
                raise RuntimeError(f"the group did not settle: generations {generations}")

    await asyncio.wait_for(settle(), SETUP_S)

    async def commit_start(m: Member) -> None:
        """The group commits each source partition's end, each member
        for its own partitions in a transaction: a staged offset and a
        group marker through the coordinators before the window."""
        if m.mine:
            m.tx.begin()
            await m.tx.send_offsets(group_id, {(source, p): start_src[p] for p in m.mine},
                                    member=m.group)
            await m.tx.commit()
        await m.rewind(ready_by)

    await asyncio.gather(*(commit_start(m) for m in members))
    for c in consumers:
        c.position = {q: start_sink[q] for q in c.mine}

    # the application and its consumers are polling when the window
    # opens, as those of a running pipeline are: their first fetches,
    # which find nothing and park, are set-up
    for m in members:
        for p in m.mine:
            await m.assign(p)
        m.tasks = [asyncio.ensure_future(m.processor()), asyncio.ensure_future(m.heartbeat())]
    for c in consumers:
        for q in c.mine:
            await c.assign(q)
    await asyncio.sleep(POLLING_S)
    say("armed")
    t0 = time.monotonic() + 0.05
    give_up = t0 + seconds + drain
    say(f"window_start {t0!r}")
    prods = [asyncio.ensure_future(producer(i, s)) for i, s in enumerate(sources)]
    cpu0 = time.process_time()
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    cpu_share = (time.process_time() - cpu0) / seconds
    say(f"window_end {time.monotonic()!r}")
    # what is in flight is waited for: late is late, not wrong
    _done, unanswered = await asyncio.wait(prods, timeout=drain)

    def behind() -> list[str]:
        """Source partitions the group has not committed to what was
        acknowledged, and sink partitions with a committed batch no
        consumer was handed."""
        return [f"{source}/{p}" for p in parts
                if committed_to.get(p, start_src[p]) < acked_end.get(p, start_src[p])] + [
            f"{sink}/{q}" for q in outs if not committed_out[q] <= set(in_order[q])]

    while behind() and time.monotonic() < give_up:
        await asyncio.sleep(0.02)
    stuck = behind()
    for m in members:   # no transaction is left open
        try:
            await asyncio.wait_for(m.lock.acquire(), drain)
        except asyncio.TimeoutError:
            fetch_errors.append(f"member {m.i}: its transaction did not end")
    polling = [f["task"] for x in (*members, *consumers) for f in x.fetchers.values()]
    running = [t for m in members for t in m.tasks]
    for t in (*unanswered, *polling, *running, *helpers):
        t.cancel()
    await asyncio.gather(*prods, *polling, *running, *helpers, return_exceptions=True)

    # ------------------------------------------ the logs, held to the reference
    broken: dict[str, list[str]] = {
        "exactly_once": [], "partitioning": [], "order": [], "atomicity": [],
        "isolation": [], "idempotence": []}
    reader = sources[0].client
    committed: dict = {}
    until = time.monotonic() + drain
    while True:
        try:
            committed = await members[0].group.fetch_offsets(
                {source: parts}, require_stable=True)
            break
        except KafkaClientError as e:
            if time.monotonic() >= until:
                fetch_errors.append(f"the group's offsets: {e!r}"[:200])
                break
            await asyncio.sleep(RETRY_S)

    def said(what: str) -> None:
        rule = what.split(":")[0]
        broken[rule].append(what)

    logs: dict[str, dict[int, list[bytes]]] = {source: {}, sink: {}}
    unread: set[int] = set()
    for topic, starts in ((source, start_src), (sink, start_sink)):
        for p, at in starts.items():
            try:
                logs[topic][p] = await whole_log(reader, topic, p, at)
            except Exception as e:
                fetch_errors.append(f"read back of {topic}/{p}: {e!r}"[:200])
                logs[topic][p] = []
                if topic == sink:
                    unread.add(p)
    for c in everyone:
        await c.close()
    want = rekeyreplay.expected(
        logs[source], start_src, logs[sink],
        {p: committed.get((source, p)) for p in parts}, n_out)
    wrong_sinks = set(want.wrong_sinks) | unread
    for what in want.breaks:
        said(what)
    for q in outs:
        news = rekeyreplay.departures(want, q, in_order[q])
        for base, marker in replay(logs[sink][q]).closed_by.items():
            under = served_under.get((q, base))
            if under is not None and under <= marker and (q, base) in delivered:
                news.append(f"isolation: sink {q} at {base}: a read_committed fetch "
                            f"returned it under a high watermark of {under}, its "
                            f"marker is at {marker}")
        for base in in_order[q]:
            t_end = end_sent.get((q, base))
            if t_end is not None and delivered[(q, base)][0] < t_end:
                news.append(f"isolation: sink {q} at {base}: handed on before its "
                            "EndTxn was sent")
        for what in news:
            said(what)
        if news:
            wrong_sinks.add(q)

    # which source batch each row is, and where its records' copies went
    out_rows = []
    for row in rows:
        p, base = row[1], row[3]
        seen = (0.0, -2, 0)
        if base >= 0 and row[6] is None:
            offsets = range(base, base + tpl[row[2]].records)
            places = [want.placed.get((p, o)) for o in offsets]
            if all(places):
                bad = any((p, o) in want.wrong for o in offsets) or any(
                    q in wrong_sinks for q, _b in places)
                got = [delivered.get(pl) for pl in places]
                if all(got):
                    when = max(g[0] for g in got)
                    # the row's share of the sink batches that hold it
                    share = round(sum(g[1] / g[2] for g in got))
                    seen = (when, -1 if bad else row[2], share)
                elif bad:
                    seen = (0.0, -1, 0)
        out_rows.append(row + list(seen))
    # the first of each broken rule ahead of the rest: the harness keeps
    # the first five
    fetch_errors[:0] = [what for rule in broken.values() for what in rule[:1]] + [
        what for rule in broken.values() for what in rule[1:]]
    pending = sum(1 for r in rows if r[3] < 0 and r[6] is None)
    return {
        "t0": t0,
        "seconds": seconds,
        "steps": steps,
        "columns": ["topic", "partition", "template", "base", "t_due",
                    "t_ack", "error", "t_sent", "tries", "in_request",
                    "t_fetch", "fetched_template", "fetched_bytes"],
        "rows": out_rows,
        "unanswered": pending + len(unanswered),
        "consumers_stuck": len(stuck),
        "requests": counts["requests"],
        "fetches": counts["fetches"],
        "fetch_errors": fetch_errors[:50],
        "fetch_error_count": len(fetch_errors),
        "late_s": sorted(late),
        "handed_late_s": sorted(handed),
        "generator_cpu_share": cpu_share,
        "payload_bytes": tpl[0].payload_bytes,
        "clients": {"producers": n_src, "members": n_members, "consumers": n_cons,
                    "max_in_flight": 1, **counts,
                    "batches_handed_on": sum(len(v) for v in in_order.values()),
                    # sink partitions a transaction wrote to: the data
                    # markers its EndTxn had delivered
                    "fanout_p50": statistics.median(fanout) if fanout else None,
                    "fanout_mean": round(statistics.fmean(fanout), 3) if fanout else None,
                    "stuck": stuck[:5],
                    "broken": {rule: len(s) for rule, s in broken.items()}},
    }
