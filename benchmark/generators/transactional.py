"""The transactional generator: producers that send one transaction a
batch at fixed times, whatever the brokers answer, and polling
`read_committed` consumers (KIP-98; the traffic file says which clients
and at what rate).

A producer is one client with a `transactional.id` of its own and one
transaction open at a time: begin, AddPartitionsToTxn at its
coordinator, the batch to the partition's leader (acks=all), EndTxn,
commit or abort as the seed drew. A batch is due when its first record
is, `linger_ms` before it closes; one that is due while the producer's
last transaction is still ending waits for it, and its latency counts
from when it was due. The batch is a pre-encoded template into which
the producer stamps its id, epoch and the partition's next sequence
(templates/transactional.py); a not-leader or not-coordinator answer is
retried inside the latency, the batch with the same sequence.

A consumer tails its share of the partitions from its own position with
one fetch in flight a broker: `isolation.level=read_committed`,
`fetch.min.bytes` and `fetch.max.wait.ms` as the traffic says, so a
fetch that finds nothing parks at the broker. It learns nothing out of
band: what it is handed is what the Kafka consumer's own filter
(txreplay.Filter) leaves of each response.

The record has `open_loop.run`'s keys and columns. Every batch due in
the window is a row, committed or aborted. `t_ack` is the produce's ack.
Of a committed batch `t_fetch` and `fetched_template` say when a
consumer held it and what its template said of it. Of an aborted batch
`fetched_template` is its own template only if a consumer's filter
dropped it on the broker's word, and `t_fetch` is that moment; one that
a consumer was handed is wrong. After the drain every partition is read
`read_uncommitted` and replayed (txreplay.replay): what the consumers
were handed has to be the replay's visible sequence, every producer's
sequences continuous, nothing handed on before its EndTxn was sent; a
row any of that touches is wrong too, and `fetch_errors` names the rule.

It runs in the load generator's own process and imports of the program
only its Kafka client and protocol codec.
"""

from __future__ import annotations

import asyncio
import json
import time

import numpy as np

from benchmark.generators.open_loop import NOT_LEADER, due_times, steps_of
from benchmark.reference import split_batches
from benchmark.run import resolve
from benchmark.txreplay import Filter, head_of, replay

#: coordinator_load_in_progress, coordinator_not_available,
#: not_coordinator, concurrent_transactions: ask again, the second and
#: third after asking who coordinates now
COORDINATOR_RETRY = (14, 15, 16, 51)
COORDINATOR_MOVED = (15, 16)
RETRY_S = 0.05
FETCH_MAX_BYTES = 52428800   # the consumer's fetch.max.bytes default


async def run(spec: dict, say) -> dict:
    """Drive one window. `spec` is what loadgen.py was handed; `say`
    prints one line to the harness. Returns the record file's content:
    one row a batch."""
    from redpanda_tpu.kafka.client import KafkaClient, KafkaClientError
    from redpanda_tpu.kafka.protocol import FETCH, PRODUCE, Msg
    from redpanda_tpu.kafka.protocol.group_apis import FIND_COORDINATOR, INIT_PRODUCER_ID
    from redpanda_tpu.kafka.protocol.tx_apis import ADD_PARTITIONS_TO_TXN, END_TXN

    traffic, config = spec["traffic"], spec["config"]
    n_prod, n_cons = int(traffic["producers"]), int(traffic["consumers"])
    seed, seconds = int(spec["seed"]), float(spec["seconds"])
    steps = steps_of(traffic, seconds)
    seconds = sum(s for s, _r in steps)
    bootstrap = [tuple(a) for a in spec["bootstrap"]]
    tpl = resolve(traffic["templates"]["maker"], "templates")(seed, traffic, config)
    by_key = {t.key: i for i, t in enumerate(tpl)}
    key_of = tpl[0].key_of
    linger = float(traffic["linger_ms"]) / 1e3
    timeout_ms = int(traffic["request_timeout_ms"])
    fetch_bytes = int(traffic["fetch_max_bytes"])
    fetch_wait = int(traffic["fetch_max_wait_ms"])
    fetch_min = int(traffic["fetch_min_bytes"])
    abort_share = float(traffic["abort_share"])
    tx_timeout_ms = int(traffic["transaction_timeout_ms"])
    ack_sample_s = float(traffic["ack_sample_s"])
    drain = float(traffic["drain_s"])
    acks = int(config["acks"])

    work = [(t["name"], p) for t in spec["topics"] for p in range(t["partitions"])]
    rng = np.random.default_rng(seed)
    owner = {tp: i % n_cons for i, tp in enumerate(work[j] for j in rng.permutation(len(work)))}
    topics = sorted({t for t, _p in work})

    # one row a batch:
    # [topic, p, tpl, base, t_due, t_ack, err, t_sent, tries, in_request]
    rows: list[list] = []
    # beside each row: [commit, when EndTxn was sent, when it was answered]
    ends: list[list] = []
    fetch_errors: list[str] = []
    fetches = requests = 0
    late: list[float] = []
    handed: list[float] = []
    last_sample = 0.0
    # (topic, partition, base) -> (when, which template, how many bytes)
    delivered: dict[tuple[str, int, int], tuple[float, int, int]] = {}
    dropped: dict[tuple[str, int, int], tuple[float, int]] = {}
    # the high watermark of the response that held the batch
    served_under: dict[tuple[str, int, int], int] = {}
    in_order: dict[tuple[str, int], list[int]] = {tp: [] for tp in work}
    twice: list[tuple[str, int, int]] = []
    owed: set[tuple[str, int, int]] = set()   # acknowledged, not yet seen by a consumer
    give_up = float("inf")

    def fail(row: list, what: str) -> None:
        row[5], row[6] = time.monotonic(), what[:200]

    # ------------------------------------------------------------ producers
    class Producer:
        def __init__(self, i: int) -> None:
            self.client = KafkaClient(bootstrap)
            self.tx_id = f"bench-tx-{i}"
            self.producer_id = self.epoch = -1
            self.coordinator = None
            self.sequence: dict[tuple[str, int], int] = {}

        async def find_coordinator(self) -> None:
            conn = await self.client.any_conn()
            resp = await conn.request(
                FIND_COORDINATOR, Msg(key=self.tx_id, key_type=1),
                conn.pick_version(FIND_COORDINATOR, 1))
            if resp.error_code or resp.node_id < 0:
                raise RuntimeError(f"find_coordinator: error_code {resp.error_code}")
            self.coordinator = await self.client._connect_addr((resp.host, resp.port))

        async def ask_coordinator(self, api, req, code_of, until: float) -> tuple:
            """(error code, answer), once the code is none of those a
            client answers by asking again."""
            nonlocal requests
            code = -1
            while True:
                try:
                    if self.coordinator is None or code in COORDINATOR_MOVED:
                        await self.find_coordinator()
                    requests += 1
                    conn = self.coordinator
                    resp = await conn.request(api, req, conn.pick_version(api, 1))
                except (OSError, RuntimeError, KafkaClientError) as e:
                    code, self.coordinator = -1, None
                    if time.monotonic() >= until:
                        raise RuntimeError(f"{api.name}: {e!r}") from e
                else:
                    code = code_of(resp)
                    if code not in COORDINATOR_RETRY or time.monotonic() >= until:
                        return code, resp
                await asyncio.sleep(RETRY_S)

        async def init(self, until: float) -> None:
            code, resp = await self.ask_coordinator(
                INIT_PRODUCER_ID,
                Msg(transactional_id=self.tx_id, transaction_timeout_ms=tx_timeout_ms),
                lambda r: r.error_code, until)
            if code:
                raise RuntimeError(f"init_producer_id {self.tx_id}: error_code {code}")
            self.producer_id, self.epoch = resp.producer_id, resp.producer_epoch

        async def transact(self, row: list, end: list) -> None:
            """One transaction of one batch; fills the row and `end`."""
            nonlocal requests
            topic, p = row[0], row[1]
            who = {"transactional_id": self.tx_id, "producer_id": self.producer_id,
                   "producer_epoch": self.epoch}
            row[7] = time.monotonic()
            late.append(row[7] - (row[4] + linger))
            code, _resp = await self.ask_coordinator(
                ADD_PARTITIONS_TO_TXN,
                Msg(**who, topics=[Msg(name=topic, partitions=[p])]),
                lambda r: max(x.error_code for t in r.results for x in t.results),
                give_up)
            if code:
                return fail(row, f"add_partitions_to_txn: error_code {code}")
            t = tpl[row[2]]
            sequence = self.sequence.get((topic, p), 0)
            req = Msg(
                transactional_id=self.tx_id, acks=acks, timeout_ms=timeout_ms,
                topics=[Msg(name=topic, partitions=[Msg(
                    index=p, records=t.stamp(self.producer_id, self.epoch, sequence))])])
            refresh = False
            while True:
                row[8] += 1
                row[9] = 1
                requests += 1
                try:
                    conn = await self.client.leader_conn(topic, p, refresh=refresh)
                    v = conn.pick_version(PRODUCE, 7)
                    resp = PRODUCE.decode_response(await conn.request_body(
                        PRODUCE, PRODUCE.encode_request(req, v), v), v)
                    pr = resp.responses[0].partition_responses[0]
                    code, base = pr.error_code, pr.base_offset
                except Exception as e:  # the connection failed: the batch did
                    fail(row, repr(e))
                    break
                now = time.monotonic()
                if code == 0:
                    self.sequence[(topic, p)] = sequence + t.records
                    acked(row, base, now)
                    break
                if code not in NOT_LEADER or now >= give_up:
                    fail(row, f"error_code {code}")
                    break
                await asyncio.sleep(RETRY_S)
                refresh = True
            commit = end[0] and row[6] is None   # a batch that failed is not committed
            end[1] = time.monotonic()
            code, _resp = await self.ask_coordinator(
                END_TXN, Msg(**who, committed=commit), lambda r: r.error_code, give_up)
            end[2] = time.monotonic()
            if code and row[6] is None:
                fail(row, f"end_txn: error_code {code}")
            key = (topic, p, row[3])
            if row[6] is None and key not in delivered and key not in dropped:
                # the marker is written before EndTxn is answered, so a
                # consumer has often seen the batch by now
                owed.add(key)

    def acked(row: list, base: int, now: float) -> None:
        nonlocal last_sample
        row[3], row[5] = base, now
        if now - last_sample >= ack_sample_s:
            # the harness reads, as this ack arrives, how many replicas
            # have flushed it
            last_sample = now
            say("acked " + json.dumps([row[0], row[1], base + tpl[row[2]].records]))

    async def producer(i: int, me: Producer) -> None:
        due = t0 + due_times(steps, n_prod, i)
        prng = np.random.default_rng([seed, i])
        order = np.concatenate([
            prng.permutation(len(work)) for _ in range(len(due) // len(work) + 1)
        ])
        aborts = aborted_of[i]
        free_at = 0.0
        for k, t_due in enumerate(due):
            ready = max(t_due + linger, free_at)
            wait = ready - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            handed.append(time.monotonic() - ready)
            tp = work[order[k]]
            row = [tp[0], tp[1], (k + i) % len(tpl), -1, float(t_due), 0.0,
                   None, 0.0, 0, 0]
            end = [not aborts[k], 0.0, 0.0]
            rows.append(row)
            ends.append(end)
            try:
                await me.transact(row, end)
            except RuntimeError as e:
                fail(row, str(e))
            free_at = time.monotonic()

    # ------------------------------------------------------------ consumers
    class Consumer:
        def __init__(self, j: int) -> None:
            self.client = KafkaClient(bootstrap)
            self.mine = [tp for tp in work if owner[tp] == j]
            self.position: dict[tuple[str, int], int] = {}
            self.start: dict[tuple[str, int], int] = {}
            self.fetchers: dict = {}   # connection -> {tps, more, task}

        async def assign(self, tp: tuple[str, int], refresh: bool = False) -> None:
            """Hands the partition to the fetcher of the broker that
            leads it, once the metadata names one."""
            while True:
                if refresh:
                    await asyncio.sleep(RETRY_S)
                try:
                    conn = await self.client.leader_conn(tp[0], tp[1], refresh=refresh)
                    break
                except (OSError, KafkaClientError) as e:
                    fetch_errors.append(f"{tp}: {e!r}"[:200])
                    refresh = True
            if conn not in self.fetchers:
                f = {"tps": set(), "more": asyncio.Event()}
                f["task"] = asyncio.ensure_future(self.fetcher(conn, f))
                self.fetchers[conn] = f
            self.fetchers[conn]["tps"].add(tp)
            self.fetchers[conn]["more"].set()

        def request(self, asked: dict, isolation: int, wait_ms: int, min_bytes: int) -> Msg:
            by_topic: dict[str, list] = {}
            for (topic, p), at in asked.items():
                by_topic.setdefault(topic, []).append((p, at))
            return Msg(
                rack_id="", replica_id=-1, max_wait_ms=wait_ms, min_bytes=min_bytes,
                max_bytes=FETCH_MAX_BYTES, isolation_level=isolation, session_id=0,
                session_epoch=-1, forgotten_topics_data=[],
                topics=[
                    Msg(topic=t, partitions=[
                        Msg(partition=p, current_leader_epoch=-1, fetch_offset=at,
                            log_start_offset=0, partition_max_bytes=fetch_bytes)
                        for p, at in ps])
                    for t, ps in by_topic.items()
                ],
            )

        async def fetcher(self, conn, mine: dict) -> None:
            """This consumer's fetches from one broker, one in flight,
            as a Kafka consumer has: each names every partition of this
            broker it tails, from its own position."""
            nonlocal fetches
            tps, more = mine["tps"], mine["more"]
            while True:
                if not tps:
                    more.clear()
                    await more.wait()
                asked = {tp: self.position[tp] for tp in tps}
                answers: dict = {}
                try:
                    resp = await conn.request(
                        FETCH, self.request(asked, 1, fetch_wait, fetch_min),
                        conn.pick_version(FETCH, 11))
                    for t in resp.responses:
                        for pr in t.partitions:
                            answers[(t.topic, pr.partition_index)] = pr
                except Exception as e:
                    fetch_errors.append(f"fetch of {len(asked)} partitions: {e!r}"[:200])
                    await asyncio.sleep(RETRY_S)
                now = time.monotonic()
                fetches += 1
                for tp, at in asked.items():
                    pr = answers.get(tp)
                    if pr is None or pr.error_code in NOT_LEADER:
                        # as a consumer does: ask who leads it now, and there
                        tps.discard(tp)
                        tasks.append(asyncio.ensure_future(self.assign(tp, refresh=True)))
                    elif pr.error_code:
                        fetch_errors.append(f"{tp} at {at}: error_code {pr.error_code}")
                        await asyncio.sleep(RETRY_S)
                    else:
                        self.hand_on(tp, at, pr, now)

        def hand_on(self, tp: tuple[str, int], at: int, pr, now: float) -> None:
            """What the consumer's filter leaves of one partition's
            answer; the position moves past everything it read."""
            flt = Filter([(a.producer_id, a.first_offset)
                          for a in pr.aborted_transactions or []])
            for base, batch in split_batches(bytes(pr.records or b"")):
                last = head_of(batch).last
                if last < at:
                    continue   # a batch may begin before the offset asked for
                what = flt.take(batch)
                key = (tp[0], tp[1], base)
                served_under.setdefault(key, pr.high_watermark)
                if what == "deliver":
                    ti = by_key.get(key_of(batch), -1)
                    if ti >= 0 and not tpl[ti].came_back(batch):
                        ti = -1
                    if key in delivered:
                        twice.append(key)
                    delivered[key] = (now, ti, len(batch))
                    in_order[tp].append(base)
                elif what == "aborted":
                    dropped.setdefault(key, (now, len(batch)))
                owed.discard(key)
                at = last + 1
            self.position[tp] = at

        async def seek_to_end(self, tp: tuple[str, int], until: float) -> None:
            """auto.offset.reset=latest: set-up's batch lies before."""
            while True:
                try:
                    at = await self.client.list_offset(tp[0], tp[1], -1)
                    break
                except KafkaClientError:   # the leadership moved: ask who leads it now
                    if time.monotonic() >= until:
                        raise
                    await asyncio.sleep(RETRY_S)
                    await self.client.leader_conn(tp[0], tp[1], refresh=True)
            self.start[tp] = self.position[tp] = at

        async def whole_log(self, tp: tuple[str, int]) -> list[bytes]:
            """Everything the partition holds from where this consumer
            began, read `read_uncommitted`."""
            out, at = [], self.start[tp]
            refresh = False
            while time.monotonic() < give_up + drain:
                conn = await self.client.leader_conn(tp[0], tp[1], refresh=refresh)
                resp = await conn.request(
                    FETCH, self.request({tp: at}, 0, 0, 0), conn.pick_version(FETCH, 11))
                pr = resp.responses[0].partitions[0]
                if pr.error_code in NOT_LEADER:
                    refresh = True
                    await asyncio.sleep(RETRY_S)
                    continue
                if pr.error_code:
                    raise RuntimeError(f"{tp} at {at}: error_code {pr.error_code}")
                for _base, batch in split_batches(bytes(pr.records or b"")):
                    last = head_of(batch).last
                    if last >= at:
                        out.append(batch)
                        at = last + 1
                if at >= pr.high_watermark:
                    return out
            raise RuntimeError(f"{tp}: the log was not read to its end")

    # --------------------------------------------------------------- set-up
    producers = [Producer(i) for i in range(n_prod)]
    consumers = [Consumer(j) for j in range(n_cons)]
    tasks: list = []
    for c in producers + consumers:
        await c.client.metadata(topics)
    # the coordinator topic is made by the first InitProducerId and its
    # partitions elect their leaders before any producer has its id
    ready_by = time.monotonic() + 45
    await asyncio.gather(*(p.init(ready_by) for p in producers))
    for c in consumers:
        for tp in c.mine:
            await c.seek_to_end(tp, ready_by)

    # which transactions abort: `abort_share` of all that are due, to the
    # nearest whole one, drawn from the seed over all the producers
    counts = [len(due_times(steps, n_prod, i)) for i in range(n_prod)]
    flags = np.zeros(sum(counts), bool)
    flags[: int(round(abort_share * len(flags)))] = True
    np.random.default_rng([seed, n_prod]).shuffle(flags)
    aborted_of = np.split(flags, np.cumsum(counts)[:-1])

    say("armed")
    t0 = time.monotonic() + 0.05
    give_up = t0 + seconds + drain
    say(f"window_start {t0!r}")
    for c in consumers:
        for tp in c.mine:
            await c.assign(tp)
    prods = [asyncio.ensure_future(producer(i, p)) for i, p in enumerate(producers)]
    cpu0 = time.process_time()
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    cpu_share = (time.process_time() - cpu0) / seconds
    say(f"window_end {time.monotonic()!r}")
    # what is in flight is waited for: late is late, not wrong
    _done, unanswered = await asyncio.wait(prods, timeout=drain)
    while owed and time.monotonic() < give_up:
        await asyncio.sleep(0.02)
    stuck = {owner[(topic, p)] for topic, p, _base in owed}
    polling = [f["task"] for c in consumers for f in c.fetchers.values()]
    for t in (*unanswered, *polling, *tasks):
        t.cancel()
    await asyncio.gather(*prods, *polling, *tasks, return_exceptions=True)

    # ----------------------------------------------- the replay of the logs
    wrong: set[tuple[str, int, int]] = set(twice)
    # what broke, by the guarantee it breaks (the configuration's names)
    broken: dict[str, list[str]] = {"isolation": [], "idempotence": [], "atomicity": []}
    for key in twice:
        broken["atomicity"].append(f"atomicity: {key} was handed on twice")

    committed: dict = {}   # partition -> the replay's visible and aborted base offsets
    aborted: dict = {}

    async def replay_mine(c: Consumer) -> None:
        for tp in c.mine:
            try:
                log = replay(await c.whole_log(tp))
            except Exception as e:
                fetch_errors.append(f"replay of {tp}: {e!r}"[:200])
                wrong.update(k for k in delivered if k[:2] == tp)
                continue
            if in_order[tp] != log.visible:
                broken["atomicity"].append(
                    f"atomicity: {tp}: consumers were handed {in_order[tp][:8]}..., "
                    f"the replay's visible sequence is {log.visible[:8]}...")
                apart = set(in_order[tp]) ^ set(log.visible)
                wrong.update((tp[0], tp[1], b) for b in apart or in_order[tp])
            for producer_id, base, expected, found in log.sequence_breaks:
                broken["idempotence"].append(
                    f"idempotence: {tp} at {base}: producer {producer_id} stored "
                    f"sequence {found} where {expected} follows")
                wrong.add((tp[0], tp[1], base))
            for base in log.open:
                broken["atomicity"].append(f"atomicity: {tp} at {base}: no marker closed it")
                wrong.add((tp[0], tp[1], base))
            for base, marker in log.closed_by.items():
                under = served_under.get((tp[0], tp[1], base))
                if under is not None and under <= marker:
                    broken["isolation"].append(
                        f"isolation: {tp} at {base}: a read_committed fetch returned it "
                        f"under a high watermark of {under}, its marker is at {marker}")
                    wrong.add((tp[0], tp[1], base))
            committed[tp], aborted[tp] = set(log.visible), set(log.aborted)

    await asyncio.gather(*(replay_mine(c) for c in consumers))
    for c in producers + consumers:
        await c.client.close()

    out_rows = []
    for row, (commit, end_sent, _end_acked) in zip(rows, ends):
        tp, key = (row[0], row[1]), (row[0], row[1], row[3])
        got = delivered.get(key)
        if row[3] < 0 or row[6] is not None:
            seen = (0.0, -2, 0)
        elif got is not None:
            if got[0] < end_sent:
                broken["isolation"].append(
                    f"isolation: {key} was handed on {end_sent - got[0]:.4f} s before "
                    "its EndTxn was sent")
            ok = commit and key not in wrong and got[0] >= end_sent \
                and row[3] in committed.get(tp, ())
            if not commit:
                broken["atomicity"].append(f"atomicity: {key} was aborted and handed on")
            seen = got if ok else (got[0], -1, got[2])
        elif not commit and key in dropped:
            ok = key not in wrong and row[3] in aborted.get(tp, ())
            seen = (dropped[key][0], row[2] if ok else -1, dropped[key][1])
        else:
            seen = (0.0, -2, 0)
        out_rows.append(row + list(seen))
    for rule in broken.values():   # the cause before what follows from it
        fetch_errors.extend(rule)
    pending = sum(1 for r in rows if r[3] < 0 and r[6] is None)
    n_aborted = sum(1 for commit, *_ in ends if not commit)
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
        "requests": requests,
        "fetches": fetches,
        "fetch_errors": fetch_errors[:50],
        "fetch_error_count": len(fetch_errors),
        "late_s": sorted(late),
        "handed_late_s": sorted(handed),
        "generator_cpu_share": cpu_share,
        "payload_bytes": tpl[0].payload_bytes,
        "clients": {"producers": n_prod, "consumers": n_cons, "max_in_flight": 1,
                    "transactions": len(rows), "aborted": n_aborted,
                    "aborted_dropped_by_filter": sum(
                        1 for r, e in zip(out_rows, ends) if not e[0] and r[11] == r[2]),
                    "committed_handed_on": sum(
                        1 for r, e in zip(out_rows, ends) if e[0] and r[11] == r[2]),
                    "broken": {rule: len(said) for rule, said in broken.items()}},
    }
