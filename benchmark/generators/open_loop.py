"""The open-loop generator: producers that close a batch at fixed times,
whatever the brokers answer, and send as a Kafka producer's sender does;
tailing consumers.

One general generator for every open-loop mix: what varies (clients,
batch, rate or a staircase of rates, requests in flight, request and
fetch sizes, the template maker) is data in the traffic file. It runs in
the load generator's own process and imports of the program only its
Kafka client and protocol codec.

A batch is due when its first record is: `linger_ms` later the batch
closes and is handed to the sender. The sender keeps at most
`max_in_flight` requests outstanding on each broker's connection. With a
slot free a closed batch goes out at once; with none it waits, and the
next request to that broker takes everything that waits for it, one
batch a partition, up to `max_request_bytes`: the multi-partition
produce request a real client sends when the brokers fall behind.
"""

from __future__ import annotations

import asyncio
import json
import time
from collections import deque

import numpy as np

from benchmark.reference import split_batches
from benchmark.run import resolve

NOT_LEADER = (3, 5, 6)  # unknown_topic_or_partition, leader_not_available, not_leader


def steps_of(traffic: dict, seconds: float) -> list[tuple[float, float]]:
    """[(seconds, batches a second)]: the traffic's `schedule`, or its
    one rate for the whole window."""
    if "schedule" in traffic:
        return [(float(s), float(r)) for s, r in traffic["schedule"]]
    return [(seconds, float(traffic["batches_per_s"]))]


def due_times(steps: list, producers: int, i: int) -> np.ndarray:
    """Seconds after the window opens at which producer `i`'s batches
    are due: evenly spaced in every step, the producers staggered."""
    out, start = [], 0.0
    for secs, rate in steps:
        if rate > 0:
            every = producers / rate
            k = np.arange(int(np.ceil(secs / every)) + 1)
            t = start + (i / producers + k) * every
            out.append(t[t < start + secs])
        start += secs
    return np.concatenate(out) if out else np.zeros(0)


async def run(spec: dict, say) -> dict:
    """Drive one window. `spec` is what loadgen.py was handed; `say`
    prints one line to the harness. Returns the record file's content:
    one row a batch."""
    from redpanda_tpu.kafka.client import KafkaClient
    from redpanda_tpu.kafka.protocol import FETCH, PRODUCE, Msg

    traffic, config = spec["traffic"], spec["config"]
    n_prod, n_cons = int(traffic["producers"]), int(traffic["consumers"])
    seed, seconds = int(spec["seed"]), float(spec["seconds"])
    steps = steps_of(traffic, seconds)
    seconds = sum(s for s, _r in steps)
    bootstrap = [tuple(a) for a in spec["bootstrap"]]
    tpl = resolve(traffic["templates"]["maker"], "templates")(seed, traffic, config)
    # what a stored batch is known by, and whether it came back, are the
    # templates' to say (benchmark/reference.py)
    by_key = {t.key: i for i, t in enumerate(tpl)}
    key_of = tpl[0].key_of
    linger = float(traffic["linger_ms"]) / 1e3
    max_in_flight = int(traffic["max_in_flight"])
    max_request = int(traffic["max_request_bytes"])
    timeout_ms = int(traffic["request_timeout_ms"])
    fetch_bytes = int(traffic["fetch_max_bytes"])
    fetch_wait = int(traffic["fetch_max_wait_ms"])
    ack_sample_s = float(traffic["ack_sample_s"])
    acks = int(config["acks"])

    work = [(t["name"], p) for t in spec["topics"] for p in range(t["partitions"])]
    rng = np.random.default_rng(seed)
    owner = {tp: i % n_cons for i, tp in enumerate(work[j] for j in rng.permutation(len(work)))}

    producers = [KafkaClient(bootstrap) for _ in range(n_prod)]
    consumers = [KafkaClient(bootstrap) for _ in range(n_cons)]
    topics = sorted({t for t, _p in work})
    for c in producers + consumers:
        await c.metadata(topics)

    # one row a batch:
    # [topic, p, tpl, base, t_due, t_ack, err, t_sent, tries, in_request]
    rows: list[list] = []
    # (topic, partition, base) -> when, which template, how many bytes
    fetched: dict[tuple[str, int, int], tuple[float, int, int]] = {}
    fetch_errors: list[str] = []
    fetches = requests = 0
    late: list[float] = []    # first send after the batch closed: the sender's queue too
    handed: list[float] = []  # handed to the sender after it closed: the generator's own
    # acknowledged base offsets no fetch has returned yet, by partition:
    # acks of one partition reach different producers in any order
    want: dict[tuple[str, int], set] = {}
    wake = [asyncio.Queue() for _ in range(n_cons)]
    sends: set = set()
    last_sample = 0.0

    def acked(row: list, base: int, now: float) -> None:
        nonlocal last_sample
        tp = (row[0], row[1])
        row[3], row[5] = base, now
        upto = base + tpl[row[2]].records
        if (tp[0], tp[1], base) not in fetched:
            want.setdefault(tp, set()).add(base)
            wake[owner[tp]].put_nowait(tp)
        if now - last_sample >= ack_sample_s:
            # the harness reads, as this ack arrives, how many replicas
            # have flushed it
            last_sample = now
            say("acked " + json.dumps([row[0], row[1], upto]))

    class Sender:
        """One producer's sender: queues and requests in flight, by
        broker connection."""

        def __init__(self, client) -> None:
            self.client = client
            self.waiting: dict = {}    # connection -> deque of rows
            self.in_flight: dict = {}  # connection -> requests outstanding

        async def submit(self, row: list, refresh: bool = False) -> None:
            try:
                conn = await self.client.leader_conn(row[0], row[1], refresh=refresh)
            except Exception as e:
                row[5], row[6] = time.monotonic(), repr(e)[:200]
                return
            self.waiting.setdefault(conn, deque()).append(row)
            self.pump(conn)

        def pump(self, conn) -> None:
            q = self.waiting.get(conn)
            while q and self.in_flight.get(conn, 0) < max_in_flight:
                taken, seen, size, rest = [], set(), 0, deque()
                while q:
                    row = q.popleft()
                    n = len(tpl[row[2]].wire)
                    if (row[0], row[1]) in seen or (taken and size + n > max_request):
                        rest.append(row)
                        continue
                    seen.add((row[0], row[1]))
                    taken.append(row)
                    size += n
                q.extend(rest)
                self.in_flight[conn] = self.in_flight.get(conn, 0) + 1
                task = asyncio.ensure_future(self.send(conn, taken))
                sends.add(task)
                task.add_done_callback(sends.discard)

        async def send(self, conn, taken: list) -> None:
            nonlocal requests
            requests += 1
            by_topic: dict[str, list] = {}
            for row in taken:
                by_topic.setdefault(row[0], []).append(row)
                if not row[8]:
                    row[7] = time.monotonic()
                    late.append(row[7] - (row[4] + linger))
                row[8] += 1
                row[9] = len(taken)
            answers: dict = {}
            error = None
            try:
                v = conn.pick_version(PRODUCE, 7)
                req = Msg(
                    transactional_id=None, acks=acks, timeout_ms=timeout_ms,
                    topics=[
                        Msg(name=t, partitions=[
                            Msg(index=r[1], records=tpl[r[2]].wire) for r in rs])
                        for t, rs in by_topic.items()
                    ],
                )
                body = PRODUCE.encode_request(req, v)
                resp = PRODUCE.decode_response(await conn.request_body(PRODUCE, body, v), v)
                for t in resp.responses:
                    for pr in t.partition_responses:
                        answers[(t.name, pr.index)] = (pr.error_code, pr.base_offset)
            except Exception as e:  # the connection failed: every batch did
                error = repr(e)[:200]
            finally:
                self.in_flight[conn] -= 1
            now = time.monotonic()
            again = []
            for row in taken:
                code, base = answers.get((row[0], row[1]), (-1, -1))
                if error is None and code == 0:
                    acked(row, base, now)
                elif error is None and code in NOT_LEADER and now < give_up:
                    again.append(row)
                else:
                    row[5], row[6] = now, error or f"error_code {code}"
            self.pump(conn)
            if again:
                await asyncio.sleep(0.05)
                for row in again:
                    await self.submit(row, refresh=True)

    async def producer(i: int) -> None:
        sender = Sender(producers[i])
        due = t0 + due_times(steps, n_prod, i)
        prng = np.random.default_rng([seed, i])
        order = np.concatenate([
            prng.permutation(len(work)) for _ in range(len(due) // len(work) + 1)
        ])
        for k, t_due in enumerate(due):
            wait = t_due + linger - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            handed.append(time.monotonic() - (t_due + linger))
            tp = work[order[k]]
            row = [tp[0], tp[1], (k + i) % len(tpl), -1, float(t_due), 0.0,
                   None, 0.0, 0, 0]
            rows.append(row)
            await sender.submit(row)

    async def consumer(j: int) -> None:
        """Hands every partition an ack points to to the fetcher of the
        broker that leads it, and once the window is over stays until no
        fetcher has anything left to ask or to hand back."""
        c = consumers[j]
        fetchers: dict = {}  # connection -> {todo, more, busy, task}
        refresh: set = set()
        closing = False
        while True:
            if closing and wake[j].empty() and not any(
                f["busy"] or f["todo"] for f in fetchers.values()
            ):
                break
            try:
                tp = await asyncio.wait_for(wake[j].get(), 0.05 if closing else None)
            except asyncio.TimeoutError:
                continue
            if tp is None:
                closing = True
                continue
            try:
                conn = await c.leader_conn(tp[0], tp[1], refresh=tp in refresh)
                refresh.discard(tp)
            except Exception as e:
                fetch_errors.append(f"{tp}: {e!r}"[:200])
                continue
            if conn not in fetchers:
                f = {"todo": set(), "more": asyncio.Event(), "busy": False}
                f["task"] = asyncio.ensure_future(fetcher(j, conn, f, refresh))
                fetchers[conn] = f
            fetchers[conn]["todo"].add(tp)
            fetchers[conn]["more"].set()
        for f in fetchers.values():
            f["task"].cancel()
        await asyncio.gather(*(f["task"] for f in fetchers.values()),
                             return_exceptions=True)

    async def fetcher(j: int, conn, mine: dict, refresh: set) -> None:
        """One consumer's fetches from one broker, one in flight, as a
        Kafka consumer has: each names every partition of this broker an
        ack has pointed to and no fetch has answered yet, from the lowest
        such offset, at most `fetch_max_bytes` a partition."""
        nonlocal fetches
        todo, more = mine["todo"], mine["more"]
        tries = 0
        while True:
            mine["busy"] = False
            await more.wait()
            more.clear()
            mine["busy"] = True
            asked = {tp: min(want[tp]) for tp in todo if want[tp]}
            todo.clear()
            if not asked or time.monotonic() > give_up:
                continue
            by_topic: dict[str, list] = {}
            for (topic, p), at in asked.items():
                by_topic.setdefault(topic, []).append((p, at))
            req = Msg(
                rack_id="", replica_id=-1, max_wait_ms=fetch_wait, min_bytes=0,
                max_bytes=0x7FFFFFFF, isolation_level=0, session_id=0,
                session_epoch=-1, forgotten_topics_data=[],
                topics=[
                    Msg(topic=t, partitions=[
                        Msg(partition=p, current_leader_epoch=-1, fetch_offset=at,
                            log_start_offset=0, partition_max_bytes=fetch_bytes)
                        for p, at in ps])
                    for t, ps in by_topic.items()
                ],
            )
            answers: dict = {}
            try:
                resp = await conn.request(FETCH, req, conn.pick_version(FETCH, 11))
                for t in resp.responses:
                    for pr in t.partitions:
                        answers[(t.topic, pr.partition_index)] = (
                            pr.error_code, bytes(pr.records or b""))
            except Exception as e:
                fetch_errors.append(f"fetch of {len(asked)} partitions: {e!r}"[:200])
            now = time.monotonic()
            fetches += 1
            again = False
            for tp, at in asked.items():
                code, wire = answers.get(tp, (-1, b""))
                got = split_batches(wire) if code == 0 else []
                for base, batch in got:
                    ti = by_key.get(key_of(batch), -1)
                    if ti >= 0 and not tpl[ti].came_back(batch):
                        ti = -1
                    fetched.setdefault((tp[0], tp[1], base), (now, ti, len(batch)))
                    want[tp].discard(base)
                if got and at in want[tp]:
                    # the answer began elsewhere than at the offset asked
                    # for: that base is never read
                    fetch_errors.append(f"{tp}: asked {at}, got {got[0][0]}")
                    want[tp].discard(at)
                if code in NOT_LEADER:
                    # as a consumer does: ask who leads it now, and there
                    refresh.add(tp)
                    wake[j].put_nowait(tp)
                elif want[tp]:
                    if code != 0 and answers:
                        fetch_errors.append(f"{tp} at {at}: error_code {code}")
                    # an error or an empty answer to what was acknowledged,
                    # so committed: the broker's to explain; keep asking
                    again = again or not got
                    todo.add(tp)
            if todo:
                more.set()
            tries = tries + 1 if again else 0
            if again:
                await asyncio.sleep(min(0.005 * tries, 0.25))

    say("armed")
    drain = float(traffic["drain_s"])
    t0 = time.monotonic() + 0.05
    give_up = t0 + seconds + drain
    say(f"window_start {t0!r}")
    cons = [asyncio.ensure_future(consumer(j)) for j in range(n_cons)]
    prods = [asyncio.ensure_future(producer(i)) for i in range(n_prod)]
    cpu0 = time.process_time()
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    cpu_share = (time.process_time() - cpu0) / seconds
    say(f"window_end {time.monotonic()!r}")
    # what is in flight is waited for: late is late, not wrong
    _done, unsent = await asyncio.wait(prods, timeout=drain)
    # a request that comes back sends what waited behind it
    while sends and time.monotonic() < give_up:
        await asyncio.wait(set(sends), timeout=max(0.0, give_up - time.monotonic()))
    unanswered = set(sends)
    for q in wake:
        q.put_nowait(None)
    _done, stuck = await asyncio.wait(cons, timeout=drain)
    for t in (*unsent, *unanswered, *stuck):
        t.cancel()
    await asyncio.gather(*prods, *cons, *unanswered, return_exceptions=True)
    for c in producers + consumers:
        await c.close()

    out_rows = []
    for row in rows:
        out_rows.append(row + list(fetched.get((row[0], row[1], row[3]), (0.0, -2, 0))))
    pending = sum(1 for r in rows if r[3] < 0 and r[6] is None)
    return {
        "t0": t0,
        "seconds": seconds,
        "steps": steps,
        "columns": ["topic", "partition", "template", "base", "t_due",
                    "t_ack", "error", "t_sent", "tries", "in_request",
                    "t_fetch", "fetched_template", "fetched_bytes"],
        "rows": out_rows,
        "unanswered": pending,
        "consumers_stuck": len(stuck),
        "requests": requests,
        "fetches": fetches,
        "fetch_errors": fetch_errors[:50],
        "fetch_error_count": len(fetch_errors),
        "late_s": sorted(late),
        "handed_late_s": sorted(handed),
        # this process's CPU seconds over the window's: at 1 the generator,
        # one thread, is what the window measured
        "generator_cpu_share": cpu_share,
        "payload_bytes": tpl[0].payload_bytes,
        "clients": {"producers": n_prod, "consumers": n_cons,
                    "max_in_flight": max_in_flight},
    }
