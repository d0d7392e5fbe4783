"""The plain reference against known answers, and against the program's
own decoder (the one place a test here looks at both)."""

import pytest

from benchmark import reference as ref
from benchmark.run import percentile, reduce_records


def test_crc32c_known_vectors():
    assert ref.crc32c(b"123456789") == 0xE3069283
    assert ref.crc32c(b"") == 0
    assert ref.crc32c(bytes(32)) == 0x8A9136AA


def test_templates_repeat_from_the_seed():
    a = ref.make_templates(2**31 + 5, 3, 4, 128)
    b = ref.make_templates(2**31 + 5, 3, 4, 128)
    c = ref.make_templates(2**31 + 6, 3, 4, 128)
    assert [t.wire for t in a] == [t.wire for t in b]
    assert a[0].wire != c[0].wire
    assert a[0].payload_bytes == 4 * 128 and a[0].records == 4
    assert all(ref.batch_holds(t.wire) for t in a)
    assert len({t.crc for t in a}) == 3


def test_program_decodes_the_reference_batch():
    from redpanda_tpu.models.record import RecordBatch

    t = ref.make_templates(7, 1, 5, 64)[0]
    batch = RecordBatch.from_kafka_wire(t.wire, verify=True)
    recs = batch.records()
    assert len(recs) == 5
    assert recs[0].key == b"k000.%011d" % 0 and len(recs[0].value) == 48
    assert batch.to_kafka_wire()[ref.CRC_AT:] == t.tail


def test_split_batches_drops_a_truncated_tail():
    t = ref.make_templates(1, 2, 2, 64)
    blob = t[0].wire + t[1].wire
    assert [b for _o, b in ref.split_batches(blob)] == [t[0].wire, t[1].wire]
    assert len(ref.split_batches(blob[:-1])) == 1
    flipped = bytearray(t[0].wire)
    flipped[-1] ^= 1
    assert not ref.batch_holds(bytes(flipped))


def test_percentile_is_nearest_rank_over_all_values():
    assert percentile(list(range(1, 101)), 0.95) == 95
    assert percentile([5.0], 0.95) == 5.0
    assert percentile(list(range(1, 21)), 0.95) == 19


def _row(base, t_due, t_ack, err, t_fetch, got, tpl=0):
    # topic, partition, template, base, t_due, t_ack, error, t_sent,
    # tries, in_request, t_fetch, fetched_template
    return ["t", 0, tpl, base, t_due, t_ack, err, t_due, 1, 1, t_fetch, got]


def test_reduce_counts_failures_as_missing_the_tail():
    rows = [_row(64 * i, 10.0 + i, 10.5 + i, None, 10.7 + i, 0) for i in range(8)]
    rows.append(_row(-1, 12.0, 12.1, "KafkaClientError(7)", 0.0, -2))
    rows.append(_row(640, 13.0, 13.2, None, 0.0, -2, tpl=1))
    rec = {"t0": 10.0, "seconds": 10.0, "rows": rows, "payload_bytes": 1000,
           "fetch_error_count": 0}
    got = reduce_records(rec, drain_s=60)
    assert got["attempted"] == 10 and got["failed"] == 2 and got["acked"] == 9
    assert got["metrics"]["produce_mb_s"] == 9 * 1000 / 1e6 / 10.0
    assert got["metrics"]["produce_p50_ms"] == pytest.approx(500.0)
    assert got["metrics"]["e2e_p50_ms"] == pytest.approx(700.0)
    assert got["tails"]["produce_p95_ms"] == 60000.0
    assert got["tails"]["e2e_p95_ms"] == 60000.0


def test_latency_counts_from_the_due_time_not_the_send():
    rows = [_row(i, 10.0 + i, 10.25 + i, None, 10.5 + i, 0) for i in range(4)]
    for r in rows:
        r[7] = r[4] + 0.2  # sent late: the wait is the system's to answer for
    rec = {"t0": 10.0, "seconds": 4.0, "rows": rows, "payload_bytes": 1,
           "fetch_error_count": 0}
    got = reduce_records(rec, drain_s=60)["metrics"]
    assert got["produce_p50_ms"] == pytest.approx(250.0)
    assert got["e2e_p50_ms"] == pytest.approx(500.0)


def test_open_loop_due_times_are_even_and_staggered():
    import numpy as np

    from benchmark.generators.open_loop import due_times, steps_of

    steps = steps_of({"batches_per_s": 8}, 2.0)
    assert steps == [(2.0, 8.0)]
    all_due = np.sort(np.concatenate([due_times(steps, 4, i) for i in range(4)]))
    assert len(all_due) == 16
    assert np.allclose(np.diff(all_due), 0.125)
    stairs = steps_of({"schedule": [[1, 4], [1, 8]]}, 99.0)
    assert len(due_times(stairs, 1, 0)) == 12
