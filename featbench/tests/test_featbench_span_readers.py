"""CPU tests of the per-layer readers of the program's host spans and of
its queue-wait histogram, by hand values."""

import os

import pytest

import test_featbench_parts as parts
from program_spans import add_program_spans

harness = parts.harness


def _read(name, ctx):
    mod = harness.load_module(os.path.join(parts.FB, "metrics", f"{name}.py"),
                              "m_" + name.replace(".", "_"))
    return mod.read(ctx)


def test_the_program_span_readers_by_hand():
    cell = harness.find_cell(parts.BENCH, "fraud_cards.read95")
    ctx = add_program_spans(parts._ctx(cell))

    # 200 ms of pumps less 60 ms of query.compute and 80 ms of
    # route.device, over 3 pumps
    assert _read("pump.host_ms", ctx) == pytest.approx(20.0)
    # 3 + 6 + 3 + 2 + 1 ms of query-side host spans over 3 + 2 batches
    assert _read("query.host_ms", ctx) == pytest.approx(3.0)
    assert _read("ingest.prepare_ms", ctx) == pytest.approx(2.0)
    # 24 waits: rank 22.8 falls in (45, 50] ms, which holds waits 16..23
    assert _read("sched.program_wait_p95_ms", ctx) == pytest.approx(
        45 + 5 * (22.8 - 15) / 8)
    # a rank in the overflow bucket ends at the largest wait
    buckets = ctx["telemetry"]["queue_wait_seconds"]["series"][0]["buckets"]
    buckets[:] = [[0.04, 0.0], [0.045, 0.0], [0.05, 0.0], ["+Inf", 2.0]]
    assert _read("sched.program_wait_p95_ms", ctx) == pytest.approx(
        50 + 30 * 0.95)
    # a bucket wider than a tenth of a decade (coarser bounds) reads none
    buckets[:] = [[0.01, 0.0], [0.03, 0.0], [0.1, 2.0], ["+Inf", 0.0]]
    assert _read("sched.program_wait_p95_ms", ctx) is None


def test_the_program_span_readers_read_none_without_the_spans():
    # a program without these spans and counts (an older tree)
    cell = harness.find_cell(parts.BENCH, "fraud_cards.read95")
    ctx = parts._ctx(cell)
    for name in ("pump.host_ms", "query.host_ms", "ingest.prepare_ms",
                 "sched.program_wait_p95_ms"):
        assert _read(name, ctx) is None, name
