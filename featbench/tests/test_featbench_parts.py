"""CPU tests of the benchmark's yardstick: byte counts, the trace reducer,
the data files the harness finds by name, and its refusals."""

import gzip
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

FB = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(FB)
sys.path.insert(0, FB)

import harness  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import xplane  # noqa: E402

BENCH = harness.benchmark()


# -- byte counts --------------------------------------------------------------


def test_fused_ingest_bytes_by_hand():
    # 3 rows of 2 lanes: each row reads key, ts and 2 lanes (16 B) and
    # writes ts and 2 lanes into the ring (12 B); 2 (key, bucket) pairs
    # each read and write 5 stats and a bitmap per lane and the bucket id
    per_row = 16 + 12
    per_seg = 2 * (2 * 5 * 4 + 2 * 4 + 4)
    assert roofline.fused_ingest_bytes(3, 2, 2) == 3 * per_row + 2 * per_seg
    assert roofline.fused_ingest_bytes(0, 0, 2) == 0


def test_ingest_bytes_do_not_follow_block_shapes():
    # eight rows on eight keys of ONE 8-key state block, and on eight keys
    # of eight blocks: the kernel streams 1 block or 8, the work is equal
    one_block = np.arange(8)
    eight_blocks = np.arange(8) * 8
    for keys in (one_block, eight_blocks):
        segs = len(np.unique(keys.astype(np.int64) << 32))
        assert roofline.fused_ingest_bytes(8, segs, 2) == \
            roofline.fused_ingest_bytes(8, 8, 2)


def test_route_rank_bytes_by_hand():
    assert roofline.route_rank_bytes(512, 4) == 512 * 8 + 16


def test_roofline_share_is_silent_without_kernel_time():
    assert roofline.roofline_share(1e6, 0.0, 819e9) is None
    assert roofline.roofline_share(819e9, 2.0, 819e9) == pytest.approx(50.0)


# -- the trace reducer -----------------------------------------------------------


def _ev(name, t, d, stats=()):
    return NS(name=name, start_ns=t, duration_ns=d, stats=list(stats))


def _profile():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion.1", 100, 50),
                                   _ev("custom-call.3", 120, 60,
                                       [("kernel", "_fused_ingest_kernel"),
                                        ("flops", 0)]),
                                   _ev("fusion.2", 400, 100)]),
        NS(name="XLA Modules", events=[_ev("jit_f(12)", 100, 80),
                                       _ev("jit_f(12)", 400, 100)]),
    ])
    other = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[_ev("fusion.9", 0, 1000)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[_ev("featbench.pump", 0, 300),
                                  _ev("featbench.deliver_writes", 300, 700),
                                  _ev("featbench.ingest", 310, 80)])])
    return NS(planes=[dev, other, host])


def test_reducer_on_a_hand_made_trace():
    r = xplane.reduce(_profile(), [0])
    # busy: [100, 180) and [400, 500) inside the window [0, 1000)
    assert r["busy_s"] == pytest.approx(180e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    # a kernel is found by its name or by its string stats
    assert xplane.seconds_matching(r["ops"], "fused_ingest") == (
        pytest.approx(60e-9), 1)
    assert r["modules"]["jit_f"][:2] == (pytest.approx(180e-9), 2)
    gaps = r["breakdown"]["idle_gaps"]
    # the longest gap [500, 1000) falls in deliver_writes; [180, 400) has
    # its middle (290) in the pump; [0, 100) also in the pump
    assert gaps[0] == ["featbench.deliver_writes", pytest.approx(500e-9)]
    assert gaps[1] == ["featbench.pump", pytest.approx(220e-9)]
    assert r["breakdown"]["device_ops"][0] == ["fusion.2",
                                               pytest.approx(100e-9)]


def test_reducer_averages_busy_over_the_chips_used():
    r = xplane.reduce(_profile(), [0, 1])
    assert r["busy_s"] == pytest.approx((180e-9 + 1000e-9) / 2)


RECORDED = os.path.join(FB, "tests", "data")


def test_reducer_on_a_recorded_chip_trace():
    """A 0.4-s traced window of fraud_cards.read95 on a TPU v5e, against
    the numbers of the profiler's own JSON export of the same trace."""
    from jax.profiler import ProfileData

    with open(os.path.join(RECORDED, "expected.json")) as f:
        want = json.load(f)
    with gzip.open(os.path.join(RECORDED, "read95_v5e.xplane.pb.gz")) as f:
        r = xplane.reduce(ProfileData.from_serialized_xspace(f.read()), [0])
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-4)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    for k, (sec, n) in want["modules"].items():
        assert r["modules"][k][:2] == (pytest.approx(sec, rel=1e-4), n)
    sec, n = xplane.seconds_matching(r["ops"], want["kernel"])
    assert n == want["kernel_runs"]
    assert sec == pytest.approx(want["kernel_s"], rel=1e-4)


def _ingest_ctx(trace):
    """A traced run's context for the fused ingest reader: one 64-row
    ingest batch of the primary table over 40 (key, bucket) pairs."""
    return {"trace": trace, "lanes": {"transactions": 2},
            "win": NS(end=1.0, ingests=[("transactions", 0.1, 0.2, 64, 40,
                                         40)]),
            "peaks": harness.load_peaks("TPU v5 lite")}


def test_fused_ingest_roofline_on_the_recorded_chip_trace():
    """On the recorded one-chip trace the reader times the kernel the
    profiler's own export counts (``kernel_s``, ``kernel_runs``)."""
    from jax.profiler import ProfileData

    with open(os.path.join(RECORDED, "expected.json")) as f:
        want = json.load(f)
    with gzip.open(os.path.join(RECORDED, "read95_v5e.xplane.pb.gz")) as f:
        r = xplane.reduce(ProfileData.from_serialized_xspace(f.read()), [0])
    reader = harness.load_module(
        os.path.join(FB, "metrics", "fused_ingest_roofline.py"), "m_fir")
    _, n = xplane.seconds_matching(r["module_ops"][reader.PROGRAM],
                                   reader.KERNEL)
    assert n == want["kernel_runs"]
    need = roofline.fused_ingest_bytes(64, 40, 2)
    assert reader.read(_ingest_ctx(r)) == pytest.approx(
        100 * need / 819e9 / want["kernel_s"], rel=1e-4)


def test_fused_ingest_roofline_finds_the_four_chip_kernel():
    """On four chips XLA names the kernel's op after the sharded store's
    vmap (``%vmap__.1``): the reader finds it as the Mosaic call inside
    ``jit__ingest_pure`` on each chip, and not the route kernel's Mosaic
    call inside the route-and-query program."""
    mosaic = 'custom_call_target="tpu_custom_call"'

    def chip(i):
        return NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=[
                _ev("jit__ingest_pure(7)", 100, 100),
                _ev("jit__unknown(9)", 300, 100)]),
            NS(name="XLA Ops", events=[
                _ev("%fusion.2 = s32[1,64]{1,0} fusion(...)", 110, 10),
                _ev(f"%vmap__.1 = (s32[1,8,256]{{2,1,0}}, ...) "
                    f"custom-call(...), {mosaic}", 130, 40 + i),
                _ev(f"%route_rank_pallas.1 = s32[512]{{0}} "
                    f"custom-call(...), {mosaic}", 310, 50)])])

    r = xplane.reduce(NS(planes=[chip(i) for i in range(4)]), range(4))
    reader = harness.load_module(
        os.path.join(FB, "metrics", "fused_ingest_roofline.py"), "m_fir4")
    kernel_s = (40 + 41 + 42 + 43) * 1e-9
    need = roofline.fused_ingest_bytes(64, 40, 2)
    assert reader.read(_ingest_ctx(r)) == pytest.approx(
        100 * need / 819e9 / kernel_s)


# -- the reference ---------------------------------------------------------------


def test_reference_range_window_keeps_edge_rows_of_the_ring_only():
    # one key, 6 rows at ts 0..5 (bucket 2 s), ring of 2, window of 4 s at
    # ts 5: rows ts 2..5 are in range; bucket of ts 2,3 is full (middle,
    # from pre-aggregates); ts 4,5 is the request's bucket (ring: 4, 5)
    t = reference.Table(np.zeros(6), np.arange(6),
                        {"v": np.arange(6, dtype=np.float32)}, capacity=2)
    req = {"key": np.array([0]), "ts": np.array([5])}
    st = t.range_window("v", req, np.array([6]), 4, 2)
    assert st["count"][0] == 4 and st["sum"][0] == 2 + 3 + 4 + 5
    assert st["max"][0] == 5
    # capacity 1: the ring holds ts 5 only, so ts 4 of the edge bucket goes
    t1 = reference.Table(np.zeros(6), np.arange(6),
                         {"v": np.arange(6, dtype=np.float32)}, capacity=1)
    st = t1.range_window("v", req, np.array([6]), 4, 2)
    assert st["count"][0] == 3 and st["sum"][0] == 2 + 3 + 5
    # rows ingested at or after the cutoff are not seen
    st = t.range_window("v", req, np.array([3]), 4, 2)
    assert st["count"][0] == 1 and st["sum"][0] == 2


def test_reference_rows_window_and_last_join():
    t = reference.Table(np.zeros(5), np.array([1, 2, 2, 3, 9]),
                        {"v": np.arange(5, dtype=np.float32)}, capacity=4)
    req = {"key": np.array([0]), "ts": np.array([3])}
    st = t.rows_window("v", req, np.array([5]), 3)
    # ring holds rows 1..4; eligible (ts <= 3): rows 1, 2, 3; newest 2
    assert st["count"][0] == 2 and st["sum"][0] == 2 + 3
    # newest row with ts <= 2 among the ring rows: row 2 (latest of ts 2)
    assert t.last_join("v", np.array([0]), np.array([2]), np.array([5]),
                       -1.0)[0] == 2
    assert t.last_join("v", np.array([1]), np.array([2]), np.array([5]),
                       -1.0)[0] == -1


def test_to_bf16_rounds_to_nearest_even():
    x = np.array([1.0, 1.00390625, 1.01171875, 3.14159265], np.float32)
    got = reference.to_bf16(x)
    assert got.tolist() == [1.0, 1.0, 1.015625, 3.140625]


# -- the data files and the refusals ----------------------------------------------


def test_every_cell_finds_its_files_by_name():
    for w in BENCH["workloads"]:
        cfg = harness.traffic.load("configs", w["config"])
        mix = harness.traffic.load("traffic", w["traffic"])
        assert cfg["name"] == w["config"] and mix["name"] == w["traffic"]
        assert os.path.isfile(os.path.join(FB, "configs",
                                           f"{w['config']}_ref.py"))
        assert set(cfg["limits"]) == {"exact_mismatch", "value_err"}
    for c in BENCH["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert set(c["reduced"]) <= set(cfg["reduced"])
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(FB, "metrics", f"{m['name']}.py"))


def test_set_up_warms_the_micro_batches_a_mix_can_deliver():
    # the connector hands over at most ingest_max_rows rows at a time, so
    # the history ends in one batch of every size up to that cap
    cfg = {"ingest_max_rows": 8, "tables": {"tx": {
        "key": "k", "num_keys": 16, "columns": {"v": ["uniform", 0, 1]}}},
        "history": {"rows": 100, "span_s": 100, "batch_rows": 10,
                    "tables": {"tx": 1.0}}}
    hist = harness.traffic.history(
        np.random.default_rng(1), cfg, {"dist": "uniform"})
    sizes = [len(c["ts"]) for _, c in hist]
    assert sizes[-8:] == list(range(1, 9)) and sum(sizes) <= 100


def test_state_fill_counts_held_ring_rows_and_written_buckets():
    state = NS(ring=NS(cursor=np.array([0, 3, 300, 8])),
               bagg=NS(bucket=np.array([[-1, 4], [-1, -1], [7, 8], [-1, 2]])))
    got = harness.state_fill(state, capacity=8)
    assert got == {"ring_slots": (0 + 3 + 8 + 8) / 32, "bucket_cells": 0.5}


def test_a_device_kind_missing_from_the_peaks_is_an_error():
    with pytest.raises(harness.Fail):
        harness.load_peaks("cpu")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_the_command_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(FB, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# -- the per-layer readers -----------------------------------------------------
#
# Each per-layer metric has a case of its own, ``reader_cases/<metric>.py``:
# ``CONTEXT``, the parts of a traced run's context that its reader reads
# (``telemetry``, ``trace``, ``win``, ``lanes``), ``WANT``, the value worked
# out by hand from them, and optionally ``CONFIG``, the configuration it is
# read in, and ``VARIANTS``, pairs of (parts of ``CONTEXT`` replaced, the
# value then read; None where the reader finds nothing).  A reader and its
# case come in together, and the tests below find both by the metric's name.

CASES = os.path.join(FB, "tests", "reader_cases")


def _case(metric):
    return harness.load_module(os.path.join(CASES, f"{metric}.py"),
                               "case_" + metric.replace(".", "_"))


def _reader(metric):
    return harness.load_module(os.path.join(FB, "metrics", f"{metric}.py"),
                               "m_" + metric.replace(".", "_"))


def _merge(a, b):
    """Dicts merged key by key, lists joined (an entry that two cases share
    is taken once); any other value has to be the same on both sides."""
    if isinstance(a, dict) and isinstance(b, dict):
        out = dict(a)
        for k, v in b.items():
            out[k] = _merge(out[k], v) if k in out else v
        return out
    if isinstance(a, list) and isinstance(b, list):
        return a + [x for x in b if x not in a]
    assert a == b, (a, b)
    return a


def _ctx(cell, parts):
    """A traced run's context for ``cell`` holding ``parts`` of cases."""
    data = _merge({"telemetry": {},
                   "trace": {"ops": {}, "modules": {}, "module_ops": {}},
                   "win": {"pumps": [], "ingests": [], "read_due": [],
                           "read_pump": []},
                   "lanes": {}}, parts)
    win = {k: np.asarray(v) if k.startswith("read_") else v
           for k, v in data["win"].items()}
    return {"cfg": harness.traffic.load("configs", cell["config"]),
            "cell": cell, "win": NS(**win), "telemetry": data["telemetry"],
            "trace": data["trace"], "lanes": data["lanes"],
            "peaks": harness.load_peaks("TPU v5 lite")}


def _listed(metric, cell):
    return cell in metric.get("workloads", [cell])


def _every_case(bench):
    parts = {}
    for m in bench["per_layer"]:
        parts = _merge(parts, _case(m["name"]).CONTEXT)
    return parts


def _reads_the_listed_metrics(name):
    bench = harness.benchmark()
    cell = harness.find_cell(bench, name)
    got = harness.per_layer(cell, _ctx(cell, _every_case(bench)))
    want = [m["name"] for m in bench["per_layer"]
            if _listed(m, name)]
    assert sorted(got) == sorted(want)
    for v in got.values():
        assert 0 <= v["value"] and v["unit"]
    return got


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_per_layer_metric_reads_a_number(name):
    """Every metric listed for the cell, and no other, reads a number from
    one context that holds every reader's case."""
    _reads_the_listed_metrics(name)


# every metric of BENCHMARK.json, and every reader kept with a case file
# for a cell that is not in the benchmark (PERF.md, Open questions)
HAND = sorted({m["name"] for m in BENCH["per_layer"]}
              | {f[:-3] for f in os.listdir(CASES) if f.endswith(".py")})


@pytest.mark.parametrize("metric", HAND)
def test_every_per_layer_metric_reads_its_hand_value(metric):
    case = _case(metric)
    listed = [w["config"] for w in BENCH["workloads"]
              for m in BENCH["per_layer"]
              if m["name"] == metric and _listed(m, w["name"])]
    cell = {"name": "hand", "config": getattr(case, "CONFIG", None)
            or listed[0]}
    read = _reader(metric).read
    for parts, want in [({}, case.WANT)] + getattr(case, "VARIANTS", []):
        got = read(_ctx(cell, {**case.CONTEXT, **parts}))
        if want is None:
            assert got is None, parts
        else:
            assert got == pytest.approx(want), parts


def test_a_cell_added_to_the_benchmark_alone_reads_its_metrics(monkeypatch):
    """A cell that enters BENCHMARK.json, and lists no per-layer metric of
    its own, reads the metrics listed for every cell and no other."""
    extra = dict(BENCH["workloads"][0], name="added.cell")
    bench = dict(BENCH, workloads=BENCH["workloads"] + [extra])
    monkeypatch.setattr(harness, "benchmark", lambda: bench)
    for w in bench["workloads"]:
        _reads_the_listed_metrics(w["name"])
    assert sorted(_reads_the_listed_metrics(extra["name"])) == sorted(
        m["name"] for m in BENCH["per_layer"] if "workloads" not in m)


def test_the_four_chip_readers_read_a_number():
    """The two readers of the sharded store's fused route and query read
    their hand values in the four-shard configuration; where BENCHMARK.json
    lists them, it lists them for four-chip cells alone."""
    four = {w["name"] for w in BENCH["workloads"] if w["chips"] == 4}
    for name in ("query.route_device_ms", "route_rank_roofline"):
        case = _case(name)
        cfg = harness.traffic.load("configs", case.CONFIG)
        assert cfg["store"]["num_shards"] == 4
        cell = {"name": "hand", "config": case.CONFIG}
        assert _reader(name).read(_ctx(cell, case.CONTEXT)) == \
            pytest.approx(case.WANT)
        for m in BENCH["per_layer"]:
            if m["name"] == name:
                assert set(m["workloads"]) <= four
