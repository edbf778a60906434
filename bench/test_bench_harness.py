"""Tests of the benchmark harness: discovery, arithmetic, trace reduction
and roofline count. CPU only; nothing here touches a TPU."""
import json
import math
import pathlib

import numpy as np
import pytest

from bench import drivers, roofline, trace_reduce
from bench import run as brun
from bench.conftest import cloud_config

TESTDATA = pathlib.Path(__file__).parent / "testdata"


def test_new_cell_from_new_files_only(tree, cpu):
    """A configuration, a traffic mix and a per-layer metric added as new
    files and entries are found and run; no harness file changes."""
    bench = tree.parent / "bench"
    cfg = cloud_config(num_cells=4)
    (bench / "configs" / "metro-4x16-cloud.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "drift-replay-150k.json")
                         .read_text())
    traffic["scenario"]["rate"] = 15000.0
    (bench / "traffic" / "drift-replay-15k.json").write_text(
        json.dumps(traffic))
    (bench / "limits" / "metro-cloud.small.json").write_text(
        (bench / "limits" / "metro-edge.replay.json").read_text())
    (bench / "metrics" / "routed_calls.py").write_text(
        "def read(ctx):\n    return float(ctx['run'].describe['calls'])\n")
    spec = json.loads(tree.read_text())
    spec["configs"].append({"name": cfg["name"], "source": "test",
                            "file": "bench/configs/metro-4x16-cloud.json",
                            "reduced": ["num_cells"], "why": "test"})
    spec["workloads"].append({"name": "metro-cloud.small",
                              "config": cfg["name"],
                              "traffic": "drift-replay-15k", "chips": 1,
                              "why": "test"})
    rps = next(m for m in spec["end_to_end"] if m["name"] == "routed_rps")
    rps["workloads"].append("metro-cloud.small")
    spec["per_layer"].append({"name": "routed_calls", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "episode", "moves": "routed_rps",
                              "workloads": ["metro-cloud.small"]})
    tree.write_text(json.dumps(spec))

    r = brun.resolve("metro-cloud.small", tree)
    assert r["config_file"] == bench / "configs" / "metro-4x16-cloud.json"
    assert r["traffic_file"] == bench / "traffic" / "drift-replay-15k.json"
    assert [m["name"] for m in r["end_to_end"]] == ["routed_rps", "setup_s"]
    assert [m["name"] for m in r["per_layer"]] == ["routed_calls"]

    res = brun.run_cell("metro-cloud.small", seed=2**33 + 5, seconds=0.3,
                        trace=False, devices=cpu, bench_json=tree)
    assert res["correct"], res["checks"]
    assert res["traffic"]["cloud_share"] > 0
    assert set(res["metrics"]) == {"routed_rps", "setup_s"}
    res = brun.run_cell("metro-cloud.small", seed=2**33 + 5, seconds=0.3,
                        trace=True, devices=cpu, bench_json=tree)
    assert res["metrics"]["routed_calls"]["value"] >= 1
    assert res["correct"], res["checks"]


def test_unknown_workload_is_refused(tree):
    with pytest.raises(SystemExit):
        brun.resolve("no.such.cell", tree)


def test_percentiles_cover_every_request_and_a_stall():
    # 20 windows of 100 requests decided 10 ms after due, but 120 requests
    # stalled for a second. Every request counts, so the stall is the p95; a median
    # of per-window medians would read 10 ms.
    due = np.arange(2000) * 1e-3
    done = due + 0.010
    done[1000:1120] = due[1000:1120] + 1.0
    lat = drivers.decision_latencies(due, done, seconds=10.0)
    assert lat.size == 2000
    assert drivers.percentile_ms(lat, 50) == pytest.approx(10.0)
    assert drivers.percentile_ms(lat, 95) == pytest.approx(1000.0)
    windows = np.median(lat.reshape(20, 100), axis=1)
    assert np.median(windows) * 1e3 == pytest.approx(10.0)


def test_undecided_requests_count_as_missing():
    due = np.arange(100) * 1e-3
    done = due + 0.002
    done[90:] = np.nan          # never decided
    lat = drivers.decision_latencies(due, done, seconds=1.0)
    assert np.isinf(lat).sum() == 10
    assert math.isinf(drivers.percentile_ms(lat, 95))
    # requests due after the window are not counted at all
    assert drivers.decision_latencies(due, done, seconds=0.05).size == 50


def test_lag_share_counts_each_window_once_a_request():
    # two windows of 100 requests, each decided 10 ms after due, but the
    # second dispatched 30 ms late: its requests carry 30 ms of driver lag
    due = np.arange(200) * 1e-3
    done = due + 0.010
    done[100:] += 0.030
    lags = np.array([0.0, 0.030])
    share = drivers.lag_share(lags, 100, due, done)
    assert share == pytest.approx(100 * 0.030 / (200 * 0.010 + 100 * 0.030))
    assert drivers.lag_share(np.zeros(0), 100, due, done) == 0.0


def test_rate_takes_all_work_over_all_time():
    # four calls of 1000 requests; one is slow. The rate is the total over
    # the total, not the median of per-call rates.
    ends = np.cumsum([1.0, 1.0, 4.0, 1.0])
    assert drivers.window_rate(4 * 1000, ends) == pytest.approx(4000 / 7.0)


def test_traffic_description_counts_the_replayed_suffix():
    hit = np.ones(1024, bool)
    hit[256 + 10] = False       # chunk 1: first miss at 10 -> 246 after
    hit[768 + 200] = False      # chunk 3: first miss at 200 -> 56 after
    hit[768 + 250] = False
    choice = np.zeros(1024, int)
    choice[:8] = 7
    d = drivers.describe(hit, choice, cloud=7, chunk=256)
    assert d["misses"] == 3
    assert d["cloud_share"] == pytest.approx(8 / 1024)
    assert d["after_first_miss_share"] == pytest.approx((246 + 56) / 1024)


def _union_by_sweep(events, t0, t1):
    points = sorted({t0, t1, *(min(max(x, t0), t1) for s, d, _ in events
                               for x in (s, s + d))})
    covered = 0.0
    for a, b in zip(points, points[1:]):
        mid = 0.5 * (a + b)
        if any(s <= mid < s + d for s, d, _ in events):
            covered += b - a
    return covered


def test_trace_reduction_on_recorded_trace():
    """Three 8192-request windows of the metro fleet recorded on a TPU v5e
    (ops cut to the loops, the kernels and the slices)."""
    trace = trace_reduce.from_json(TESTDATA / "tpu_trace_small.json")
    t0, t1 = trace_reduce.window(trace)
    dev = trace["devices"]["/device:TPU:0"]
    busy = trace_reduce.busy_s(trace)["/device:TPU:0"]
    assert busy == pytest.approx(_union_by_sweep(dev["ops"], t0, t1))
    routes = [d for s, d, n in dev["modules"] if n.startswith("jit__route")]
    assert len(routes) == 3
    # the loops cover nearly all of each program's execution
    assert 0.9 * sum(routes) < busy <= t1 - t0

    from bench.metrics import idle_share, route_score_roofline, \
        route_us_per_req

    class Run:
        traced = {"requests_per_module": 8192}
        chunk = 256

    ctx = {"trace": trace, "run": Run, "table": {"flops": np.zeros(1025)},
           "device_kind": "TPU v5 lite"}
    assert idle_share.read(ctx) == pytest.approx(
        100 * (1 - busy / (t1 - t0)))
    assert route_us_per_req.read(ctx) == pytest.approx(
        1e6 * sum(routes) / (3 * 8192))
    kern = [d for s, d, n in dev["ops"] if "tpu_custom_call" in n]
    assert len(kern) == 96      # 32 chunks a window
    least = 4 * (256 * 3 + 1025 * 3 + 256 * 1025) / 819e9
    assert route_score_roofline.read(ctx) == pytest.approx(
        100 * least * 96 / sum(kern))
    assert 0 < route_score_roofline.read(ctx) < 100
    gaps = trace_reduce.idle_gaps(trace)
    assert sum(s for _, s in gaps) == pytest.approx(t1 - t0 - busy)
    assert gaps[0][0] in {"bench.route", "bench.fetch", "inside the program"}
    assert trace_reduce.top_ops(trace)[0][0].startswith("%while")
    assert trace_reduce.short_name(
        "%c.1 = f32[256,1152]{1,0} custom-call(f32[8,256] %p), "
        "custom_call_target=\"tpu_custom_call\"") == \
        "%c.1 = f32[256,1152]{1,0} tpu_custom_call"


def test_readers_return_nothing_without_a_trace():
    from bench.metrics import idle_share, route_score_roofline

    assert idle_share.read({"trace": None}) is None
    empty = {"devices": {}, "host": [(0.0, 0.0, "bench.trace_open"),
                                     (1.0, 0.0, "bench.trace_close")]}
    assert idle_share.read({"trace": empty}) is None
    ctx = {"trace": empty, "run": None, "table": {"flops": np.zeros(3)},
           "device_kind": "TPU v5 lite"}

    class Run:
        chunk = 256
    ctx["run"] = Run
    assert route_score_roofline.read(ctx) is None


def test_roofline_count_by_hand():
    w = roofline.route_score_work(256, 1025)
    # request strip: 3 words x 256; server strip: 3 words x 1025;
    # panel: 256 x 1025 float32, written once
    assert w["bytes"] == 4 * 768 + 4 * 3075 + 4 * 262400 == 1064972
    assert w["flops"] == 3 * 262400
    t, bound = roofline.least_time_s(w, roofline.peaks("TPU v5 lite"))
    assert bound == "bytes"
    assert t == pytest.approx(1064972 / 819e9)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_metric_reader_found_by_name_or_its_stem(tree):
    """``idle_share.online`` has no file of its own and is read by
    ``idle_share.py``; a metric with no reader at all is an error."""
    metrics = tree.parent / "bench" / "metrics"
    assert brun.reader(metrics, "idle_share.online").__module__ == \
        "bench.metrics.idle_share"
    (metrics / "idle_share.online.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    assert brun.reader(metrics, "idle_share.online")({}) == 1.0
    with pytest.raises(FileNotFoundError):
        brun.reader(metrics, "no_such_metric.online")


def test_result_line_holds_no_infinity():
    # a missing decision reads infinite; JSON has no infinity
    res = brun.finite({"metrics": {"decide_p95_ms": {"value": math.inf}},
                       "checks": [math.nan, 1.5]})
    assert res == {"metrics": {"decide_p95_ms": {"value": None}},
                   "checks": [None, 1.5]}
    json.dumps(res, allow_nan=False)


def test_configuration_must_state_float32(tmp_path):
    from bench import fleet

    cfg = json.loads((brun.ROOT / "bench" / "configs" /
                      "metro-64x16-edge.json").read_text())
    cfg["precision"] = "bfloat16"
    path = tmp_path / "c.json"
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError):
        fleet.load(path)


def test_unknown_scenario_key_is_refused():
    from bench import streams

    with pytest.raises(ValueError):
        streams.scenario({"rate": 10.0, "zipf": 1.5})
    assert streams.scenario({"rate": 10.0})["zipf_s"] == 0.0
