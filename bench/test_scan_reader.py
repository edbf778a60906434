"""Tests of the commit-scan reader (``bench/metrics/scan_us_per_req.py``):
its count on a trace recorded on the chip and on one made by hand, and the
routing program it compiles to find each op's named scope. CPU only."""
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import fleet, streams, trace_reduce
from bench.conftest import cloud_config
from bench.metrics import route_score_roofline, route_us_per_req, \
    scan_us_per_req

TESTDATA = pathlib.Path(__file__).parent / "testdata"


class Run:
    traced = {"requests_per_module": 8192}
    chunk = 256


def _union_by_sweep(events, t0, t1):
    points = sorted({t0, t1, *(min(max(x, t0), t1) for s, d, _ in events
                               for x in (s, s + d))})
    return sum(b - a for a, b in zip(points, points[1:])
               if any(s <= 0.5 * (a + b) < s + d for s, d, _ in events))


def test_scan_time_on_recorded_trace(monkeypatch):
    """One ``simulate`` call of three 8192-request windows on the
    metro-edge fleet, recorded on a TPU v5e with the router's named scopes
    (ops cut to the loops, the kernels and the slices; the HLO cut to
    their instructions)."""
    trace = trace_reduce.from_json(TESTDATA / "tpu_trace_scoped.json")
    monkeypatch.setattr(scan_us_per_req, "route_hlo",
                        lambda ctx: trace["route_hlo"])
    t0, t1 = trace_reduce.window(trace)
    dev = trace["devices"]["/device:TPU:0"]
    routes = [(s, s + d) for s, d, n in dev["modules"]
              if n.startswith("jit__route_batch") and t0 <= s < t1]
    assert len(routes) == 3
    # the speculative scan and the replay loop, inside the programs
    scan = [e for e in dev["ops"] if e[2] in ("%while.100", "%while.104")
            and any(a <= e[0] < b for a, b in routes)]
    ctx = {"trace": trace, "run": Run, "table": {"flops": np.zeros(1024)},
           "device_kind": "TPU v5 lite"}
    got = scan_us_per_req.read(ctx)
    assert got == pytest.approx(
        1e6 * _union_by_sweep(scan, t0, t1) / (3 * 8192))
    # the scan is nearly all of the route program, and never more
    route = route_us_per_req.read(ctx)
    assert 0.9 * route < got <= route
    # the kernel is found by its name
    kern = [n for _, _, n in dev["ops"] if "tpu_custom_call" in n]
    assert len(kern) == 96 and all("route_score" in n for n in kern)
    assert 0 < route_score_roofline.read(ctx) < 100


def test_scan_time_by_hand(monkeypatch):
    """Scan ops count once where they nest, only inside routing programs
    that start in the window, and whole where such a program runs past
    the window's end."""
    monkeypatch.setattr(scan_us_per_req, "route_hlo", lambda ctx: (
        '  %while.100 = (f32[8]) while(%t), metadata={op_name='
        '"jit(_route_batch)/while/body/route.spec_scan/while"}\n'
        '  %fusion.1 = f32[8]{0} fusion(%p), metadata={op_name='
        '"jit(_route_batch)/while/body/route.spec_scan/while/body/add"}\n'
        '  %while.104 = (f32[8]) while(%t), metadata={op_name='
        '"jit(_route_batch)/while/body/route.replay/while"}\n'
        '  %while.7 = (f32[8]) while(%t), metadata={op_name='
        '"jit(_route_batch)/route.commit_scan/while"}\n'
        '  %route_score.8 = f32[256,1024]{1,0} custom-call(%a), '
        'frontend_attributes={kernel_metadata={}}, metadata={op_name='
        '"jit(_route_batch)/while/body/route.score/route_score/pallas_call"}'
        '\n  %while.99 = (f32[8]) while(%t), metadata={op_name='
        '"jit(_route_batch)/while"}\n'))
    ops = [(-0.05, 0.09, "%while.100"),    # in a program that starts before
           (0.10, 0.20, "%while.99"),
           (0.10, 0.01, "%route_score.8 = f32[256,1024]{1,0} tpu_custom_call"),
           (0.12, 0.16, "%while.100"),
           (0.13, 0.01, "%fusion.1"),      # nested in the loop above
           (0.28, 0.01, "%while.104"),
           (0.40, 0.05, "%while.100"),     # in no routing program
           (0.52, 0.08, "%while.7"),
           (0.95, 0.15, "%while.100")]     # past the window's end
    modules = [(-0.1, 0.15, "jit__route_batch(1)"),
               (0.1, 0.2, "jit__route_batch(1)"),
               (0.4, 0.05, "jit_dynamic_slice(2)"),
               (0.5, 0.2, "jit__route_batch(1)"),
               (0.9, 0.3, "jit__route_batch(1)")]
    marks = [(0.0, 0.0, "bench.trace_open"), (1.0, 0.0, "bench.trace_close")]
    trace = {"host": marks,
             "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}

    class Small:
        traced = {"requests_per_module": 1000}

    ctx = {"trace": trace, "run": Small}
    assert scan_us_per_req.read(ctx) == pytest.approx(
        1e6 * (0.17 + 0.08 + 0.15) / 3000)
    assert route_us_per_req.read(ctx) == pytest.approx(1e6 * 0.7 / 3000)


def test_scan_reads_nothing_without_scopes(monkeypatch):
    def no_compile(ctx):
        raise AssertionError("nothing to read: no routing program ran")

    monkeypatch.setattr(scan_us_per_req, "route_hlo", no_compile)
    empty = {"devices": {}, "host": [(0.0, 0.0, "bench.trace_open"),
                                     (1.0, 0.0, "bench.trace_close")]}
    assert scan_us_per_req.read({"trace": None}) is None
    assert scan_us_per_req.read({"trace": empty, "run": Run}) is None
    # a program without named scopes: routing programs, no scoped op
    small = trace_reduce.from_json(TESTDATA / "tpu_trace_small.json")
    monkeypatch.setattr(scan_us_per_req, "route_hlo", lambda ctx: (
        '  %while.109 = (f32[8]) while(%t), metadata={op_name='
        '"jit(_route_batch)/while/body/while"}\n'))
    assert scan_us_per_req.read({"trace": small, "run": Run}) is None


def test_route_hlo_compiles_the_window_program_with_its_scopes():
    """The reader's fleet has the shapes of the driver's, and the program
    it compiles for a window carries the scan scopes."""
    cfg = cloud_config(num_cells=2)
    table = fleet.table(cfg)
    shapes = jax.tree.map(lambda x: (np.shape(x), np.asarray(x).dtype),
                          scan_us_per_req._fleet(table))
    assert shapes == jax.tree.map(lambda x: (np.shape(x),
                                             np.asarray(x).dtype),
                                  fleet.program_fleet(cfg))
    traffic = json.loads((pathlib.Path(__file__).parent / "traffic" /
                          "steady-online.json").read_text())
    cols = streams.generate(streams.scenario(traffic["scenario"]), seed=3,
                            n=512, num_models=len(cfg["models"]),
                            num_cells=cfg["num_cells"])

    class Small:
        traced = {"requests_per_module": 512}

    Small.cols = cols
    ctx = {"run": Small, "table": table,
           "traffic": {**traffic, "chunk": 128, "backend": "xla"}}
    names = scan_us_per_req.scoped_names(scan_us_per_req.route_hlo(ctx))
    assert any(n.startswith("%while") for n in names)
