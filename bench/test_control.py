"""The correctness check must fail what is wrong: the bfloat16 control, and
the program with its timed path broken underneath. CPU, small sizes; the
harness's look for a chip is skipped by handing it the CPU device."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, fleet, reference, streams
from bench import run as brun
from bench.conftest import ROOT, cloud_config

CELLS = ["metro-edge.online", "metro-edge.replay"]


def _limits(workload):
    import json
    return json.loads((ROOT / "bench" / "limits" / f"{workload}.json")
                      .read_text())


@pytest.mark.parametrize("workload", CELLS)
def test_bf16_control_fails_the_check(workload):
    r = brun.resolve(workload)
    cfg = fleet.load(r["config_file"])
    import json
    traffic = json.loads(r["traffic_file"].read_text())
    cols = streams.generate(streams.scenario(traffic["scenario"]),
                            seed=2**31 + 99, n=8192,
                            num_models=len(cfg["models"]),
                            num_cells=cfg["num_cells"])
    table = fleet.table(cfg)
    lim = _limits(workload)
    got = reference.check(table, cols, *control.route(table, cols))
    assert (got["gap_max"] > lim["gap_max"]["limit"]
            or got["lat_err_max"] > lim["lat_err_max"]["limit"]), got
    # the same stream scored at float32 passes: the control fails by its
    # precision alone
    ok = reference.check(table, cols, *control.route(table, cols, "float32"))
    assert ok["gap_max"] <= lim["gap_max"]["limit"]


def _state_unchanged(route):
    @functools.wraps(route)
    def broken(params, state, reqs, *a, **kw):
        _, out = route(params, state, reqs, *a, **kw)
        return state, out
    return broken


def _half_left_out(route):
    @functools.wraps(route)
    def broken(params, state, reqs, *a, **kw):
        b = reqs.model.shape[0]
        half = jax.tree.map(lambda x: x[: b // 2], reqs)
        state, out = route(params, state, half, *a, **kw)
        pad = b - b // 2
        return state, out._replace(
            choice=jnp.concatenate([out.choice, jnp.full(pad, -1, jnp.int32)]),
            latency=jnp.concatenate([out.latency,
                                     jnp.full(pad, jnp.inf, out.latency.dtype)]),
            hit=jnp.concatenate([out.hit, jnp.zeros(pad, bool)]),
            cause=jnp.concatenate([out.cause, jnp.ones(pad, jnp.int32)]))
    return broken


def _answer_altered(route):
    """The first request of every window is sent to the next server of
    its own cell, where the router produced it."""
    @functools.wraps(route)
    def broken(params, state, reqs, *a, **kw):
        state, out = route(params, state, reqs, *a, **kw)
        per = 16 if params.flops_per_s.shape[0] > 200 else 3
        c = out.choice[0]
        base = (c // per) * per
        alt = jnp.where(c < params.flops_per_s.shape[0] - 1,
                        base + (c - base + 1) % per, 0)
        return state, out._replace(choice=out.choice.at[0].set(alt))
    return broken


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _answer_altered])
def test_broken_timed_path_is_not_correct(workload, fault, tree, cpu,
                                          monkeypatch):
    from repro.core import batch_router

    good = brun.run_cell(workload, seed=2**32 + 17, seconds=0.3, trace=False,
                         devices=cpu, bench_json=tree)
    assert good["correct"], good["checks"]
    monkeypatch.setattr(batch_router, "route_batch",
                        fault(batch_router.route_batch))
    bad = brun.run_cell(workload, seed=2**32 + 17, seconds=0.3, trace=False,
                        devices=cpu, bench_json=tree)
    assert not bad["correct"], bad["checks"]


def test_reference_follows_a_cloud_column():
    """On a small fleet with a cloud column, over a stream short enough
    that float32 rounding stays at rounding, the program's plain window
    and the reference agree on every decision, cloud commits included."""
    from bench.drivers import _batch
    from repro.core import batch_router

    cfg = cloud_config(num_cells=4)
    table = fleet.table(cfg)
    params, state = fleet.program_fleet(cfg)
    spec = streams.scenario({"rate": 30000.0, "zipf_s": 1.5})
    cols = streams.generate(spec, seed=3, n=4096, num_models=4, num_cells=4)
    _, out = batch_router.route_batch(params, state, _batch(cols, slice(None)),
                                      chunk=256, backend="xla")
    assert (np.asarray(out.choice) == table["cloud"]).any()
    got = reference.check(table, cols, np.asarray(out.choice),
                          np.asarray(out.latency), np.asarray(out.hit))
    assert got["gap_max"] == 0.0
    assert got["lat_err_max"] < 1e-5
