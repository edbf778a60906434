"""Scenario workload subsystem: determinism, stream invariants, drift
semantics, the windowed simulator's equivalence to the unwindowed
``route_batch`` oracle, and the per-window stats variant."""
import hashlib
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batch_router as br
from repro.core.catalog import build_catalog
from repro.launch.serve import make_multicell_fleet, serve
from repro.workloads import (FaultSpec, ScenarioSpec, SimResult,
                             compile_scenario, generators, get_scenario,
                             list_scenarios, simulate)
from repro.workloads.simulate import request_energy_j

EDGE_ARCHS = ["smollm_135m", "starcoder2_3b", "mamba2_2p7b",
              "musicgen_medium"]


def _stream_digest(name, seed, n, num_models, num_cells):
    spec = get_scenario(name, num_requests=n)
    reqs = compile_scenario(spec, seed=seed, num_models=num_models,
                            num_cells=num_cells)
    h = hashlib.sha256()
    for field in br.RequestBatch._fields:
        arr = getattr(reqs, field)
        if arr is not None:
            h.update(np.ascontiguousarray(np.asarray(arr)).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# registry + stream invariants
# ---------------------------------------------------------------------------
def test_registry_has_the_named_scenarios():
    names = set(list_scenarios())
    assert {"steady", "bursty", "diurnal", "flash-crowd",
            "popularity-drift", "hotspot-cell"} <= names


@pytest.mark.parametrize("name", list_scenarios())
def test_scenarios_compile_with_sound_streams(name):
    """Every registered scenario lowers to a well-formed RequestBatch:
    right dtypes/shapes, in-range columns, and NON-DECREASING arrival
    stamps (the sequential-commit router assumes stream order)."""
    n, k, cells = 257, 4, 2
    reqs = compile_scenario(get_scenario(name, num_requests=n), seed=11,
                            num_models=k, num_cells=cells)
    assert reqs.model.shape == (n,) and reqs.model.dtype == np.int32
    model = np.asarray(reqs.model)
    assert ((model >= 0) & (model < k)).all()
    prompt = np.asarray(reqs.prompt_bits)
    assert ((prompt >= 1e5) & (prompt < 1e6)).all()
    gen = np.asarray(reqs.gen_tokens)
    assert ((gen >= 8) & (gen < 128)).all()
    cell = np.asarray(reqs.cell)
    assert ((cell >= 0) & (cell < cells)).all()
    arr = np.asarray(reqs.arrival_s)
    assert (np.diff(arr) >= 0).all(), f"{name} arrivals not sorted"
    assert arr[0] >= 0.0
    # single-cell topologies compile the cell column away
    single = compile_scenario(get_scenario(name, num_requests=16), seed=0,
                              num_models=k, num_cells=1)
    assert single.cell is None


def test_same_spec_seed_is_bit_identical_in_process():
    a = _stream_digest("bursty", 5, 300, 4, 2)
    b = _stream_digest("bursty", 5, 300, 4, 2)
    assert a == b
    assert a != _stream_digest("bursty", 6, 300, 4, 2)  # seed matters


def test_same_spec_seed_is_bit_identical_across_processes():
    """The determinism contract: (spec, seed) regenerates the stream
    bit-identically in a FRESH interpreter."""
    digest = _stream_digest("popularity-drift", 3, 200, 4, 2)
    repo = Path(__file__).resolve().parents[1]
    code = (
        "import sys; sys.path.insert(0, 'tests'); "
        "from test_workloads import _stream_digest; "
        "print(_stream_digest('popularity-drift', 3, 200, 4, 2))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, check=True,
        env=dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu"),
    )
    assert out.stdout.strip().splitlines()[-1] == digest


def test_component_independence():
    """Changing the arrival process must not reshuffle the other
    columns (each component draws from its own SeedSequence child)."""
    a = compile_scenario(ScenarioSpec(arrival="poisson", num_requests=100),
                         seed=9, num_models=4, num_cells=2)
    b = compile_scenario(
        ScenarioSpec(arrival="flash", spike_start_s=0.05, num_requests=100),
        seed=9, num_models=4, num_cells=2,
    )
    assert np.array_equal(np.asarray(a.model), np.asarray(b.model))
    assert np.array_equal(np.asarray(a.cell), np.asarray(b.cell))
    assert not np.array_equal(np.asarray(a.arrival_s),
                              np.asarray(b.arrival_s))


# ---------------------------------------------------------------------------
# generator semantics
# ---------------------------------------------------------------------------
def test_zipf_popularity_sums_to_one_and_ranks_decrease():
    p = generators.zipf_popularity(6, 1.5)
    assert np.isclose(p.sum(), 1.0)
    assert (np.diff(p) < 0).all()
    assert np.allclose(generators.zipf_popularity(5, 0.0), 0.2)  # uniform


def test_drifting_popularity_reorders_ranks():
    rng = np.random.default_rng(0)
    probs, perms = generators.drifting_popularity(rng, 8, 6, 1.5)
    assert np.allclose(probs.sum(axis=1), 1.0)
    base = generators.zipf_popularity(6, 1.5)
    # every row holds the same Zipf masses, re-assigned to models
    assert np.allclose(np.sort(probs, axis=1), np.sort(base))
    for w in range(8):  # perms[w, r] holds rank r's mass in window w
        assert np.allclose(probs[w, perms[w]], base)
    # the rank order actually changes across windows
    assert any(not np.array_equal(perms[0], perms[w]) for w in range(1, 8))


def test_flash_crowd_spikes_and_mmpp_bursts():
    rng = np.random.default_rng(2)
    arr = generators.flash_crowd_arrivals(rng, 2000, rate=100.0,
                                          spike_start_s=3.0, spike_dur_s=1.0,
                                          spike_mult=20.0)
    in_spike = ((arr >= 3.0) & (arr < 4.0)).sum()          # ~2000/s * 1s
    before = (arr < 3.0).sum() / 3.0                       # ~100/s
    assert in_spike / 1.0 > 5 * before
    arr = generators.mmpp_arrivals(np.random.default_rng(3), 2000, 50.0,
                                   2000.0, 2.0, 0.25)
    gaps = np.diff(arr)
    assert (gaps >= 0).all()
    # burst sojourns produce much denser gaps than quiet ones
    assert np.percentile(gaps, 10) < np.percentile(gaps, 90) / 5


def test_hotspot_cell_skew():
    reqs = compile_scenario(get_scenario("hotspot-cell", num_requests=2000),
                            seed=0, num_models=4, num_cells=4)
    share = (np.asarray(reqs.cell) == 0).mean()
    assert 0.6 < share < 0.8  # spec: 70% of traffic on cell 0


def test_burst_train_matches_legacy_fixture_construction():
    """The policy_serving port: generators consumed in the canonical
    order reproduce the legacy hand-rolled numpy stream bit for bit."""
    n, burst, gap = 512, 64, 0.5
    rng = np.random.default_rng(7)
    arrivals = generators.burst_train_arrivals(rng, n, burst, gap)
    fields = generators.stream_fields(rng, n, 4, num_cells=2)
    rng = np.random.default_rng(7)
    legacy_arr = np.sort((np.arange(n) // burst) * gap
                         + rng.uniform(0.0, 1e-3, n))
    assert np.array_equal(arrivals, legacy_arr)
    assert np.array_equal(fields["model"], rng.integers(0, 4, n))
    assert np.array_equal(fields["prompt_bits"], rng.uniform(1e5, 1e6, n))
    assert np.array_equal(fields["gen_tokens"], rng.integers(8, 128, n))
    assert np.array_equal(fields["cell"], rng.integers(0, 2, n))


# ---------------------------------------------------------------------------
# simulator: windowed episode == unwindowed oracle (drain-free)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["greedy", "load"])
def test_windowed_simulation_bit_matches_single_call(policy):
    catalog = build_catalog(EDGE_ARCHS)
    fleet = make_multicell_fleet(2, 3, catalog, drain_rate=0.0)
    params, state0 = br.fleet_from_servers(fleet, catalog)
    reqs = compile_scenario(get_scenario("bursty", num_requests=300), seed=1,
                            num_models=len(catalog), num_cells=2)
    state_w, out_w, series = simulate(params, state0, reqs, policy=policy,
                                      window_requests=64,
                                      cloud_index=len(fleet) - 1)
    state_1, out_1 = br.route_batch(params, state0, reqs, policy=policy)
    assert np.array_equal(np.asarray(out_w.choice), np.asarray(out_1.choice))
    assert np.array_equal(np.asarray(out_w.latency),
                          np.asarray(out_1.latency))
    assert np.array_equal(np.asarray(out_w.hit), np.asarray(out_1.hit))
    for a, b in zip(jax.tree.leaves(state_w), jax.tree.leaves(state_1)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # series shape checks: 300 requests in 64-windows -> 5 windows
    assert series.requests.tolist() == [64, 64, 64, 64, 44]
    assert (series.window_start_s[1:] >= series.window_end_s[:-1]).all()


def _serial_episode(params, state, reqs, *, w, policy="greedy",
                    drain_tokens=None, cloud_index=None, faults=None):
    """The episode without a pipeline: route a window, wait for it, take
    its queue percentiles, then stats and energy over the whole stream."""
    b = int(reqs.model.shape[0])
    n_srv = int(np.asarray(params.flops_per_s).shape[0])
    n = max(1, -(-b // w))
    arr = np.asarray(reqs.arrival_s)
    outs, q50, q90, qmax = [], [], [], []
    for i in range(n):
        sl = slice(i * w, min((i + 1) * w, b))
        win = jax.tree.map(lambda x: x[sl], reqs)
        dw = drain_tokens
        if dw is not None and np.ndim(dw) == 1:
            dw = dw[sl]
        p, outage = params, None
        if faults is not None:
            t = float(arr[sl.start])
            down = np.zeros(n_srv, bool)
            stall = np.zeros(n_srv, bool)
            for srv, start, end in faults.outages:
                down[srv] |= start <= t < end
            for srv, start, end in faults.drain_outages:
                stall[srv] |= start <= t < end
            if down.any():
                outage = jnp.asarray(down)
            if stall.any():
                p = params._replace(drain_rate=jnp.where(
                    jnp.asarray(stall), 0.0, params.drain_rate))
        state, out = br.route_batch(p, state, win, dw, policy=policy,
                                    outage=outage)
        outs.append(out)
        q = np.asarray(state.queue_tokens)
        if cloud_index is not None:
            q = np.delete(q, cloud_index)
        q50.append(np.percentile(q, 50))
        q90.append(np.percentile(q, 90))
        qmax.append(q.max())
    outcome = br.RouteOutcome(*(jnp.concatenate([getattr(o, f) for o in outs])
                                for f in br.RouteOutcome._fields))
    stats = br.window_stats(
        outcome, np.arange(b) // w, n, cloud_index=cloud_index,
        completed_means={"mean_energy_j": request_energy_j(params, reqs,
                                                           outcome)})
    starts = np.arange(0, b, w)
    series = SimResult(
        window_start_s=np.minimum.reduceat(arr, starts),
        window_end_s=np.maximum.reduceat(arr, starts),
        requests=stats["requests"], mean_latency=stats["mean_latency"],
        mean_energy_j=stats["mean_energy_j"],
        completion_rate=stats["completion_rate"],
        residency_hit_rate=stats["residency_hit_rate"],
        cloud_fallback_rate=stats.get("cloud_fallback_rate"),
        queue_p50=np.asarray(q50), queue_p90=np.asarray(q90),
        queue_max=np.asarray(qmax),
        infeasible_rate=stats.get("infeasible_rate"),
        admission_rate=stats.get("admission_rate"),
        outage_rate=stats.get("outage_rate"),
    )
    return state, outcome, series


def _assert_same_bits(a, b, what):
    if a is None or b is None:
        assert a is None and b is None, what
        return
    a, b = np.asarray(a), np.asarray(b)
    assert (a.dtype, a.shape) == (b.dtype, b.shape), what
    assert a.tobytes() == b.tobytes(), what


@pytest.mark.parametrize("case", ["cloud-ragged-tail", "drain-tokens",
                                  "faults", "load", "single-window"])
def test_pipelined_simulation_bit_matches_serial_episode(case):
    catalog = build_catalog(EDGE_ARCHS)
    fleet = make_multicell_fleet(2, 3, catalog, drain_rate=50.0)
    params, state0 = br.fleet_from_servers(fleet, catalog)
    reqs = compile_scenario(get_scenario("bursty", num_requests=300), seed=4,
                            num_models=len(catalog), num_cells=2)
    arr = np.asarray(reqs.arrival_s)
    kw = {"w": 64, "cloud_index": len(fleet) - 1}
    if case == "drain-tokens":
        kw["drain_tokens"] = np.random.default_rng(4).uniform(
            0.0, 40.0, 300).astype(np.float32)
    elif case == "faults":
        kw["w"] = 32
        kw["faults"] = FaultSpec(
            outages=((0, float(arr[60]), float(arr[150])),),
            drain_outages=((2, float(arr[100]), float(arr[250])),))
    elif case == "load":
        kw["policy"] = "load"
    elif case == "single-window":
        kw["w"] = 512
    want = _serial_episode(params, state0, reqs, **kw)
    w = kw.pop("w")
    got = simulate(params, state0, reqs, window_requests=w, **kw)
    for field in br.FleetState._fields:
        _assert_same_bits(getattr(got[0], field), getattr(want[0], field),
                          f"state.{field}")
    for field in br.RouteOutcome._fields:
        _assert_same_bits(getattr(got[1], field), getattr(want[1], field),
                          f"outcome.{field}")
    for field in SimResult._fields:
        _assert_same_bits(getattr(got[2], field), getattr(want[2], field),
                          f"series.{field}")
    if case == "faults":  # windows opening inside the outage avoid server 0
        starts = arr[::w]
        down = np.flatnonzero((starts >= arr[60]) & (starts < arr[150]))
        assert down.size
        choice = np.asarray(got[1].choice)
        for i in down:
            assert (choice[i * w:(i + 1) * w] != 0).all()


def test_window_stats_matches_stats():
    catalog = build_catalog(EDGE_ARCHS)
    fleet = make_multicell_fleet(2, 2, catalog, drain_rate=0.0)
    params, state0 = br.fleet_from_servers(fleet, catalog)
    reqs = compile_scenario(get_scenario("steady", num_requests=128), seed=2,
                            num_models=len(catalog), num_cells=2)
    _, out = br.route_batch(params, state0, reqs)
    cloud = len(fleet) - 1
    whole = br.stats(out, cloud_index=cloud)
    one = br.window_stats(out, np.zeros(128, np.int64), 1,
                          cloud_index=cloud)
    assert one["requests"].tolist() == [128]
    for key in ("mean_latency", "completion_rate", "residency_hit_rate",
                "cloud_fallback_rate"):
        assert np.isclose(one[key][0], whole[key]), key
    # two windows partition the stream: counts add up, rates average back
    two = br.window_stats(out, (np.arange(128) >= 64).astype(np.int64), 2,
                          cloud_index=cloud)
    assert two["requests"].sum() == 128
    assert np.isclose(two["residency_hit_rate"].mean(),
                      whole["residency_hit_rate"])
    # completed_means: a constant column averages back to the constant
    extra = br.window_stats(out, np.zeros(128, np.int64), 1,
                            completed_means={"x": np.full(128, 2.5)})
    assert np.isclose(extra["x"][0], 2.5)


def test_empty_and_rejected_windows_are_masked():
    out = br.RouteOutcome(
        choice=np.array([0, -1, 1, -1], np.int32),
        latency=np.array([1.0, np.inf, 3.0, np.inf]),
        hit=np.array([True, False, True, False]),
    )
    ws = br.window_stats(out, np.array([0, 0, 1, 1]), 3)
    assert np.allclose(ws["mean_latency"][:2], [1.0, 3.0])  # inf masked out
    assert ws["mean_latency"][2] == np.inf                  # empty window
    assert np.allclose(ws["completion_rate"], [0.5, 0.5, 0.0])
    # hit rate over COMPLETED requests: each window's one completed
    # request hit, so the rejections must not drag the rate to 0.5
    assert np.allclose(ws["residency_hit_rate"][:2], [1.0, 1.0])
    assert np.isnan(ws["residency_hit_rate"][2])            # empty window


def test_fully_rejected_window_reports_nan_not_zero():
    """A fully-rejected flash-crowd window has no completed requests: a
    completed-mean of 0.0 would read as impossibly perfect (zero energy
    per request) — it must be nan, consistent with inf mean_latency."""
    out = br.RouteOutcome(
        choice=np.array([0, 1, -1, -1], np.int32),
        latency=np.array([1.0, 3.0, np.inf, np.inf]),
        hit=np.array([True, False, False, False]),
    )
    ws = br.window_stats(out, np.array([0, 0, 1, 1]), 2,
                         completed_means={"energy_j": np.array(
                             [2.0, 4.0, 0.0, 0.0])})
    assert np.isclose(ws["energy_j"][0], 3.0)
    assert np.isnan(ws["energy_j"][1])       # zero completed -> nan
    assert ws["mean_latency"][1] == np.inf
    assert np.isnan(ws["residency_hit_rate"][1])
    assert ws["completion_rate"][1] == 0.0   # the rate itself is real


# ---------------------------------------------------------------------------
# the paper's switching dynamic + serve wiring
# ---------------------------------------------------------------------------
def test_popularity_drift_lowers_hit_rate():
    """The acceptance dynamic: under the same greedy policy and a fleet
    whose per-cell cache cannot hold the whole catalogue, drifting
    popularity forces eq. 7 switches that steady traffic avoids."""
    from benchmarks.scenario_suite import (ARCHS, CACHE_SLOTS, CELLS,
                                           DRAIN_RATE, SERVERS_PER_CELL)

    catalog = build_catalog(ARCHS)
    fleet = make_multicell_fleet(CELLS, SERVERS_PER_CELL, catalog,
                                 slots=CACHE_SLOTS, drain_rate=DRAIN_RATE,
                                 cloud=False)
    params, state0 = br.fleet_from_servers(fleet, catalog)
    hit = {}
    for name in ("steady", "popularity-drift"):
        reqs = compile_scenario(get_scenario(name), seed=0,
                                num_models=len(catalog), num_cells=CELLS)
        _, out, _ = simulate(params, state0, reqs, policy="greedy")
        hit[name] = br.stats(out)["residency_hit_rate"]
    assert hit["popularity-drift"] < hit["steady"] - 0.02, hit


def test_serve_scenario_roundtrip():
    """serve(--scenario, --seed) wires the compiled stream end to end
    and is reproducible: same seed, same stats; different seed, a
    different stream."""
    kw = dict(num_requests=48, n_servers=2, execute=False, n_cells=2,
              drain_rate=2e4, scenario="hotspot-cell")
    a = serve(seed=5, **kw)
    b = serve(seed=5, **kw)
    c = serve(seed=6, **kw)
    assert a["scenario"] == "hotspot-cell" and a["seed"] == 5
    for key in ("mean_latency", "residency_hit_rate", "completion_rate",
                "cloud_fallback_rate"):
        assert a[key] == b[key], key
    assert any(a[k] != c[k] for k in ("mean_latency", "cloud_fallback_rate"))


def test_scenario_suite_registered_in_run():
    from benchmarks import run as bench_run

    assert "scenarios" in dict(bench_run.SECTIONS)
