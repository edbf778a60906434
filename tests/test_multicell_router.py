"""Multi-cell fleets + time-based drain vs the scalar oracle — exact.

The block-diagonal cell mask (in-cell servers + the fleet-wide
``CLOUD_CELL`` fallback column) and the wall-clock queue drain
(``drain_rate * dt`` folded into the scan carry) must reproduce the
scalar ``ModelAwareRouter`` request for request, for C in {1, 2, 4}
cells — same choices, residency, LRU clocks, queues and fleet clock.
The time-based drain is additionally pinned against a hand-computed
queue trace, and ``drain_rate == 0`` must reproduce the synchronous
(PR 1) behaviour bit for bit.
"""
import copy
import json

import numpy as np
import pytest
import jax.numpy as jnp

from x64 import enable_x64
from repro.core import batch_router as br
from repro.core.catalog import build_catalog
from repro.core.router import CLOUD_CELL, EdgeServer, ModelAwareRouter, Request
from repro.launch.serve import make_cloud_server, make_multicell_fleet

CATALOG = build_catalog(
    ["smollm_135m", "starcoder2_3b", "mamba2_2p7b", "musicgen_medium"]
)


def _random_multicell_fleet(rng, n_cells, per_cell, cache_slots=2,
                            drain_hi=40.0, cloud=True):
    fleet = [
        EdgeServer(
            name=f"c{c}-es{i}",
            flops_per_s=float(rng.uniform(5e13, 2e14)),
            cache_slots=cache_slots,
            uplink_bps=float(rng.uniform(5e7, 2e8)),
            backhaul_bps=float(rng.uniform(5e8, 2e9)),
            resident=list(
                rng.choice(len(CATALOG), size=cache_slots, replace=False)
            ),
            cell=c,
            drain_rate=float(rng.uniform(0.0, drain_hi)),
        )
        for c in range(n_cells)
        for i in range(per_cell)
    ]
    if cloud:
        fleet.append(
            make_cloud_server(
                CATALOG, drain_rate=float(rng.uniform(0.0, 2.0 * drain_hi))
            )
        )
    return fleet


def _random_stream(rng, n, n_cells, rate=500.0):
    return (
        rng.integers(0, len(CATALOG), n),
        rng.uniform(1e5, 1e6, n),
        rng.integers(1, 64, n),
        rng.integers(0, n_cells, n),
        np.cumsum(rng.exponential(1.0 / rate, n)),
    )


def _run_scalar(fleet, models, bits, toks, cells, arrivals, **knobs):
    """The scalar oracle over the stream; ``knobs`` are optional
    per-request ``Request`` columns (``deadline_s``, ``eta``, ...)."""
    router = ModelAwareRouter(copy.deepcopy(fleet), CATALOG)
    choices, lats = [], []
    for i, (m, b, t, c, a) in enumerate(
            zip(models, bits, toks, cells, arrivals)):
        ch, l = router.route(
            Request(int(m), float(b), int(t), cell=int(c), arrival_s=float(a),
                    **{k: float(v[i]) for k, v in knobs.items()})
        )
        choices.append(ch)
        lats.append(l)
    return router, np.array(choices), np.array(lats)


def _run_batched(fleet, models, bits, toks, cells, arrivals, dtype):
    params, state = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, dtype),
        gen_tokens=jnp.asarray(toks, dtype),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, dtype),
    )
    return br.route_batch(params, state, reqs)


def _assert_fleet_state_matches(router, state):
    resident = np.asarray(state.resident)
    last_use = np.asarray(state.last_use)
    for i, srv in enumerate(router.servers):
        assert set(np.nonzero(resident[i])[0]) == set(srv.resident), i
        for m in srv.resident:
            if m in srv.last_use:
                assert last_use[i, m] == srv.last_use[m], (i, m)
    np.testing.assert_allclose(
        np.asarray(state.queue_tokens),
        np.array([s.queue_tokens for s in router.servers]),
        rtol=1e-6, atol=1e-9,
    )
    np.testing.assert_allclose(float(state.time_s), router.time_s, rtol=1e-6)


@pytest.mark.parametrize("seed,n_cells,per_cell", [
    (0, 1, 4), (1, 2, 3), (2, 4, 2), (3, 4, 4),
])
def test_multicell_matches_scalar_oracle_exactly(seed, n_cells, per_cell):
    """x64: C-cell fleets with cloud + time drain match the oracle."""
    with enable_x64():
        rng = np.random.default_rng(seed)
        fleet = _random_multicell_fleet(rng, n_cells, per_cell)
        models, bits, toks, cells, arrivals = _random_stream(
            rng, 300, n_cells
        )
        router, sc_choice, sc_lat = _run_scalar(
            fleet, models, bits, toks, cells, arrivals
        )
        state, out = _run_batched(
            fleet, models, bits, toks, cells, arrivals, jnp.float64
        )
        np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
        np.testing.assert_allclose(np.asarray(out.latency), sc_lat,
                                   rtol=1e-12, atol=0.0)
        _assert_fleet_state_matches(router, state)


@pytest.mark.parametrize("seed,n_cells", [(10, 2), (11, 4)])
def test_float32_multicell_same_decisions(seed, n_cells):
    """The f32 serving path agrees on every choice and residency set."""
    rng = np.random.default_rng(seed)
    fleet = _random_multicell_fleet(rng, n_cells, 3)
    models, bits, toks, cells, arrivals = _random_stream(rng, 400, n_cells)
    router, sc_choice, _ = _run_scalar(
        fleet, models, bits, toks, cells, arrivals
    )
    state, out = _run_batched(
        fleet, models, bits, toks, cells, arrivals, jnp.float32
    )
    np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
    resident = np.asarray(state.resident)
    for i, srv in enumerate(router.servers):
        assert set(np.nonzero(resident[i])[0]) == set(srv.resident), i


def test_choices_respect_cell_boundaries():
    """No request ever lands on an out-of-cell edge server."""
    rng = np.random.default_rng(5)
    fleet = _random_multicell_fleet(rng, 4, 3)
    models, bits, toks, cells, arrivals = _random_stream(rng, 500, 4)
    _, out = _run_batched(
        fleet, models, bits, toks, cells, arrivals, jnp.float32
    )
    srv_cell = np.array([s.cell for s in fleet])
    chosen = srv_cell[np.asarray(out.choice)]
    assert np.all((chosen == cells) | (chosen == CLOUD_CELL))
    # the cell-starved layout must actually exercise the cloud column
    assert np.any(chosen == CLOUD_CELL) or len(set(cells)) == 1


def test_score_matrix_masks_out_of_cell_servers():
    """(B, N) scores are +inf exactly on the out-of-cell, non-cloud pairs."""
    rng = np.random.default_rng(6)
    fleet = _random_multicell_fleet(rng, 3, 2)
    params, state = br.fleet_from_servers(fleet, CATALOG)
    models, bits, toks, cells, _ = _random_stream(rng, 40, 3)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
    )
    scores = np.asarray(br.score_matrix(params, state, reqs))
    srv_cell = np.array([s.cell for s in fleet])
    visible = (srv_cell[None, :] == cells[:, None]) | (
        srv_cell[None, :] == CLOUD_CELL
    )
    assert np.all(np.isinf(scores[~visible]))
    assert np.all(np.isfinite(scores[visible]))


def test_time_drain_matches_hand_computed_trace():
    """Queue decay over a synthetic wall-clock schedule, checked by hand.

    Two single-server cells force every choice, so the queues follow
    arithmetic we can do on paper:
      r0 cell0 t=1.0 gen=10:   dt=1.0  q=(0,0)          -> commit (10, 0)
      r1 cell1 t=2.0 gen=5:    dt=1.0  q=(10-2, 0)      -> commit (8, 5)
      r2 cell0 t=4.5 gen=3:    dt=2.5  q=(8-5, 5-7.5|0) -> commit (6, 0)
      r3 cell0 t=4.5 gen=1:    dt=0.0  q=(6, 0)         -> commit (7, 0)
    """
    with enable_x64():
        mk = lambda cell, drain: EdgeServer(
            name=f"s{cell}", flops_per_s=1e14, cache_slots=len(CATALOG),
            uplink_bps=1e8, backhaul_bps=1e9,
            resident=list(range(len(CATALOG))), cell=cell, drain_rate=drain,
        )
        fleet = [mk(0, 2.0), mk(1, 3.0)]
        params, state = br.fleet_from_servers(fleet, CATALOG)
        reqs = br.RequestBatch(
            model=jnp.zeros((4,), jnp.int32),
            prompt_bits=jnp.full((4,), 1e5, jnp.float64),
            gen_tokens=jnp.asarray([10.0, 5.0, 3.0, 1.0], jnp.float64),
            cell=jnp.asarray([0, 1, 0, 0], jnp.int32),
            arrival_s=jnp.asarray([1.0, 2.0, 4.5, 4.5], jnp.float64),
        )
        state, out = br.route_batch(params, state, reqs)
        np.testing.assert_array_equal(np.asarray(out.choice), [0, 1, 0, 0])
        np.testing.assert_allclose(
            np.asarray(state.queue_tokens), [7.0, 0.0], rtol=0, atol=0
        )
        assert float(state.time_s) == 4.5


def test_drain_rate_zero_is_exactly_synchronous():
    """drain_rate == 0 with arrival stamps == the PR 1 no-drain path, bit
    for bit (choices, latencies, queues, residency, LRU clocks)."""
    rng = np.random.default_rng(14)
    fleet = _random_multicell_fleet(rng, 2, 3, drain_hi=0.0)
    assert all(s.drain_rate == 0.0 for s in fleet)
    models, bits, toks, cells, arrivals = _random_stream(rng, 250, 2)

    params, state = br.fleet_from_servers(fleet, CATALOG)
    base = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
    )
    timed = base._replace(arrival_s=jnp.asarray(arrivals, jnp.float32))

    state_sync, out_sync = br.route_batch(params, state, base)
    state_time, out_time = br.route_batch(params, state, timed)

    np.testing.assert_array_equal(np.asarray(out_sync.choice),
                                  np.asarray(out_time.choice))
    np.testing.assert_array_equal(np.asarray(out_sync.latency),
                                  np.asarray(out_time.latency))
    np.testing.assert_array_equal(np.asarray(state_sync.queue_tokens),
                                  np.asarray(state_time.queue_tokens))
    np.testing.assert_array_equal(np.asarray(state_sync.resident),
                                  np.asarray(state_time.resident))
    np.testing.assert_array_equal(np.asarray(state_sync.last_use),
                                  np.asarray(state_time.last_use))


def test_midstream_snapshot_carries_wall_clock():
    """Snapshotting the oracle mid-stream must thread time_s or the next
    batched drain would replay the whole elapsed wall clock."""
    with enable_x64():
        rng = np.random.default_rng(15)
        fleet = _random_multicell_fleet(rng, 2, 2)
        models, bits, toks, cells, arrivals = _random_stream(rng, 200, 2)

        router, sc_choice, _ = _run_scalar(
            fleet, models, bits, toks, cells, arrivals
        )

        half = 100
        warm = ModelAwareRouter(copy.deepcopy(fleet), CATALOG)
        for m, b, t, c, a in zip(models[:half], bits[:half], toks[:half],
                                 cells[:half], arrivals[:half]):
            warm.route(Request(int(m), float(b), int(t), cell=int(c),
                               arrival_s=float(a)))
        params, state = br.fleet_from_servers(
            warm.servers, CATALOG, clock=warm.clock, time_s=warm.time_s
        )
        reqs = br.RequestBatch(
            model=jnp.asarray(models[half:], jnp.int32),
            prompt_bits=jnp.asarray(bits[half:], jnp.float64),
            gen_tokens=jnp.asarray(toks[half:], jnp.float64),
            cell=jnp.asarray(cells[half:], jnp.int32),
            arrival_s=jnp.asarray(arrivals[half:], jnp.float64),
        )
        state, out = br.route_batch(params, state, reqs)
        np.testing.assert_array_equal(np.asarray(out.choice),
                                      sc_choice[half:])
        _assert_fleet_state_matches(router, state)


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("seed,n_cells,chunk", [
    (40, 1, 64), (41, 2, 100), (42, 4, 64), (43, 4, 300),
])
def test_chunked_multicell_matches_scalar_oracle(seed, n_cells, chunk,
                                                 backend):
    """The chunked two-phase commit reproduces the oracle for C in
    {1, 2, 4} cells with cloud fallback + time drain enabled, under
    both scoring backends, including chunks that do not divide B."""
    with enable_x64():
        rng = np.random.default_rng(seed)
        fleet = _random_multicell_fleet(rng, n_cells, 3)
        models, bits, toks, cells, arrivals = _random_stream(
            rng, 250, n_cells
        )
        router, sc_choice, sc_lat = _run_scalar(
            fleet, models, bits, toks, cells, arrivals
        )
        params, state = br.fleet_from_servers(fleet, CATALOG)
        reqs = br.RequestBatch(
            model=jnp.asarray(models, jnp.int32),
            prompt_bits=jnp.asarray(bits, jnp.float64),
            gen_tokens=jnp.asarray(toks, jnp.float64),
            cell=jnp.asarray(cells, jnp.int32),
            arrival_s=jnp.asarray(arrivals, jnp.float64),
        )
        state, out = br.route_batch(params, state, reqs, chunk=chunk,
                                    backend=backend)
        np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
        # the chunked path re-associates eq. 9 (see batch_router
        # docstring): latencies agree to ulps, decisions exactly
        np.testing.assert_allclose(np.asarray(out.latency), sc_lat,
                                   rtol=1e-12, atol=0.0)
        _assert_fleet_state_matches(router, state)


def test_chunked_orphan_rejection_and_stats():
    """Chunked path: infeasible requests reject uncommitted, and
    ``stats`` masks them out of mean_latency via completion_rate."""
    rng = np.random.default_rng(44)
    fleet = _random_multicell_fleet(rng, 2, 2, cloud=False)
    models = np.array([0, 1, 2, 3])
    bits = np.array([2e5, 3e5, 4e5, 5e5])
    toks = np.array([8, 16, 4, 2])
    cells = np.array([0, 5, 1, 7])  # requests 1 and 3 are unroutable
    arrivals = np.array([0.1, 0.2, 0.3, 0.4])

    router, sc_choice, _ = _run_scalar(
        fleet, models, bits, toks, cells, arrivals
    )
    params, state = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    state, out = br.route_batch(params, state, reqs, chunk=3)
    np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
    assert np.isinf(np.asarray(out.latency)[[1, 3]]).all()
    _assert_fleet_state_matches(router, state)

    summary = br.stats(out)
    assert summary["completion_rate"] == pytest.approx(0.5)
    assert np.isfinite(summary["mean_latency"])


def test_chunked_clamps_custom_policy_like_legacy():
    """A custom callable policy that picks out-of-cell servers is
    clamped to the masked argmin identically on the chunked and
    single-scan paths (decision-for-decision, state-for-state)."""

    def rogue(lats, obs, queue):
        return jnp.int32(0)  # always server 0, whatever the cell

    rng = np.random.default_rng(45)
    fleet = _random_multicell_fleet(rng, 3, 2)
    models, bits, toks, cells, arrivals = _random_stream(rng, 150, 3)
    params, state = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    s0, o0 = br.route_batch(params, state, reqs, policy=rogue)
    s1, o1 = br.route_batch(params, state, reqs, policy=rogue, chunk=64)
    np.testing.assert_array_equal(np.asarray(o0.choice),
                                  np.asarray(o1.choice))
    np.testing.assert_array_equal(np.asarray(s0.resident),
                                  np.asarray(s1.resident))
    srv_cell = np.array([s.cell for s in fleet])
    chosen = srv_cell[np.asarray(o1.choice)]
    assert np.all((chosen == cells) | (chosen == CLOUD_CELL))


def test_actor_cannot_escape_cell_mask():
    """An actor that picks out-of-cell servers is clamped to the masked
    greedy argmin — identically in the scalar and batched paths."""

    def rogue_actor(obs, lats):
        return jnp.int32(0)  # always server 0, whatever the cell

    rng = np.random.default_rng(16)
    fleet = _random_multicell_fleet(rng, 3, 2)
    models, bits, toks, cells, arrivals = _random_stream(rng, 150, 3)

    router = ModelAwareRouter(copy.deepcopy(fleet), CATALOG,
                              policy="actor", actor=rogue_actor)
    sc_choice = [
        router.route(Request(int(m), float(b), int(t), cell=int(c),
                             arrival_s=float(a)))[0]
        for m, b, t, c, a in zip(models, bits, toks, cells, arrivals)
    ]

    params, state = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    state, out = br.route_batch(params, state, reqs, policy="actor",
                                actor=rogue_actor)
    np.testing.assert_array_equal(np.asarray(out.choice),
                                  np.array(sc_choice))
    srv_cell = np.array([s.cell for s in fleet])
    chosen = srv_cell[np.asarray(out.choice)]
    assert np.all((chosen == cells) | (chosen == CLOUD_CELL))
    # server 0 (cell 0) must still be honoured for cell-0 requests
    assert np.any(np.asarray(out.choice)[cells == 0] == 0)


def test_orphan_cell_requests_are_rejected_uncommitted():
    """A cell with no servers and no cloud column: choice -1, inf latency,
    and NO state mutation — identically in scalar and batched paths."""
    rng = np.random.default_rng(17)
    fleet = _random_multicell_fleet(rng, 2, 2, cloud=False)
    # cells: request 0 is routable (cell 0); request 1 references cell 5
    models = np.array([0, 1, 2])
    bits = np.array([2e5, 3e5, 4e5])
    toks = np.array([8, 16, 4])
    cells = np.array([0, 5, 1])
    arrivals = np.array([0.1, 0.2, 0.3])

    router, sc_choice, sc_lat = _run_scalar(
        fleet, models, bits, toks, cells, arrivals
    )
    state, out = _run_batched(
        fleet, models, bits, toks, cells, arrivals, jnp.float32
    )
    assert sc_choice.tolist()[1] == -1 and np.isinf(sc_lat[1])
    np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
    assert np.isinf(np.asarray(out.latency)[1])
    assert not bool(np.asarray(out.hit)[1])
    _assert_fleet_state_matches(router, state)
    # the orphan's model must not have been cached anywhere new
    initially = np.array([1 in s.resident for s in fleet])
    np.testing.assert_array_equal(np.asarray(state.resident)[:, 1], initially)


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("seed,n_cells,chunk,cache_slots,cloud,knobs", [
    # hit-heavy: whole chunks commit speculatively
    pytest.param(50, 1, 64, 2, True, False, id="50-1-64-2-True"),
    # slots=1 + orphan cells: constant conflicts
    pytest.param(51, 2, 100, 1, False, False, id="51-2-100-1-False"),
    # miss-heavy with cloud + drain
    pytest.param(52, 4, 64, 1, True, False, id="52-4-64-1-True"),
    # misses replayed up to a padded last chunk, under an SLO deadline
    # column and a partial offload priced on the device (eta + local)
    pytest.param(53, 2, 64, 1, False, True, id="53-2-64-1-False-slo-eta"),
])
def test_speculative_commit_matches_scalar_oracle(seed, n_cells, chunk,
                                                  cache_slots, cloud, knobs,
                                                  backend):
    """The speculative parallel commit reproduces the scalar oracle —
    choices, LRU clocks, residency, queues and fleet clock — for C in
    {1, 2, 4} cells with cloud fallback, time drain and rejections, on
    both scoring backends. The slots=1 configs force a residency-
    mutating commit (a conflict) in essentially every chunk, so the
    serial suffix replay is exercised, not just the all-hit fast path;
    the no-cloud config streams orphan cells so rejected requests flow
    through the speculative recurrence too, and the ``knobs`` config
    runs the replay through the deadline, eta and local-rate columns.
    The speculative path must also equal the plain correction scan bit
    for bit (latencies included), not merely to ulps."""
    with enable_x64():
        rng = np.random.default_rng(seed)
        fleet = _random_multicell_fleet(rng, n_cells, 3,
                                        cache_slots=cache_slots, cloud=cloud)
        # without the cloud column, draw some unroutable cells too
        models, bits, toks, cells, arrivals = _random_stream(
            rng, 250, n_cells if cloud else n_cells + 1
        )
        cols = {}
        if knobs:  # tight / loose / no SLO; quarter offload ratios
            krng = np.random.default_rng([seed, 1])
            cols = dict(
                deadline_s=krng.choice([0.05, 5.0, np.inf], size=250),
                eta=krng.choice([0.0, 0.25, 0.5, 0.75, 1.0], size=250),
                local_flops_per_s=krng.uniform(5e11, 5e12, 250),
            )
        router, sc_choice, sc_lat = _run_scalar(
            fleet, models, bits, toks, cells, arrivals, **cols
        )
        params, state0 = br.fleet_from_servers(fleet, CATALOG)
        reqs = br.RequestBatch(
            model=jnp.asarray(models, jnp.int32),
            prompt_bits=jnp.asarray(bits, jnp.float64),
            gen_tokens=jnp.asarray(toks, jnp.float64),
            cell=jnp.asarray(cells, jnp.int32),
            arrival_s=jnp.asarray(arrivals, jnp.float64),
            **{k: jnp.asarray(v, jnp.float64) for k, v in cols.items()},
        )
        st_spec, out_spec = br.route_batch(params, state0, reqs, chunk=chunk,
                                           backend=backend, speculative=True)
        st_ser, out_ser = br.route_batch(params, state0, reqs, chunk=chunk,
                                         backend=backend, speculative=False)
        if not cloud:  # the orphan cells actually exercised rejection
            assert (sc_choice == -1).any()
        if knobs:  # the deadlines rejected some, and misses were replayed
            assert (sc_choice == -1).any()
            assert (~np.asarray(out_spec.hit) & (sc_choice >= 0)).any()
        np.testing.assert_array_equal(np.asarray(out_spec.choice), sc_choice)
        # an SLO rejection reports its best score, where the oracle
        # reports inf: latencies are compared where the oracle routed
        done = sc_choice >= 0 if knobs else slice(None)
        np.testing.assert_allclose(np.asarray(out_spec.latency)[done],
                                   sc_lat[done], rtol=1e-12, atol=0.0)
        _assert_fleet_state_matches(router, st_spec)
        # speculative vs serial correction scan: bit-identical
        np.testing.assert_array_equal(np.asarray(out_spec.choice),
                                      np.asarray(out_ser.choice))
        np.testing.assert_array_equal(np.asarray(out_spec.latency),
                                      np.asarray(out_ser.latency))
        np.testing.assert_array_equal(np.asarray(out_spec.hit),
                                      np.asarray(out_ser.hit))
        for a, b in zip(st_spec, st_ser):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_empty_cell_rejection_heavy_chunked_matches_scan():
    """An EMPTY cell (no servers, no cloud column): every request tagged
    to it is rejected, and the scan, chunked and speculative paths all
    agree with the scalar oracle decision for decision — the inert
    rejected steps must not desynchronise the chunk bookkeeping."""
    mk = lambda c, i, res: EdgeServer(
        name=f"c{c}-es{i}", flops_per_s=1e14, cache_slots=2,
        uplink_bps=1e8, backhaul_bps=1e9, resident=res, cell=c,
        drain_rate=2e4,
    )
    fleet = [mk(0, 0, [0, 1]), mk(0, 1, [2, 3]),
             mk(2, 0, [1, 2]), mk(2, 1, [0, 3])]  # cell 1 has no servers
    rng = np.random.default_rng(53)
    models, bits, toks, cells, arrivals = _random_stream(rng, 200, 3)
    assert (cells == 1).any()

    router, sc_choice, _ = _run_scalar(
        fleet, models, bits, toks, cells, arrivals
    )
    assert (sc_choice == -1).sum() >= 50  # genuinely rejection-heavy
    params, state0 = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    st_scan, out_scan = br.route_batch(params, state0, reqs)
    runs = {
        "chunked": br.route_batch(params, state0, reqs, chunk=64,
                                  speculative=False),
        "spec": br.route_batch(params, state0, reqs, chunk=64,
                               speculative=True),
    }
    np.testing.assert_array_equal(np.asarray(out_scan.choice), sc_choice)
    # f32 stream: decisions/residency vs the oracle exactly, queues to f32
    resident = np.asarray(st_scan.resident)
    for i, srv in enumerate(router.servers):
        assert set(np.nonzero(resident[i])[0]) == set(srv.resident), i
    np.testing.assert_allclose(
        np.asarray(st_scan.queue_tokens),
        [s.queue_tokens for s in router.servers], rtol=1e-4,
    )
    for name, (st, out) in runs.items():
        np.testing.assert_array_equal(np.asarray(out.choice), sc_choice,
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(out.hit),
                                      np.asarray(out_scan.hit), err_msg=name)
        np.testing.assert_array_equal(np.asarray(st.resident), resident,
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(st.last_use),
                                      np.asarray(st_scan.last_use),
                                      err_msg=name)
    # the hit-rate fix: rejected requests don't deflate the metric
    s = br.stats(out_scan)
    ok = sc_choice >= 0
    assert s["completion_rate"] == pytest.approx(ok.mean())
    assert s["residency_hit_rate"] == pytest.approx(
        np.asarray(out_scan.hit)[ok].mean())


# ---------------------------------------------------------------------------
# mesh-sharded routing (core.mesh_router) — the multi-device matrix.
#
# Marked ``multidevice``: on a 1-device host conftest re-runs these once in
# a forced-8-device child (see tests/conftest.py and docs/sharding.md).
# Exactness tiers, pinned here exactly as the module docstring states them:
#   * device-count invariance is ALWAYS bitwise (any fleet, any policy);
#   * vs the plain single-device scan, bitwise whenever no cross-cell cloud
#     feedback exists inside the window (cloud-free fleets, or streams
#     where a single cell contributes all cloud traffic) and drain_rate=0;
#   * with drain_rate > 0 the per-cell decay composition differs from the
#     per-global-arrival one by ulps — choices/latencies agree, queues to
#     a tolerance.
# ---------------------------------------------------------------------------
from repro.core import mesh_router as mr  # noqa: E402


def _sharded_state_equal(st_a, st_b):
    for f in ("resident", "queue_tokens"):
        np.testing.assert_array_equal(np.asarray(getattr(st_a, f)),
                                      np.asarray(getattr(st_b, f)), err_msg=f)
    assert int(st_a.clock) == int(st_b.clock)
    if st_a.time_s is not None:
        np.testing.assert_array_equal(np.asarray(st_a.time_s),
                                      np.asarray(st_b.time_s))
    res = np.asarray(st_a.resident)
    np.testing.assert_array_equal(np.asarray(st_a.last_use)[res],
                                  np.asarray(st_b.last_use)[res])


def _sharded_outcome_equal(out_a, out_b):
    for f in br.RouteOutcome._fields:
        np.testing.assert_array_equal(np.asarray(getattr(out_a, f)),
                                      np.asarray(getattr(out_b, f)), err_msg=f)


@pytest.mark.multidevice
@pytest.mark.parametrize("devices", [1, 2, 4, 8])
@pytest.mark.parametrize("n_cells", [2, 4, 8])
def test_sharded_bitwise_vs_plain_cloud_free(n_cells, devices):
    """C x D matrix (non-dividing pairs included: 8 cells on 4 devices
    packs 2 blocks/device, 2 cells on 8 leaves idle shards): cloud-free
    drain-free fleets are bitwise vs the plain scan AND the oracle."""
    rng = np.random.default_rng(100 + 10 * n_cells + devices)
    fleet = _random_multicell_fleet(rng, n_cells, 3, drain_hi=0.0,
                                    cloud=False)
    models, bits, toks, cells, arrivals = _random_stream(rng, 200, n_cells)
    params, state0 = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    st_p, out_p = br.route_batch(params, state0, reqs)
    st_s, out_s = mr.route_batch_sharded(params, state0, reqs,
                                         num_devices=devices)
    _sharded_outcome_equal(out_p, out_s)
    _sharded_state_equal(st_p, st_s)
    router, sc_choice, _ = _run_scalar(fleet, models, bits, toks, cells,
                                       arrivals)
    np.testing.assert_array_equal(np.asarray(out_s.choice), sc_choice)


@pytest.mark.multidevice
@pytest.mark.parametrize("n_cells,devices", [(3, 8), (5, 4), (6, 4)])
def test_sharded_non_dividing_cell_device_counts(n_cells, devices):
    """Cell counts that do not divide (or even reach) the device count
    still route bitwise vs the plain scan."""
    rng = np.random.default_rng(200 + n_cells)
    fleet = _random_multicell_fleet(rng, n_cells, 2, drain_hi=0.0,
                                    cloud=False)
    models, bits, toks, cells, arrivals = _random_stream(rng, 150, n_cells)
    params, state0 = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    st_p, out_p = br.route_batch(params, state0, reqs)
    st_s, out_s = mr.route_batch_sharded(params, state0, reqs,
                                         num_devices=devices)
    _sharded_outcome_equal(out_p, out_s)
    _sharded_state_equal(st_p, st_s)


@pytest.mark.multidevice
@pytest.mark.parametrize("devices", [2, 8])
def test_sharded_cloud_single_contributor_bitwise(devices):
    """With a cloud column but ALL traffic from one cell, no cross-cell
    cloud feedback exists — the sharded window is bitwise vs the plain
    scan, cloud backlog and cloud LRU included."""
    rng = np.random.default_rng(300 + devices)
    fleet = _random_multicell_fleet(rng, 4, 2, drain_hi=0.0, cloud=True)
    models, bits, toks, _, arrivals = _random_stream(rng, 150, 1)
    cells = np.zeros(150, np.int64)
    params, state0 = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    st_p, out_p = br.route_batch(params, state0, reqs)
    st_s, out_s = mr.route_batch_sharded(params, state0, reqs,
                                         num_devices=devices)
    # the fixture must actually exercise the shared cloud column
    srv_cell = np.array([s.cell for s in fleet])
    assert (srv_cell[np.asarray(out_s.choice)] == CLOUD_CELL).any()
    _sharded_outcome_equal(out_p, out_s)
    _sharded_state_equal(st_p, st_s)


@pytest.mark.multidevice
@pytest.mark.parametrize("chunk", [None, 64])
@pytest.mark.parametrize("n_cells", [3, 8])
def test_sharded_device_count_invariance(n_cells, chunk):
    """THE sharded-router invariant: the device count is a pure execution
    detail. Cloud on, drain on, all cells contributing — the hardest
    configuration — must produce bit-identical choices, outcomes, queues,
    residency and LRU clocks for D in {1, 2, 4, 8}."""
    rng = np.random.default_rng(400 + n_cells + (0 if chunk is None else 1))
    fleet = _random_multicell_fleet(rng, n_cells, 2, drain_hi=40.0,
                                    cloud=True)
    models, bits, toks, cells, arrivals = _random_stream(rng, 200, n_cells)
    params, state0 = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    st_1, out_1 = mr.route_batch_sharded(params, state0, reqs,
                                         num_devices=1, chunk=chunk)
    for d in (2, 4, 8):
        st_d, out_d = mr.route_batch_sharded(params, state0, reqs,
                                             num_devices=d, chunk=chunk)
        _sharded_outcome_equal(out_1, out_d)
        _sharded_state_equal(st_1, st_d)


@pytest.mark.multidevice
def test_sharded_rejections_and_orphan_cells():
    """No cloud + out-of-range request cells: rejections (-1, inf, no
    mutation) flow through the sharded path exactly like the plain scan
    and the scalar oracle."""
    rng = np.random.default_rng(500)
    fleet = _random_multicell_fleet(rng, 3, 2, drain_hi=0.0, cloud=False)
    models, bits, toks, cells, arrivals = _random_stream(rng, 150, 5)
    assert (cells >= 3).any()
    params, state0 = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    st_p, out_p = br.route_batch(params, state0, reqs)
    st_s, out_s = mr.route_batch_sharded(params, state0, reqs, num_devices=4)
    router, sc_choice, _ = _run_scalar(fleet, models, bits, toks, cells,
                                       arrivals)
    assert (sc_choice == -1).any()
    np.testing.assert_array_equal(np.asarray(out_s.choice), sc_choice)
    _sharded_outcome_equal(out_p, out_s)
    _sharded_state_equal(st_p, st_s)


@pytest.mark.multidevice
def test_sharded_drain_rate_close_to_plain():
    """drain_rate > 0: each cell composes its queue decay over its OWN
    arrival gaps while the plain scan decays at every global arrival —
    same total elapsed time, but the clamp at zero fires at different
    instants, so queues drift a fraction of a percent over a window.
    Decisions and latencies agree; queues to a tolerance. (Bitwise
    ACROSS device counts is pinned separately by
    test_sharded_device_count_invariance.)"""
    with enable_x64():
        rng = np.random.default_rng(600)
        fleet = _random_multicell_fleet(rng, 4, 3, drain_hi=40.0,
                                        cloud=False)
        models, bits, toks, cells, arrivals = _random_stream(rng, 250, 4)
        params, state0 = br.fleet_from_servers(fleet, CATALOG)
        reqs = br.RequestBatch(
            model=jnp.asarray(models, jnp.int32),
            prompt_bits=jnp.asarray(bits, jnp.float64),
            gen_tokens=jnp.asarray(toks, jnp.float64),
            cell=jnp.asarray(cells, jnp.int32),
            arrival_s=jnp.asarray(arrivals, jnp.float64),
        )
        st_p, out_p = br.route_batch(params, state0, reqs)
        st_s, out_s = mr.route_batch_sharded(params, state0, reqs,
                                             num_devices=4)
        np.testing.assert_array_equal(np.asarray(out_p.choice),
                                      np.asarray(out_s.choice))
        np.testing.assert_allclose(np.asarray(out_p.latency),
                                   np.asarray(out_s.latency),
                                   rtol=1e-12, atol=0.0)
        np.testing.assert_array_equal(np.asarray(st_p.resident),
                                      np.asarray(st_s.resident))
        np.testing.assert_allclose(np.asarray(st_p.queue_tokens),
                                   np.asarray(st_s.queue_tokens),
                                   rtol=1e-2, atol=1e-6)


@pytest.mark.multidevice
def test_sharded_chunked_and_speculative_paths_agree():
    """Inside each cell shard the scan/chunked/speculative inner paths
    stay interchangeable on 4 devices: identical decisions, residency
    and LRU clocks; latencies/queues to ulps (the chunked commit
    re-associates the eq. 9 sums exactly like the unsharded chunked
    path — see test_chunked_multicell_matches_scalar_oracle). The two
    chunked variants (speculative on/off) ARE bitwise twins."""
    rng = np.random.default_rng(700)
    fleet = _random_multicell_fleet(rng, 4, 3, drain_hi=0.0, cloud=False)
    models, bits, toks, cells, arrivals = _random_stream(rng, 200, 4)
    params, state0 = br.fleet_from_servers(fleet, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        cell=jnp.asarray(cells, jnp.int32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    st_a, out_a = mr.route_batch_sharded(params, state0, reqs, num_devices=4)
    st_b, out_b = mr.route_batch_sharded(params, state0, reqs, num_devices=4,
                                         chunk=32, speculative=True)
    st_c, out_c = mr.route_batch_sharded(params, state0, reqs, num_devices=4,
                                         chunk=32, speculative=False)
    for st, out in ((st_b, out_b), (st_c, out_c)):
        np.testing.assert_array_equal(np.asarray(out_a.choice),
                                      np.asarray(out.choice))
        np.testing.assert_array_equal(np.asarray(out_a.hit),
                                      np.asarray(out.hit))
        np.testing.assert_allclose(np.asarray(out_a.latency),
                                   np.asarray(out.latency), rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(st_a.resident),
                                      np.asarray(st.resident))
        res = np.asarray(st_a.resident)
        np.testing.assert_array_equal(np.asarray(st_a.last_use)[res],
                                      np.asarray(st.last_use)[res])
        np.testing.assert_allclose(np.asarray(st_a.queue_tokens),
                                   np.asarray(st.queue_tokens), rtol=1e-5)
    _sharded_outcome_equal(out_b, out_c)
    _sharded_state_equal(st_b, st_c)


@pytest.mark.multidevice
def test_chip_smoke_four_chip_path_on_host_devices(monkeypatch, capsys):
    """``chip_smoke.py --chips 4`` rehearsed tiny on host devices: the
    sharded metro window is device-count invariant, the cloud-free
    variant equals plain ``route_batch``, and the D=4 outputs live on
    four devices."""
    from test_chip_smoke import rehearse

    rehearse(monkeypatch).main(["--chips", "4"])
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1])["ok"] is True
    assert "  device-count invariance, D=4 vs D=1: bitwise equal" in out
    assert "  cloud-free, D=4 vs plain route_batch: bitwise equal" in out
