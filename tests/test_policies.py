"""Serving-policy subsystem (core/policies.py + the drain-aware greedy).

Pins the three contracts the trained-actor serving path rests on:

* the observation bridge reproduces ``core.env.observe``'s eq. 16 layout
  field for field (C in {1, 2} cell topologies);
* an actor checkpoint round-trips through ``checkpoint.checkpointer``
  and routes batches deterministically, with the scalar oracle
  reproducing the stream bit for bit given the same action sequence;
* the drain-aware greedy matches its scalar-oracle twin on both scan
  paths, degenerates to plain greedy without drain, and beats plain
  greedy on a bursty-arrival fixture.
"""
import copy

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from x64 import enable_x64
from repro.core import batch_router as br
from repro.core import env as env_lib, maddpg, policies
from repro.core.catalog import build_catalog, env_params_from_catalog
from repro.core.router import CLOUD_CELL, EdgeServer, ModelAwareRouter, Request

CATALOG = build_catalog(
    ["smollm_135m", "starcoder2_3b", "mamba2_2p7b", "musicgen_medium"]
)


# ---------------------------------------------------------------------------
# observation bridge vs core.env
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cells", [1, 2])
def test_obs_dim_matches_env(cells):
    p = env_lib.default_params(num_eds=5, num_models=3, num_ess=4,
                               num_cells=cells)
    assert policies.obs_dim(policies.spec_from_env(p)) == env_lib.obs_dim(p)


@pytest.mark.parametrize("cells", [1, 2])
def test_build_obs_matches_env_observe(cells):
    """The builder reproduces every agent's eq. 16 row exactly, given the
    env's own state — including the cell-masked compat columns."""
    p = env_lib.default_params(num_eds=4, num_models=3, num_ess=4,
                               num_cells=cells)
    state = env_lib.reset(jax.random.key(3), p)
    want = np.asarray(env_lib.observe(state, p))
    spec = policies.spec_from_env(p)
    es_cell = np.asarray(env_lib.es_cell(p))
    ed_cell = np.asarray(env_lib.ed_cell(p))
    for m in range(p.num_eds):
        mu = int(state.task.mu[m])
        compat = np.asarray(state.cache)[:, mu] * (es_cell == ed_cell[m])
        got = policies.build_obs(
            spec,
            model=jnp.int32(mu),
            x_bits=state.task.x_bits[m],
            rho=state.task.rho[m],
            f_es=jnp.full((p.num_ess,), p.f_es),
            compat=jnp.asarray(compat, jnp.float32),
            ed_pos=state.ed_pos[m],
            es_pos=state.es_pos,
            cc_pos=state.cc_pos,
            f_ed=state.f_ed[m],
        )
        np.testing.assert_allclose(np.asarray(got), want[m], rtol=1e-6,
                                   err_msg=f"agent {m}")


def test_cell_index_map_single_cell_trained():
    """num_cells=1 actor on a C-cell fleet: row c gathers cell c's
    servers; the cloud column is never offered."""
    spec = policies.spec_from_env(
        env_lib.default_params(num_eds=2, num_models=4, num_ess=3)
    )
    fleet_cell = np.array([0, 0, 0, 1, 1, 1, CLOUD_CELL], np.int32)
    rows, col_cell = policies.cell_index_map(spec, fleet_cell)
    np.testing.assert_array_equal(rows, [[0, 1, 2], [3, 4, 5]])
    np.testing.assert_array_equal(col_cell, [[0, 0, 0], [1, 1, 1]])


def test_cell_index_map_matched_topology():
    """num_cells=C actor on the matching fleet: every row is the full
    edge fleet (compat is cell-masked downstream, as in training)."""
    p = env_lib.default_params(num_eds=4, num_models=3, num_ess=4,
                               num_cells=2)
    spec = policies.spec_from_env(p)
    fleet_cell = np.asarray(env_lib.es_cell(p))  # round-robin 0,1,0,1
    rows, col_cell = policies.cell_index_map(spec, fleet_cell)
    np.testing.assert_array_equal(rows, [[0, 1, 2, 3]] * 2)
    np.testing.assert_array_equal(col_cell, [fleet_cell] * 2)
    # env-style compat mask falls out of col_cell == request cell
    np.testing.assert_array_equal(col_cell[0] == 0, [True, False] * 2)
    np.testing.assert_array_equal(col_cell[1] == 1, [False, True] * 2)


def test_cell_index_map_rejects_mismatched_geometry():
    spec = policies.spec_from_env(
        env_lib.default_params(num_eds=2, num_models=4, num_ess=3)
    )
    with pytest.raises(ValueError, match="2 edge servers"):
        policies.cell_index_map(spec, np.array([0, 0, 1, 1], np.int32))
    with pytest.raises(ValueError, match="cannot map"):
        policies.cell_index_map(
            spec._replace(num_cells=3), np.array([0, 0, 1, 1], np.int32)
        )


# ---------------------------------------------------------------------------
# actor checkpoint round-trip through the batched router
# ---------------------------------------------------------------------------
def _multicell_fleet(n_cells, per_cell, drain_rate=0.0):
    fleet = [
        EdgeServer(
            name=f"c{c}-es{i}", flops_per_s=197e12, cache_slots=2,
            uplink_bps=1e8, backhaul_bps=1e9,
            resident=[(2 * i + j) % len(CATALOG) for j in range(2)],
            cell=c, drain_rate=drain_rate,
        )
        for c in range(n_cells)
        for i in range(per_cell)
    ]
    fleet.append(EdgeServer(
        name="cloud", flops_per_s=2e15, cache_slots=len(CATALOG),
        uplink_bps=5e7, backhaul_bps=1e9,
        resident=list(range(len(CATALOG))), cell=CLOUD_CELL,
    ))
    return fleet


def test_actor_checkpoint_roundtrip_routes_deterministically(tmp_path):
    """save -> restore -> route: parameters survive bit-exactly, routing
    is deterministic, and the scalar oracle replaying the SAME action
    sequence reproduces latencies and fleet state bit for bit."""
    with enable_x64():
        p = env_params_from_catalog(CATALOG, num_eds=4, num_ess=3)
        cfg = maddpg.AlgoConfig(hidden=32)
        ts = maddpg.init_state(jax.random.key(0), p, cfg)
        policies.save_actor_checkpoint(tmp_path, ts.actor, p, cfg)

        restored, spec, extra = policies.load_actor_checkpoint(tmp_path)
        for a, b in zip(jax.tree.leaves(ts.actor), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert spec == policies.spec_from_env(p)
        assert extra["model_aware"] is True

        fleet = _multicell_fleet(2, 3)
        params, state = br.fleet_from_servers(fleet, CATALOG)
        policy = policies.load_actor_policy(tmp_path, params)

        rng = np.random.default_rng(5)
        n = 150
        reqs = br.RequestBatch(
            model=jnp.asarray(rng.integers(0, len(CATALOG), n), jnp.int32),
            prompt_bits=jnp.asarray(rng.uniform(1e5, 1e6, n), jnp.float64),
            gen_tokens=jnp.asarray(rng.integers(1, 64, n), jnp.float64),
            cell=jnp.asarray(rng.integers(0, 2, n), jnp.int32),
        )
        _, out1 = br.route_batch(params, state, reqs, policy=policy)
        state2, out2 = br.route_batch(params, state, reqs, policy=policy)
        np.testing.assert_array_equal(np.asarray(out1.choice),
                                      np.asarray(out2.choice))
        np.testing.assert_array_equal(np.asarray(out1.latency),
                                      np.asarray(out2.latency))

        # the actor only ever places requests on in-cell edge servers
        srv_cell = np.array([s.cell for s in fleet])
        choices = np.asarray(out2.choice)
        np.testing.assert_array_equal(srv_cell[choices],
                                      np.asarray(reqs.cell))

        # scalar oracle, same action sequence -> same latencies/state
        script = iter(choices.tolist())
        router = ModelAwareRouter(copy.deepcopy(fleet), CATALOG,
                                  policy="actor",
                                  actor=lambda obs, lats: next(script))
        sc = [router.route(Request(int(m), float(b), int(t), cell=int(c)))
              for m, b, t, c in zip(np.asarray(reqs.model),
                                    np.asarray(reqs.prompt_bits),
                                    np.asarray(reqs.gen_tokens),
                                    np.asarray(reqs.cell))]
        np.testing.assert_array_equal(choices, [c for c, _ in sc])
        np.testing.assert_allclose(np.asarray(out2.latency),
                                   [l for _, l in sc], rtol=1e-12, atol=0.0)
        resident = np.asarray(state2.resident)
        for i, srv in enumerate(router.servers):
            assert set(np.nonzero(resident[i])[0]) == set(srv.resident), i
        np.testing.assert_allclose(np.asarray(state2.queue_tokens),
                                   [s.queue_tokens for s in router.servers],
                                   rtol=1e-12)


def test_actor_policy_chunked_matches_scan(tmp_path):
    """The ctx-threaded chunked path reproduces the single-scan actor
    decisions (the PolicyCtx plumbing is path-invariant)."""
    p = env_params_from_catalog(CATALOG, num_eds=4, num_ess=3)
    cfg = maddpg.AlgoConfig(hidden=32)
    ts = maddpg.init_state(jax.random.key(1), p, cfg)
    policies.save_actor_checkpoint(tmp_path, ts.actor, p, cfg)

    fleet = _multicell_fleet(2, 3, drain_rate=1e4)
    params, state = br.fleet_from_servers(fleet, CATALOG)
    policy = policies.load_actor_policy(tmp_path, params)

    rng = np.random.default_rng(6)
    n = 130
    reqs = br.RequestBatch(
        model=jnp.asarray(rng.integers(0, len(CATALOG), n), jnp.int32),
        prompt_bits=jnp.asarray(rng.uniform(1e5, 1e6, n), jnp.float32),
        gen_tokens=jnp.asarray(rng.integers(1, 64, n), jnp.float32),
        cell=jnp.asarray(rng.integers(0, 2, n), jnp.int32),
        arrival_s=jnp.asarray(np.cumsum(rng.exponential(0.01, n)),
                              jnp.float32),
    )
    s0, o0 = br.route_batch(params, state, reqs, policy=policy)
    s1, o1 = br.route_batch(params, state, reqs, policy=policy, chunk=32)
    np.testing.assert_array_equal(np.asarray(o0.choice),
                                  np.asarray(o1.choice))
    np.testing.assert_array_equal(np.asarray(s0.resident),
                                  np.asarray(s1.resident))
    np.testing.assert_allclose(np.asarray(s0.queue_tokens),
                               np.asarray(s1.queue_tokens), rtol=1e-6)


# ---------------------------------------------------------------------------
# drain-aware greedy
# ---------------------------------------------------------------------------
def _random_drain_fleet(rng, n_servers):
    return [
        EdgeServer(
            name=f"es{i}",
            flops_per_s=float(rng.uniform(5e13, 2e14)),
            cache_slots=2,
            uplink_bps=float(rng.uniform(5e7, 2e8)),
            backhaul_bps=float(rng.uniform(5e8, 2e9)),
            resident=list(rng.choice(len(CATALOG), size=2, replace=False)),
            drain_rate=float(rng.uniform(0.0, 1e5)),
        )
        for i in range(n_servers)
    ]


@pytest.mark.parametrize("chunk", [None, 64])
def test_drain_policy_matches_scalar_oracle(chunk):
    """policy='drain' on both batched paths == the scalar oracle's drain
    policy, over random drain rates and Poisson-ish arrivals."""
    rng = np.random.default_rng(17)
    servers = _random_drain_fleet(rng, 5)
    n = 200
    models = rng.integers(0, len(CATALOG), n)
    bits = rng.uniform(1e5, 1e6, n)
    toks = rng.integers(1, 64, n)
    arrivals = np.cumsum(rng.exponential(0.01, n))

    router = ModelAwareRouter(copy.deepcopy(servers), CATALOG,
                              policy="drain")
    sc_choice = [
        router.route(Request(int(m), float(b), int(t),
                             arrival_s=float(a)))[0]
        for m, b, t, a in zip(models, bits, toks, arrivals)
    ]
    params, state = br.fleet_from_servers(servers, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    state, out = br.route_batch(params, state, reqs, policy="drain",
                                chunk=chunk)
    np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
    resident = np.asarray(state.resident)
    for i, srv in enumerate(router.servers):
        assert set(np.nonzero(resident[i])[0]) == set(srv.resident), i
    np.testing.assert_allclose(np.asarray(state.queue_tokens),
                               [s.queue_tokens for s in router.servers],
                               rtol=1e-5)


def test_drain_degenerates_to_greedy_without_drain():
    """drain_rate == 0 everywhere: the discounted score equals eq. 11 and
    the two policies route identically."""
    rng = np.random.default_rng(23)
    servers = _random_drain_fleet(rng, 4)
    for s in servers:
        s.drain_rate = 0.0
    n = 150
    reqs = br.RequestBatch(
        model=jnp.asarray(rng.integers(0, len(CATALOG), n), jnp.int32),
        prompt_bits=jnp.asarray(rng.uniform(1e5, 1e6, n), jnp.float32),
        gen_tokens=jnp.asarray(rng.integers(1, 64, n), jnp.float32),
    )
    params, state = br.fleet_from_servers(servers, CATALOG)
    _, o_greedy = br.route_batch(params, state, reqs, policy="greedy")
    _, o_drain = br.route_batch(params, state, reqs, policy="drain")
    np.testing.assert_array_equal(np.asarray(o_greedy.choice),
                                  np.asarray(o_drain.choice))


def _bursty_fixture():
    """Hand-built burst pattern where drain awareness pays: server A is
    fast and drains its in-burst backlog away (its ``drain_rate`` is a
    multiple of its own decode throughput); server B is 10x slower and
    never drains. Greedy prices A's transient backlog at face value and
    spills onto B mid-burst, paying B's slow service; the drain-aware
    policy knows A's backlog melts and keeps the burst on A."""
    model = 1  # starcoder2_3b: ftok ~6e9 -> A's throughput ~3e4 tok/s
    servers = [
        EdgeServer(name="A", flops_per_s=2e14, cache_slots=2,
                   uplink_bps=1e8, backhaul_bps=1e9, resident=[model],
                   drain_rate=5e5),
        EdgeServer(name="B", flops_per_s=2e13, cache_slots=2,
                   uplink_bps=1e8, backhaul_bps=1e9, resident=[model],
                   drain_rate=0.0),
    ]
    n_bursts, per_burst = 4, 80
    n = n_bursts * per_burst
    arrivals = np.repeat(np.arange(n_bursts) * 1.0, per_burst)
    reqs = br.RequestBatch(
        model=jnp.full((n,), model, jnp.int32),
        prompt_bits=jnp.full((n,), 1e5, jnp.float32),
        gen_tokens=jnp.full((n,), 500.0, jnp.float32),
        arrival_s=jnp.asarray(arrivals, jnp.float32),
    )
    return servers, reqs


def _requests_list(reqs):
    return [
        Request(int(m), float(b), int(t), arrival_s=float(a))
        for m, b, t, a in zip(np.asarray(reqs.model),
                              np.asarray(reqs.prompt_bits),
                              np.asarray(reqs.gen_tokens),
                              np.asarray(reqs.arrival_s))
    ]


def test_drain_beats_greedy_on_bursty_fixture():
    """Compared on the drain-corrected realized latency (the model-
    consistent metric — raw eq. 11 is greedy's own objective and prices
    the draining backlog with a known bias, see
    ``policies.drain_corrected_latencies``)."""
    servers, reqs = _bursty_fixture()
    params, state = br.fleet_from_servers(servers, CATALOG)
    _, o_greedy = br.route_batch(params, state, reqs, policy="greedy")
    _, o_drain = br.route_batch(params, state, reqs, policy="drain")
    # the policies genuinely diverge: greedy spills part of each burst
    # onto the slow no-drain server
    g_choice = np.asarray(o_greedy.choice)
    d_choice = np.asarray(o_drain.choice)
    assert (g_choice != d_choice).any()
    assert (g_choice == 1).sum() > (d_choice == 1).sum()

    requests = _requests_list(reqs)
    lat_greedy = np.mean(policies.drain_corrected_latencies(
        servers, CATALOG, requests, g_choice))
    lat_drain = np.mean(policies.drain_corrected_latencies(
        servers, CATALOG, requests, d_choice))
    # structural margin (greedy keeps paying B's slow undrained service),
    # not a tie-break accident
    assert lat_drain < 0.9 * lat_greedy, (lat_drain, lat_greedy)


# ---------------------------------------------------------------------------
# chunk-level actor hook: batched table scoring + drift replay
# ---------------------------------------------------------------------------
def _hooked_actor(tmp_path, seed=2):
    p = env_params_from_catalog(CATALOG, num_eds=4, num_ess=3)
    cfg = maddpg.AlgoConfig(hidden=32)
    ts = maddpg.init_state(jax.random.key(seed), p, cfg)
    policies.save_actor_checkpoint(tmp_path, ts.actor, p, cfg)
    fleet = _multicell_fleet(2, 3, drain_rate=1e4)
    params, state = br.fleet_from_servers(fleet, CATALOG)
    return policies.load_actor_policy(tmp_path, params), params, state


def test_chunk_hook_radius1_table_and_drift_flag(tmp_path):
    """The hook contract, pinned at the unit level: chunk_precompute
    prices the chunk-entry compat row plus every single-bit flip, and
    chunk_apply resolves the LIVE row against that table — exact for
    Hamming distance <= 1, flagged inexact (whole-chunk replay) for
    multi-bit drift."""
    policy, params, state = _hooked_actor(tmp_path)
    assert policy.needs_ctx and hasattr(policy, "chunk_precompute")
    m = 1
    scalars = dict(
        model=jnp.asarray([m], jnp.int32),
        prompt_bits=jnp.asarray([2e5], jnp.float32),
        gen_tokens=jnp.asarray([16.0], jnp.float32),
        flops_tok=params.decode_flops_per_token[jnp.asarray([m])],
    )
    cctx = br.ChunkPolicyCtx(params=params, resident=state.resident,
                             cell=jnp.asarray([0], jnp.int32), **scalars)
    aux = policy.chunk_precompute(cctx)
    aux_b = jax.tree.map(lambda a: a[0], aux)
    ctx = br.PolicyCtx(
        params=params, model=jnp.int32(m),
        prompt_bits=jnp.float32(2e5), gen_tokens=jnp.float32(16.0),
        flops_tok=params.decode_flops_per_token[m],
        resident=state.resident[:, m], queue=state.queue_tokens,
        cell=jnp.int32(0),
    )
    # no drift: table hit, same decision as the per-request path
    choice0, exact0 = policy.chunk_apply(aux_b, ctx)
    assert bool(exact0)
    assert int(choice0) == int(policy(None, None, None, ctx))
    # single-bit drift on an IN-CELL server: still a table hit
    flip1 = ctx._replace(resident=ctx.resident.at[0].set(~ctx.resident[0]))
    choice1, exact1 = policy.chunk_apply(aux_b, flip1)
    assert bool(exact1)
    assert int(choice1) == int(policy(None, None, None, flip1))
    # drift on an OUT-OF-CELL server is invisible through the cell mask
    flip_oc = ctx._replace(resident=ctx.resident.at[4].set(
        ~ctx.resident[4]))
    choice_oc, exact_oc = policy.chunk_apply(aux_b, flip_oc)
    assert bool(exact_oc)
    assert int(choice_oc) == int(choice0)
    # two-bit drift: outside the radius-1 table -> inexact, replay
    flip2 = flip1._replace(resident=flip1.resident.at[1].set(
        ~flip1.resident[1]))
    _, exact2 = policy.chunk_apply(aux_b, flip2)
    assert not bool(exact2)


def test_chunk_hook_forced_replay_matches_scan(tmp_path):
    """The router's whole-chunk replay path: a hook whose chunk_apply
    always reports inexact forces EVERY chunk through the serial
    per-request fallback — the stream must still match the unchunked
    scan decision for decision, state for state."""
    base, params, state = _hooked_actor(tmp_path, seed=3)

    def forced(lats, obs, queue, ctx):
        return base(lats, obs, queue, ctx)

    forced.needs_obs = False
    forced.needs_ctx = True
    forced.chunk_precompute = base.chunk_precompute
    forced.chunk_apply = lambda aux_b, ctx: (base.chunk_apply(aux_b, ctx)[0],
                                             jnp.bool_(False))

    rng = np.random.default_rng(8)
    n = 130
    reqs = br.RequestBatch(
        model=jnp.asarray(rng.integers(0, len(CATALOG), n), jnp.int32),
        prompt_bits=jnp.asarray(rng.uniform(1e5, 1e6, n), jnp.float32),
        gen_tokens=jnp.asarray(rng.integers(1, 64, n), jnp.float32),
        cell=jnp.asarray(rng.integers(0, 2, n), jnp.int32),
        arrival_s=jnp.asarray(np.cumsum(rng.exponential(0.01, n)),
                              jnp.float32),
    )
    s0, o0 = br.route_batch(params, state, reqs, policy=base)
    s1, o1 = br.route_batch(params, state, reqs, policy=forced, chunk=32)
    np.testing.assert_array_equal(np.asarray(o0.choice),
                                  np.asarray(o1.choice))
    np.testing.assert_array_equal(np.asarray(o0.hit), np.asarray(o1.hit))
    resident = np.asarray(s0.resident)
    np.testing.assert_array_equal(resident, np.asarray(s1.resident))
    # non-resident clocks are dead state (the two paths park them
    # differently); the LIVE clocks must agree exactly
    np.testing.assert_array_equal(
        np.where(resident, np.asarray(s0.last_use), 0),
        np.where(resident, np.asarray(s1.last_use), 0))
    np.testing.assert_allclose(np.asarray(s0.queue_tokens),
                               np.asarray(s1.queue_tokens), rtol=1e-6)
