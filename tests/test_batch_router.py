"""Batched router vs the scalar ModelAwareRouter oracle — exact equivalence.

The batched ``lax.scan`` path must reproduce the scalar reference request
for request: same choices, same predicted latencies, same residency sets,
same LRU evictions, same queues — over randomised request streams, fleet
shapes and cache sizes. Integer decisions are compared exactly; latencies
under x64 to within a couple of ulps (XLA emits FMAs the Python oracle
cannot). The float32 fast path must still agree on every integer decision.
"""
import copy

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from x64 import enable_x64
from repro.core import batch_router as br
from repro.core.catalog import build_catalog
from repro.core.router import EdgeServer, ModelAwareRouter, Request

CATALOG = build_catalog(
    ["smollm_135m", "starcoder2_3b", "mamba2_2p7b", "musicgen_medium"]
)


def _random_fleet(rng, n_servers, cache_slots):
    return [
        EdgeServer(
            name=f"es{i}",
            flops_per_s=float(rng.uniform(5e13, 2e14)),
            cache_slots=cache_slots,
            uplink_bps=float(rng.uniform(5e7, 2e8)),
            backhaul_bps=float(rng.uniform(5e8, 2e9)),
            resident=list(
                rng.choice(len(CATALOG), size=cache_slots, replace=False)
            ),
        )
        for i in range(n_servers)
    ]


def _random_stream(rng, n_requests):
    return (
        rng.integers(0, len(CATALOG), n_requests),
        rng.uniform(1e5, 1e6, n_requests),
        rng.integers(1, 64, n_requests),
    )


def _run_scalar(servers, models, bits, toks, drain, policy="greedy",
                actor=None):
    router = ModelAwareRouter(copy.deepcopy(servers), CATALOG,
                              policy=policy, actor=actor)
    choices, lats, hits = [], [], []
    for m, b, t in zip(models, bits, toks):
        srv_resident = [int(m) in s.resident for s in router.servers]
        c, l = router.route(Request(int(m), float(b), int(t)))
        choices.append(c)
        lats.append(l)
        hits.append(srv_resident[c])
        router.drain(drain)
    return router, np.array(choices), np.array(lats), np.array(hits)


def _run_batched(servers, models, bits, toks, drain, dtype, policy="greedy",
                 actor=None):
    params, state = br.fleet_from_servers(servers, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, dtype),
        gen_tokens=jnp.asarray(toks, dtype),
    )
    return br.route_batch(params, state, reqs, drain, policy=policy,
                          actor=actor)


def _assert_fleet_state_matches(router, state):
    resident = np.asarray(state.resident)
    last_use = np.asarray(state.last_use)
    for i, srv in enumerate(router.servers):
        assert set(np.nonzero(resident[i])[0]) == set(srv.resident), i
        for m in srv.resident:
            if m in srv.last_use:  # touched models carry the exact clock
                assert last_use[i, m] == srv.last_use[m], (i, m)
    np.testing.assert_allclose(
        np.asarray(state.queue_tokens),
        np.array([s.queue_tokens for s in router.servers]),
        rtol=1e-6,
    )


@pytest.mark.parametrize("seed,n_servers,cache_slots", [
    (0, 2, 1), (1, 3, 2), (2, 5, 2), (3, 8, 3), (4, 4, 1), (5, 6, 4),
])
def test_batched_matches_scalar_oracle_exactly(seed, n_servers, cache_slots):
    """x64: choices, latencies, residency, LRU clocks and queues all equal."""
    with enable_x64():
        rng = np.random.default_rng(seed)
        servers = _random_fleet(rng, n_servers, cache_slots)
        models, bits, toks = _random_stream(rng, 300)
        drain = float(rng.uniform(0.0, 50.0))

        router, sc_choice, sc_lat, sc_hit = _run_scalar(
            servers, models, bits, toks, drain
        )
        state, out = _run_batched(
            servers, models, bits, toks, drain, jnp.float64
        )

        np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
        # XLA fuses mul+add into an FMA the Python oracle can't express;
        # latencies agree to the last couple of ulps, decisions exactly.
        np.testing.assert_allclose(np.asarray(out.latency), sc_lat,
                                   rtol=1e-12, atol=0.0)
        np.testing.assert_array_equal(np.asarray(out.hit), sc_hit)
        _assert_fleet_state_matches(router, state)


@pytest.mark.parametrize("seed", [10, 11, 12])
def test_float32_fast_path_same_decisions(seed):
    """The f32 serving path must agree on every choice/eviction (decisions
    are integer-valued; f32 rounding never flips a non-degenerate argmin)."""
    rng = np.random.default_rng(seed)
    servers = _random_fleet(rng, 4, 2)
    models, bits, toks = _random_stream(rng, 400)

    router, sc_choice, _, sc_hit = _run_scalar(servers, models, bits, toks, 5.0)
    state, out = _run_batched(servers, models, bits, toks, 5.0, jnp.float32)

    np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
    np.testing.assert_array_equal(np.asarray(out.hit), sc_hit)
    resident = np.asarray(state.resident)
    for i, srv in enumerate(router.servers):
        assert set(np.nonzero(resident[i])[0]) == set(srv.resident), i


def test_actor_policy_matches_scalar_actor():
    """A (traceable) actor drives both routers to identical streams."""

    def actor(obs, lats):
        # busiest-server actor: pathological but deterministic in both paths
        queue = jnp.reshape(jnp.asarray(obs), (-1, 3))[:, 1]
        return jnp.argmax(queue)

    rng = np.random.default_rng(7)
    servers = _random_fleet(rng, 5, 2)
    models, bits, toks = _random_stream(rng, 120)

    router, sc_choice, _, _ = _run_scalar(
        servers, models, bits, toks, 0.0, policy="actor", actor=actor
    )
    state, out = _run_batched(
        servers, models, bits, toks, 0.0, jnp.float32, policy="actor",
        actor=actor,
    )
    np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
    _assert_fleet_state_matches(router, state)


def test_load_policy_balances_queues():
    rng = np.random.default_rng(8)
    servers = _random_fleet(rng, 4, 2)
    models, bits, toks = _random_stream(rng, 200)
    state, out = _run_batched(
        servers, models, bits, toks, 0.0, jnp.float32, policy="load"
    )
    counts = np.bincount(np.asarray(out.choice), minlength=4)
    # least-loaded dispatch spreads work across every server
    assert counts.min() > 0
    queues = np.asarray(state.queue_tokens)
    assert queues.max() < 2.0 * queues.min() + float(np.max(toks))


def test_score_matrix_matches_candidate_latency():
    """One-shot (B, N) scoring == the oracle's per-candidate pricing."""
    with enable_x64():
        rng = np.random.default_rng(9)
        servers = _random_fleet(rng, 6, 2)
        models, bits, toks = _random_stream(rng, 50)
        router = ModelAwareRouter(copy.deepcopy(servers), CATALOG)
        expected = np.array([
            [router._candidate_latency(s, Request(int(m), float(b), int(t)))
             for s in router.servers]
            for m, b, t in zip(models, bits, toks)
        ])
        params, state = br.fleet_from_servers(servers, CATALOG)
        reqs = br.RequestBatch(
            model=jnp.asarray(models, jnp.int32),
            prompt_bits=jnp.asarray(bits, jnp.float64),
            gen_tokens=jnp.asarray(toks, jnp.float64),
        )
        got = np.asarray(br.score_matrix(params, state, reqs))
        np.testing.assert_allclose(got, expected, rtol=1e-12, atol=0.0)


def test_per_request_drain_vector():
    """A (B,) drain schedule matches per-request scalar drains."""
    with enable_x64():
        rng = np.random.default_rng(13)
        servers = _random_fleet(rng, 3, 2)
        models, bits, toks = _random_stream(rng, 80)
        drains = rng.uniform(0.0, 30.0, 80)

        router = ModelAwareRouter(copy.deepcopy(servers), CATALOG)
        sc_choice = []
        for m, b, t, d in zip(models, bits, toks, drains):
            c, _ = router.route(Request(int(m), float(b), int(t)))
            sc_choice.append(c)
            router.drain(float(d))

        params, state = br.fleet_from_servers(servers, CATALOG)
        reqs = br.RequestBatch(
            model=jnp.asarray(models, jnp.int32),
            prompt_bits=jnp.asarray(bits, jnp.float64),
            gen_tokens=jnp.asarray(toks, jnp.float64),
        )
        state, out = br.route_batch(params, state, reqs, jnp.asarray(drains))
        np.testing.assert_array_equal(np.asarray(out.choice),
                                      np.array(sc_choice))
        _assert_fleet_state_matches(router, state)


def test_midstream_snapshot_continues_oracle():
    """Snapshotting a scalar router mid-stream (warm last_use clocks) and
    continuing batched must keep matching — requires threading the oracle's
    clock, or the new batch's clocks would sort BELOW existing residents'."""
    with enable_x64():
        rng = np.random.default_rng(21)
        servers = _random_fleet(rng, 4, 2)
        models, bits, toks = _random_stream(rng, 240)

        router = ModelAwareRouter(copy.deepcopy(servers), CATALOG)
        sc_choice = []
        for m, b, t in zip(models, bits, toks):
            c, _ = router.route(Request(int(m), float(b), int(t)))
            sc_choice.append(c)

        half = 120
        warm = ModelAwareRouter(copy.deepcopy(servers), CATALOG)
        for m, b, t in zip(models[:half], bits[:half], toks[:half]):
            warm.route(Request(int(m), float(b), int(t)))
        params, state = br.fleet_from_servers(warm.servers, CATALOG,
                                              clock=warm.clock)
        reqs = br.RequestBatch(
            model=jnp.asarray(models[half:], jnp.int32),
            prompt_bits=jnp.asarray(bits[half:], jnp.float64),
            gen_tokens=jnp.asarray(toks[half:], jnp.float64),
        )
        state, out = br.route_batch(params, state, reqs)
        np.testing.assert_array_equal(np.asarray(out.choice),
                                      np.array(sc_choice[half:]))
        _assert_fleet_state_matches(router, state)


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("chunk", [64, 100])
def test_chunked_matches_scalar_oracle(chunk, backend):
    """The two-phase chunked commit (incl. a chunk that does NOT divide
    B, exercising the inert padding tail) reproduces the oracle's
    choices, hits, residency, LRU clocks and queues under BOTH scoring
    backends; latencies agree to a few ulps (the chunked path
    re-associates eq. 9, see batch_router docstring)."""
    with enable_x64():
        rng = np.random.default_rng(31)
        servers = _random_fleet(rng, 5, 2)
        models, bits, toks = _random_stream(rng, 300)
        drain = float(rng.uniform(0.0, 50.0))

        router, sc_choice, sc_lat, sc_hit = _run_scalar(
            servers, models, bits, toks, drain
        )
        params, state = br.fleet_from_servers(servers, CATALOG)
        reqs = br.RequestBatch(
            model=jnp.asarray(models, jnp.int32),
            prompt_bits=jnp.asarray(bits, jnp.float64),
            gen_tokens=jnp.asarray(toks, jnp.float64),
        )
        state, out = br.route_batch(params, state, reqs, drain, chunk=chunk,
                                    backend=backend)
        np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
        np.testing.assert_array_equal(np.asarray(out.hit), sc_hit)
        np.testing.assert_allclose(np.asarray(out.latency), sc_lat,
                                   rtol=1e-12, atol=0.0)
        _assert_fleet_state_matches(router, state)


def test_chunked_matches_legacy_scan_all_policies():
    """chunk=c and chunk=None agree decision-for-decision per policy."""

    def busiest_actor(obs, lats):
        queue = jnp.reshape(jnp.asarray(obs), (-1, 3))[:, 1]
        return jnp.argmax(queue)

    rng = np.random.default_rng(33)
    servers = _random_fleet(rng, 6, 2)
    models, bits, toks = _random_stream(rng, 250)
    params, state = br.fleet_from_servers(servers, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
    )
    for policy, actor in [("greedy", None), ("load", None),
                          ("actor", busiest_actor)]:
        s0, o0 = br.route_batch(params, state, reqs, 3.0, policy=policy,
                                actor=actor)
        s1, o1 = br.route_batch(params, state, reqs, 3.0, policy=policy,
                                actor=actor, chunk=64)
        np.testing.assert_array_equal(np.asarray(o0.choice),
                                      np.asarray(o1.choice), err_msg=policy)
        np.testing.assert_array_equal(np.asarray(s0.resident),
                                      np.asarray(s1.resident), err_msg=policy)
        np.testing.assert_allclose(np.asarray(s0.queue_tokens),
                                   np.asarray(s1.queue_tokens), rtol=1e-6)


def test_stats_masks_rejected_requests():
    """Rejected requests must not poison mean_latency OR deflate the
    hit rate; completion_rate reports them (the paper's third headline
    metric). Rejected requests are forced hit=False by the router, so
    residency_hit_rate averages over COMPLETED requests only."""
    out = br.RouteOutcome(
        choice=jnp.asarray([0, -1, 2, -1], jnp.int32),
        latency=jnp.asarray([1.0, jnp.inf, 3.0, jnp.inf], jnp.float32),
        hit=jnp.asarray([True, False, False, False]),
    )
    got = br.stats(out)
    assert got["mean_latency"] == pytest.approx(2.0)
    assert got["completion_rate"] == pytest.approx(0.5)
    assert got["residency_hit_rate"] == pytest.approx(0.5)  # 1 of 2 done

    none = br.stats(out._replace(
        choice=jnp.full((4,), -1, jnp.int32),
        latency=jnp.full((4,), jnp.inf, jnp.float32),
    ))
    assert none["completion_rate"] == 0.0
    assert np.isinf(none["mean_latency"])  # no finite sample to average
    assert np.isnan(none["residency_hit_rate"])  # nothing completed


def test_route_batch_unroll_is_a_knob():
    """unroll only changes the compiled schedule, never a decision."""
    rng = np.random.default_rng(35)
    servers = _random_fleet(rng, 4, 2)
    models, bits, toks = _random_stream(rng, 120)
    params, state = br.fleet_from_servers(servers, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
    )
    ref_state, ref_out = br.route_batch(params, state, reqs)
    for unroll in (1, 4, 32):
        s, o = br.route_batch(params, state, reqs, unroll=unroll)
        np.testing.assert_array_equal(np.asarray(o.choice),
                                      np.asarray(ref_out.choice))
        np.testing.assert_array_equal(np.asarray(s.last_use),
                                      np.asarray(ref_state.last_use))


@pytest.mark.slow
def test_fleet_scale_single_call():
    """Acceptance shape: B=4096 requests over N=64 servers, one jitted call,
    still bit-identical to the scalar oracle on choices and residency —
    on both the single-scan path and the chunked two-phase commit."""
    rng = np.random.default_rng(42)
    servers = _random_fleet(rng, 64, 2)
    models, bits, toks = _random_stream(rng, 4096)

    router, sc_choice, _, _ = _run_scalar(servers, models, bits, toks, 0.0)
    state, out = _run_batched(servers, models, bits, toks, 0.0, jnp.float32)

    np.testing.assert_array_equal(np.asarray(out.choice), sc_choice)
    resident = np.asarray(state.resident)
    for i, srv in enumerate(router.servers):
        assert set(np.nonzero(resident[i])[0]) == set(srv.resident), i

    params, st0 = br.fleet_from_servers(servers, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
    )
    st_c, out_c = br.route_batch(params, st0, reqs, 0.0, chunk=256)
    np.testing.assert_array_equal(np.asarray(out_c.choice), sc_choice)
    np.testing.assert_array_equal(np.asarray(st_c.resident), resident)


@pytest.mark.parametrize("chunk", [None, 64])
def test_out_of_range_policy_falls_back_to_argmin(chunk):
    """Untopologied (has_cells=False) fleets: a policy emitting an index
    >= N (or negative) used to be silently clamped to server N-1 by XLA
    gather semantics and committed with no signal. It now falls back to
    the masked greedy argmin — the same fallback the out-of-cell clamp
    applies — on both the single-scan and chunked paths."""

    def rogue(lats, obs, queue):
        return jnp.int32(99)  # far out of range, every request

    rng = np.random.default_rng(57)
    servers = _random_fleet(rng, 4, 2)
    models, bits, toks = _random_stream(rng, 150)
    params, state = br.fleet_from_servers(servers, CATALOG)
    reqs = br.RequestBatch(
        model=jnp.asarray(models, jnp.int32),
        prompt_bits=jnp.asarray(bits, jnp.float32),
        gen_tokens=jnp.asarray(toks, jnp.float32),
    )
    s_rogue, o_rogue = br.route_batch(params, state, reqs, policy=rogue,
                                      chunk=chunk)
    s_greedy, o_greedy = br.route_batch(params, state, reqs,
                                        policy="greedy", chunk=chunk)
    # the fallback IS the greedy argmin: identical stream and state
    np.testing.assert_array_equal(np.asarray(o_rogue.choice),
                                  np.asarray(o_greedy.choice))
    np.testing.assert_array_equal(np.asarray(o_rogue.hit),
                                  np.asarray(o_greedy.hit))
    np.testing.assert_array_equal(np.asarray(s_rogue.resident),
                                  np.asarray(s_greedy.resident))
    np.testing.assert_array_equal(np.asarray(s_rogue.queue_tokens),
                                  np.asarray(s_greedy.queue_tokens))
    assert (np.asarray(o_rogue.choice) < 4).all()
    assert (np.asarray(o_rogue.choice) >= 0).all()

    def negative(lats, obs, queue):
        return jnp.int32(-3)

    _, o_neg = br.route_batch(params, state, reqs, policy=negative,
                              chunk=chunk)
    np.testing.assert_array_equal(np.asarray(o_neg.choice),
                                  np.asarray(o_greedy.choice))
