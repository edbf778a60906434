"""Env <-> router cross-check for the eq. 16 action space.

The training environment (``core.env.step``) and the serving oracle
(``core.router.ModelAwareRouter``) price the SAME paper equations —
eq. 3 local share, eq. 5 uplink, eq. 7/8 model switch, eq. 9 edge
compute, eq. 13 max-overlap — from two different codebases. This module
pins them against each other for full ``(target, eta, beta)`` action
sequences: with power-of-two task sizes, densities and ratios every
product in both pipelines is exact, so the two latencies must agree
BITWISE (the only rounding happens in the shared divisions, which see
identical operands). Residency/LRU dynamics are compared step for step
along the way.

The mapping between the two worlds:

* ``x * rho`` (env cycles)  ==  ``gen_tokens * decode_flops_per_token``
  (router work) — the test picks ``gen = x * rho / ftok`` exactly;
* the env's per-step Shannon rate becomes the server's ``uplink_bps``
  (M = 1, so the contention divisor is 1 and the rate is static);
* the env has no queue backlog — the oracle's queues are zeroed before
  each pricing (commit effects are tested separately in
  ``tests/test_batch_router.py``).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from x64 import enable_x64
from repro.core import costs, env
from repro.core.catalog import CatalogEntry
from repro.core.router import EdgeServer, ModelAwareRouter, Request
from repro.core.types import Action
from repro.workloads.simulate import request_energy_j

# power-of-two world: every product below is exact in f32 AND f64
_X_BITS = [2.0 ** 23, 2.0 ** 22, 2.0 ** 24]       # task sizes
_RHO = [2.0 ** 6, 2.0 ** 5, 2.0 ** 7]             # compute densities
_FTOK = 2.0 ** 20                                  # decode FLOPs/token
_MODEL_BITS = (2.0 ** 30, 2.0 ** 31, 2.0 ** 29)    # switch payloads
_F_ES = 2.0 ** 33
_F_ED = 2.0 ** 31
_ETAS = [1.0, 0.5, 0.25, 0.75]


def _setup(num_ess=2, cache=((0, 1), (1, 2))):
    """One-ED env + the equivalent oracle fleet, residency synced."""
    p = env.default_params(num_eds=1, num_models=3, num_ess=num_ess)
    p = p._replace(model_bits=_MODEL_BITS, f_es=_F_ES,
                   deadline=(64.0,) * 3)
    s = env.reset(jax.random.key(0), p)
    cache_arr = np.zeros((num_ess, 3), np.float64)
    for n, models in enumerate(cache):
        cache_arr[n, list(models)] = 1.0
    s = s._replace(
        f_ed=jnp.full((1,), _F_ED, jnp.float64),
        cache=jnp.asarray(cache_arr),
        last_use=jnp.zeros((num_ess, 3), jnp.int32),
    )
    # the env's per-(ED, ES) Shannon rate IS the server's uplink
    dist = jnp.linalg.norm(
        s.ed_pos[0].astype(jnp.float64) - s.es_pos.astype(jnp.float64),
        axis=-1)
    gain = costs.channel_gain(dist, p.pathloss_ref, p.pathloss_exp)
    rates = costs.shannon_rate(p.bandwidth_hz, p.tx_power_w, gain,
                               p.noise_w_per_hz)
    catalog = [
        CatalogEntry(k, f"m{k}", "f", 1, _MODEL_BITS[k], _FTOK)
        for k in range(3)
    ]
    servers = [
        EdgeServer(name=f"es{n}", flops_per_s=_F_ES, cache_slots=2,
                   uplink_bps=float(rates[n]), backhaul_bps=p.backhaul_bps,
                   resident=list(cache[n]))
        for n in range(num_ess)
    ]
    return p, s, catalog, servers


def _env_step(p, s, *, model, x, rho, target, eta, beta):
    """Run one eager x64 env step on a crafted task/action pair."""
    s = s._replace(task=s.task._replace(
        mu=jnp.asarray([model], jnp.int32),
        x_bits=jnp.asarray([x], jnp.float64),
        rho=jnp.asarray([rho], jnp.float64),
    ))
    act = Action(target=jnp.asarray([target], jnp.int32),
                 eta=jnp.asarray([eta], jnp.float64),
                 beta=jnp.asarray([1.0 if beta else 0.0], jnp.float64))
    s2, _, out, _ = env.step(s, act, p)
    # keep the crafted-task loop going: step resamples tasks, positions
    # and f_ed persist
    return s2, out


# one action per step: (model, x, rho, es target (1-based), eta, beta)
_SEQUENCE = [
    (0, _X_BITS[0], _RHO[0], 1, 0.5, True),    # hit on es0 (model 0)
    (1, _X_BITS[1], _RHO[1], 1, 1.0, True),    # hit on es0 (model 1)
    (2, _X_BITS[2], _RHO[2], 1, 0.25, True),   # miss -> download, evict
    (2, _X_BITS[0], _RHO[1], 2, 0.75, True),   # hit on es1 (model 2)
    (0, _X_BITS[1], _RHO[2], 2, 0.5, True),    # miss -> download on es1
    (1, _X_BITS[2], _RHO[0], 1, 1.0, True),    # post-eviction revisit
]


@pytest.mark.parametrize("local", [False, True])
def test_env_latency_bitmatches_oracle_sequence(local):
    """Env step latencies == oracle partial-offload pricing, bit for bit,
    along a fixed (target, eta, beta) sequence; residency/LRU evolve in
    lockstep. ``local`` toggles the eq. 3 device share (eq. 13 max)."""
    with enable_x64():
        p, s, catalog, servers = _setup()
        router = ModelAwareRouter(servers, catalog, policy="actor",
                                  actor=None)
        for step_i, (m, x, rho, tgt, eta, beta) in enumerate(_SEQUENCE):
            s, out = _env_step(p, s, model=m, x=x, rho=rho, target=tgt,
                               eta=eta, beta=beta)
            assert float(out.failed_compat[0]) == 0.0
            # oracle prices the same action against a clean queue
            for srv in router.servers:
                srv.queue_tokens = 0.0
            router.actor = lambda obs, lats, _t=tgt: _t - 1
            req = Request(
                m, x, x * rho / _FTOK, eta=eta, beta=beta,
                local_flops_per_s=_F_ED if local else None,
            )
            choice, lat = router.route(req)
            assert choice == tgt - 1, step_i
            if local:
                np.testing.assert_array_equal(
                    lat, float(out.latency[0]), err_msg=f"step {step_i}")
            else:  # edge-only pricing: the env's eq. 13 max still applies
                t_loc = costs.local_latency(x, eta, rho, _F_ED)
                np.testing.assert_array_equal(
                    max(float(t_loc), lat), float(out.latency[0]),
                    err_msg=f"step {step_i}")
            # residency dynamics track bit for bit (download + LRU evict)
            cache = np.asarray(s.cache)
            for n, srv in enumerate(router.servers):
                assert set(srv.resident) == set(np.nonzero(cache[n])[0]), \
                    f"step {step_i} server {n}"


def test_env_energy_matches_equation_composition():
    """Env step energy == the eta-aware eq. 4/6/8/10 composition (the
    corrected variants), term for term through ``core.costs``."""
    with enable_x64():
        p, s, _, servers = _setup()
        m, x, rho, tgt, eta = 2, _X_BITS[2], _RHO[2], 1, 0.25
        dist = float(np.linalg.norm(
            np.asarray(s.ed_pos[0], np.float64)
            - np.asarray(s.es_pos[tgt - 1], np.float64)))
        gain = costs.channel_gain(dist, p.pathloss_ref, p.pathloss_exp)
        rate = costs.shannon_rate(p.bandwidth_hz, p.tx_power_w, gain,
                                  p.noise_w_per_hz)
        _, out = _env_step(p, s, model=m, x=x, rho=rho, target=tgt,
                           eta=eta, beta=True)
        t_trans = costs.trans_latency(x, eta, rate)
        t_switch = costs.switch_latency(_MODEL_BITS[m], p.backhaul_bps)
        e_edge = costs.edge_total_energy(
            costs.trans_energy(p.tx_power_w, t_trans),
            costs.switch_energy(p.backhaul_power_w, t_switch),
            costs.edge_energy_corrected(x, eta, rho, p.kappa_es, p.f_es),
        )
        e_local = costs.local_energy_corrected(x, eta, rho, p.kappa_ed,
                                               _F_ED)
        np.testing.assert_array_equal(
            float(costs.total_energy(e_local, e_edge, False)),
            float(out.energy[0]))


def test_refused_miss_is_env_failed_compat_and_oracle_inf():
    """beta = False on a residency miss: the env flags failed_compat,
    the oracle prices that candidate +inf (refusal re-prices against
    resident-only columns — the shared eq. 16 semantics)."""
    with enable_x64():
        p, s, catalog, servers = _setup()
        m, x, rho, tgt = 2, _X_BITS[2], _RHO[2], 1   # model 2 not on es0
        _, out = _env_step(p, s, model=m, x=x, rho=rho, target=tgt,
                           eta=0.5, beta=False)
        assert float(out.failed_compat[0]) == 1.0
        assert float(out.completed[0]) == 0.0
        router = ModelAwareRouter(servers, catalog)
        req = Request(m, x, x * rho / _FTOK, eta=0.5, beta=False)
        assert np.isinf(router._candidate_latency(router.servers[0], req))
        # the refused fleet re-prices resident-only: es1 holds model 2
        choice, lat = router.route(req)
        assert choice == 1 and np.isfinite(lat)
        # a hit under beta = False completes on both sides
        s2, out2 = _env_step(p, s, model=0, x=x, rho=rho, target=1,
                             eta=0.5, beta=False)
        assert float(out2.failed_compat[0]) == 0.0
        assert float(out2.completed[0]) == 1.0


def test_request_energy_eta_scales_edge_share():
    """The serving-side energy metric scales eq. 6/10 with eta and keeps
    the eq. 8 hit gate — the eta = 1 column equals the eta-free call."""
    from repro.core import batch_router as br
    from repro.core.catalog import build_catalog

    with enable_x64():
        cat = build_catalog(["smollm_135m", "starcoder2_3b"])
        fleet = [EdgeServer(name="es0", flops_per_s=1e14, cache_slots=2,
                            uplink_bps=1e8, backhaul_bps=1e9, resident=[0])]
        params, state = br.fleet_from_servers(fleet, cat)
        reqs = br.RequestBatch(
            model=jnp.asarray([0, 1], jnp.int32),
            prompt_bits=jnp.asarray([2.0 ** 20, 2.0 ** 21]),
            gen_tokens=jnp.asarray([16.0, 32.0]),
        )
        _, out = br.route_batch(params, state, reqs)
        base = request_energy_j(params, reqs, out)
        ones = request_energy_j(
            params, reqs._replace(eta=jnp.asarray([1.0, 1.0])), out)
        np.testing.assert_array_equal(base, ones)
        half = request_energy_j(
            params, reqs._replace(eta=jnp.asarray([0.5, 0.5])), out)
        # transmission + compute halve; the eq. 8 switch term does not
        model = np.asarray(reqs.model)
        t_switch = np.where(
            np.asarray(out.hit), 0.0,
            np.asarray(params.size_bits)[model]
            / np.asarray(params.backhaul_bps)[np.asarray(out.choice)])
        e_switch = 2.0 * t_switch
        np.testing.assert_allclose(
            half - e_switch, (base - e_switch) / 2.0, rtol=1e-9)
