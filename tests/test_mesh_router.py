"""Mesh-sharded router units: layout, mesh construction, validation.

Single-device tests for the ``core.mesh_router`` plumbing — the
cell-major ``FleetState`` layout helpers in ``core.batch_router``, the
``make_mesh`` device-count validation (regression: it used to build a
mesh silently over a SUBSET of the platform's devices), the sharded
entry point's own validation errors, and D=1 bitwise equivalence
against the plain ``route_batch`` scan (the multi-device matrix lives
in ``tests/test_multicell_router.py`` under the ``multidevice``
marker; see docs/sharding.md).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import batch_router as br
from repro.core import mesh_router as mr
from repro.core import networks, policies
from repro.core.catalog import build_catalog
from repro.core.router import CLOUD_CELL, EdgeServer
from repro.distributed import sharding
from repro.launch import serve
from repro.workloads.simulate import simulate

CATALOG = build_catalog(
    ["smollm_135m", "starcoder2_3b", "mamba2_2p7b", "musicgen_medium"]
)


def _edge(i, cell, rng, drain=0.0):
    return EdgeServer(
        name=f"c{cell}-es{i}",
        flops_per_s=float(rng.uniform(5e13, 2e14)),
        cache_slots=2,
        uplink_bps=float(rng.uniform(5e7, 2e8)),
        backhaul_bps=float(rng.uniform(5e8, 2e9)),
        resident=list(rng.choice(len(CATALOG), size=2, replace=False)),
        cell=cell,
        drain_rate=drain,
    )


def _fleet(rng, n_cells, per_cell, cloud=False, drain=0.0):
    fleet = [_edge(i, c, rng, drain)
             for c in range(n_cells) for i in range(per_cell)]
    if cloud:
        fleet.append(serve.make_cloud_server(CATALOG, drain_rate=drain))
    return fleet


def _stream(rng, n, n_cells, dtype=jnp.float32):
    return br.RequestBatch(
        model=jnp.asarray(rng.integers(0, len(CATALOG), n), jnp.int32),
        prompt_bits=jnp.asarray(rng.uniform(1e5, 1e6, n), dtype),
        gen_tokens=jnp.asarray(rng.integers(1, 64, n).astype(float), dtype),
        cell=jnp.asarray(rng.integers(0, n_cells, n), jnp.int32),
        arrival_s=jnp.asarray(np.cumsum(rng.exponential(2e-3, n)), dtype),
    )


def _assert_bitwise(st_a, out_a, st_b, out_b):
    """Full-outcome + full-state bitwise equality (LRU compared only on
    resident entries: a non-resident slot's clock is unobservable)."""
    np.testing.assert_array_equal(np.asarray(out_a.choice),
                                  np.asarray(out_b.choice))
    np.testing.assert_array_equal(np.asarray(out_a.latency),
                                  np.asarray(out_b.latency))
    np.testing.assert_array_equal(np.asarray(out_a.hit),
                                  np.asarray(out_b.hit))
    np.testing.assert_array_equal(np.asarray(st_a.resident),
                                  np.asarray(st_b.resident))
    np.testing.assert_array_equal(np.asarray(st_a.queue_tokens),
                                  np.asarray(st_b.queue_tokens))
    assert int(st_a.clock) == int(st_b.clock)
    if st_a.time_s is not None:
        np.testing.assert_array_equal(np.asarray(st_a.time_s),
                                      np.asarray(st_b.time_s))
    res = np.asarray(st_a.resident)
    np.testing.assert_array_equal(np.asarray(st_a.last_use)[res],
                                  np.asarray(st_b.last_use)[res])


# ---------------------------------------------------------------------------
# make_mesh device-count validation (regression)
# ---------------------------------------------------------------------------
def test_make_mesh_rejects_mismatched_axis_shapes():
    """It must be impossible to build a mesh whose axis shapes silently
    cover only part of the devices it draws from."""
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"require {n + 1} device"):
        sharding.make_mesh((n + 1,), ("x",))
    with pytest.raises(ValueError, match="devices argument supplies"):
        sharding.make_mesh((2,), ("x",), devices=jax.devices()[:1])
    # exact-match shapes still build, with and without explicit devices
    assert sharding.make_mesh((n,), ("x",)).shape["x"] == n
    m = sharding.make_mesh((1,), ("x",), devices=jax.devices()[:1])
    assert m.shape["x"] == 1


def test_cells_mesh_smoke():
    mesh = mr.cells_mesh(1)
    assert mesh.axis_names == ("cells",)
    assert mesh.shape["cells"] == 1
    with pytest.raises(ValueError):
        mr.cells_mesh(len(jax.devices()) + 1)


# ---------------------------------------------------------------------------
# cell-major layout helpers
# ---------------------------------------------------------------------------
def test_cell_layout_of_canonical_fleet():
    rng = np.random.default_rng(0)
    params, _ = br.fleet_from_servers(_fleet(rng, 3, 4, cloud=True), CATALOG)
    layout = br.cell_layout(params)
    assert layout == br.CellLayout(num_cells=3, per_cell=4, num_cloud=1)
    assert layout.num_edge == 12 and layout.num_servers == 13


def test_cell_layout_untopologied_fleet_is_one_cell():
    rng = np.random.default_rng(1)
    params, _ = br.fleet_from_servers(
        [_edge(i, 0, rng) for i in range(5)], CATALOG
    )
    layout = br.cell_layout(params)
    assert (layout.num_cells, layout.per_cell, layout.num_cloud) in {
        (1, 5, 0),  # params.cell is None or all zeros — both are one cell
    }


def test_cell_layout_rejects_non_cell_major():
    rng = np.random.default_rng(2)
    interleaved = [_edge(0, 0, rng), _edge(0, 1, rng),
                   _edge(1, 0, rng), _edge(1, 1, rng)]
    params, _ = br.fleet_from_servers(interleaved, CATALOG)
    with pytest.raises(ValueError, match="contiguous ascending"):
        br.cell_layout(params)

    unequal = [_edge(0, 0, rng), _edge(1, 0, rng), _edge(0, 1, rng)]
    params, _ = br.fleet_from_servers(unequal, CATALOG)
    with pytest.raises(ValueError, match="equal-sized"):
        br.cell_layout(params)

    mid_cloud = [_edge(0, 0, rng), serve.make_cloud_server(CATALOG),
                 _edge(0, 1, rng)]
    params, _ = br.fleet_from_servers(mid_cloud, CATALOG)
    with pytest.raises(ValueError, match="CLOUD_CELL servers must trail"):
        br.cell_layout(params)


def test_cell_major_order_and_permute_roundtrip():
    """A shuffled fleet permutes into a valid cell-major layout, and the
    permutation is a pure relabelling of every per-server array."""
    rng = np.random.default_rng(3)
    fleet = _fleet(rng, 3, 2, cloud=True)
    perm = rng.permutation(len(fleet))
    shuffled = [fleet[i] for i in perm]
    params, state = br.fleet_from_servers(shuffled, CATALOG)
    with pytest.raises(ValueError):
        br.cell_layout(params)
    order = br.cell_major_order(np.asarray(params.cell))
    p2, s2 = br.permute_fleet(params, state, order)
    layout = br.cell_layout(p2)
    assert (layout.num_cells, layout.per_cell, layout.num_cloud) == (3, 2, 1)
    np.testing.assert_array_equal(np.asarray(p2.flops_per_s),
                                  np.asarray(params.flops_per_s)[order])
    np.testing.assert_array_equal(np.asarray(s2.resident),
                                  np.asarray(state.resident)[order])


def test_local_block_params_relabel():
    rng = np.random.default_rng(4)
    params, _ = br.fleet_from_servers(_fleet(rng, 3, 2, cloud=True), CATALOG)
    layout = br.cell_layout(params)
    local = br.local_block_params(params, layout, 1)
    cell = np.asarray(local.cell)
    np.testing.assert_array_equal(cell, [0, 0, CLOUD_CELL])
    np.testing.assert_array_equal(np.asarray(local.flops_per_s)[:2],
                                  np.asarray(params.flops_per_s)[2:4])
    np.testing.assert_array_equal(np.asarray(local.flops_per_s)[2:],
                                  np.asarray(params.flops_per_s)[6:])


# ---------------------------------------------------------------------------
# sharded entry-point validation
# ---------------------------------------------------------------------------
def test_sharded_rejects_drain_tokens():
    rng = np.random.default_rng(5)
    params, state = br.fleet_from_servers(_fleet(rng, 2, 2), CATALOG)
    reqs = _stream(rng, 16, 2)
    with pytest.raises(ValueError, match="drain_tokens"):
        mr.route_batch_sharded(params, state, reqs, 4.0, num_devices=1)


def test_sharded_requires_full_cloud_residency():
    rng = np.random.default_rng(6)
    fleet = _fleet(rng, 2, 2)
    partial_cloud = serve.make_cloud_server(CATALOG)
    partial_cloud.resident = [0, 1]  # missing models 2, 3
    partial_cloud.cache_slots = 2
    fleet.append(partial_cloud)
    params, state = br.fleet_from_servers(fleet, CATALOG)
    reqs = _stream(rng, 16, 2)
    with pytest.raises(ValueError, match="cloud"):
        mr.route_batch_sharded(params, state, reqs, num_devices=1)


def test_sharded_empty_batch_delegates_to_plain():
    rng = np.random.default_rng(7)
    params, state = br.fleet_from_servers(_fleet(rng, 2, 2), CATALOG)
    reqs = _stream(rng, 0, 2)
    st, out = mr.route_batch_sharded(params, state, reqs, num_devices=1)
    assert out.choice.shape == (0,)
    np.testing.assert_array_equal(np.asarray(st.resident),
                                  np.asarray(state.resident))


# ---------------------------------------------------------------------------
# D=1 bitwise equivalence vs the plain scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["greedy", "load", "drain"])
@pytest.mark.parametrize("chunk", [None, 16])
def test_sharded_single_device_bitwise_vs_plain(policy, chunk):
    rng = np.random.default_rng(8)
    params, state = br.fleet_from_servers(_fleet(rng, 4, 3), CATALOG)
    reqs = _stream(rng, 150, 4)
    st_p, out_p = br.route_batch(params, state, reqs, policy=policy,
                                 chunk=chunk)
    st_s, out_s = mr.route_batch_sharded(params, state, reqs, policy=policy,
                                         chunk=chunk, num_devices=1)
    _assert_bitwise(st_p, out_p, st_s, out_s)


def test_sharded_spec_scan_kernel_matches_xla():
    """The speculative scan kernel batches under the sharded router's
    ``vmap`` over cell blocks inside ``shard_map``: on
    ``pallas-interpret`` the sharded greedy path routes decision for
    decision, and leaves the fleet state bit for bit, as on ``xla``."""
    rng = np.random.default_rng(10)
    params, state = br.fleet_from_servers(
        _fleet(rng, 4, 3, cloud=True, drain=2e4), CATALOG)
    reqs = _stream(rng, 150, 4)
    (st_x, out_x), (st_k, out_k) = (
        mr.route_batch_sharded(params, state, reqs, chunk=16, backend=b,
                               speculative=True, num_devices=1)
        for b in ("xla", "pallas-interpret"))
    _assert_bitwise(st_x, out_x, st_k, out_k)


def test_sharded_auto_permutes_shuffled_fleet():
    """A non-cell-major fleet routes through an internal permutation and
    comes back in CALLER order — bitwise equal to the plain scan on the
    same shuffled fleet."""
    rng = np.random.default_rng(9)
    fleet = _fleet(rng, 3, 2)
    perm = rng.permutation(len(fleet))
    params, state = br.fleet_from_servers([fleet[i] for i in perm], CATALOG)
    reqs = _stream(rng, 120, 3)
    st_p, out_p = br.route_batch(params, state, reqs)
    st_s, out_s = mr.route_batch_sharded(params, state, reqs, num_devices=1)
    _assert_bitwise(st_p, out_p, st_s, out_s)


# ---------------------------------------------------------------------------
# cell-block actor policy
# ---------------------------------------------------------------------------
def _toy_actor(spec):
    sizes = [policies.obs_dim(spec), 16, 16, spec.num_ess + 3]
    return networks.stacked_init(jax.random.key(0), 2, sizes)


def test_actor_policy_for_cell_blocks_matches_global():
    """One block-local actor closure under the mesh == the global-fleet
    actor closure on the plain path, decision for decision."""
    rng = np.random.default_rng(10)
    params, state = br.fleet_from_servers(_fleet(rng, 3, 4, cloud=True),
                                          CATALOG)
    spec = policies.ObsSpec(num_models=len(CATALOG), num_ess=4, num_cells=1,
                            task_bits_hi=8e6, rho_hi=400.0, f_cc=2e14,
                            f_ed_hi=5e9, area_m=500.0)
    actor = _toy_actor(spec)
    pol_global = policies.make_actor_policy(actor, spec, params)
    pol_local = policies.actor_policy_for_cell_blocks(actor, spec, params)
    reqs = _stream(rng, 96, 3)
    st_p, out_p = br.route_batch(params, state, reqs, policy=pol_global)
    st_s, out_s = mr.route_batch_sharded(params, state, reqs,
                                         policy=pol_local, num_devices=1)
    _assert_bitwise(st_p, out_p, st_s, out_s)


def test_actor_policy_for_cell_blocks_rejects_bad_geometry():
    rng = np.random.default_rng(11)
    params, _ = br.fleet_from_servers(_fleet(rng, 3, 4, cloud=True), CATALOG)
    spec = policies.ObsSpec(num_models=len(CATALOG), num_ess=4, num_cells=1,
                            task_bits_hi=8e6, rho_hi=400.0, f_cc=2e14,
                            f_ed_hi=5e9, area_m=500.0)
    actor = _toy_actor(spec)
    with pytest.raises(ValueError, match="single-cell-trained"):
        policies.actor_policy_for_cell_blocks(
            actor, spec._replace(num_cells=3), params
        )
    with pytest.raises(ValueError, match="cell blocks hold 4"):
        policies.actor_policy_for_cell_blocks(
            actor, spec._replace(num_ess=3), params
        )


# ---------------------------------------------------------------------------
# mesh knobs on the simulator and the serve CLI
# ---------------------------------------------------------------------------
def test_simulate_mesh_windows_match_plain_single_call():
    """Drain-free + cloud-free: sharded windowed simulate == ONE plain
    route_batch call on the whole stream (windowing is a pure
    re-chunking; each window is bitwise vs the plain scan)."""
    rng = np.random.default_rng(12)
    params, state = br.fleet_from_servers(_fleet(rng, 3, 2), CATALOG)
    reqs = _stream(rng, 150, 3)
    st_p, out_p = br.route_batch(params, state, reqs)
    st_s, out_s, series = simulate(params, state, reqs, window_requests=64,
                                   num_devices=1)
    _assert_bitwise(st_p, out_p, st_s, out_s)
    assert len(series.requests) == 3


def test_simulate_rejects_drain_tokens_under_mesh():
    rng = np.random.default_rng(13)
    params, state = br.fleet_from_servers(_fleet(rng, 2, 2), CATALOG)
    reqs = _stream(rng, 16, 2)
    with pytest.raises(ValueError, match="drain_tokens"):
        simulate(params, state, reqs, drain_tokens=4.0, num_devices=1)


def test_serve_mesh_flag_smoke():
    stats = serve.serve(num_requests=12, n_servers=2, execute=False,
                        n_cells=2, mesh=1)
    assert stats["requests"] == 12
    assert stats["completion_rate"] == 1.0


# ---------------------------------------------------------------------------
# seed-pinned fuzz (hypothesis-free twin of test_properties.py's
# test_all_router_paths_agree — same driver, fixed draws, so the
# path-matrix invariant runs in CI without hypothesis installed)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,n_cells,per_cell,cloud,policy,chunk", [
    (1001, 3, 2, False, "greedy", 16),
    (1002, 2, 3, True, "drain", 48),
    (1003, 4, 1, False, "load", 16),
])
def test_router_paths_agree_seeded(seed, n_cells, per_cell, cloud, policy,
                                   chunk):
    from fuzz_paths import check_router_paths_agree

    check_router_paths_agree(seed, n_cells, per_cell, cloud, policy, chunk)


@pytest.mark.parametrize(
    "seed,n_cells,per_cell,cloud,policy,chunk,deadline,spill,outage", [
        (1101, 3, 2, False, "greedy", 16, True, False, False),
        (1102, 2, 2, False, "drain", 16, False, True, False),
        (1103, 3, 1, False, "greedy", 48, False, False, True),
        (1104, 4, 2, False, "drain", 16, True, True, True),
        (1105, 2, 3, True, "load", 48, True, False, True),
    ])
def test_router_paths_agree_robustness_seeded(seed, n_cells, per_cell, cloud,
                                              policy, chunk, deadline, spill,
                                              outage):
    """Seed-pinned twin of the hypothesis sweep's robustness knobs: SLO
    deadline column, neighbour-cell spill adjacency and server-outage
    mask through every router path, rejection causes included."""
    from fuzz_paths import check_router_paths_agree

    check_router_paths_agree(seed, n_cells, per_cell, cloud, policy, chunk,
                             deadline=deadline, spill=spill, outage=outage)


@pytest.mark.parametrize(
    "seed,n_cells,per_cell,cloud,policy,chunk,eta,beta,deadline,outage,spill",
    [
        (1201, 3, 2, False, "greedy", 16, "mixed", False, False, False,
         False),
        (1202, 2, 3, True, "drain", 48, False, "mixed", False, False, False),
        (1203, 3, 1, False, "greedy", 16, "zero", "refuse", True, False,
         False),
        (1204, 2, 2, True, "drain", 16, "mixed", "mixed", False, True,
         False),
        (1205, 4, 2, False, "load", 48, "mixed", "download", True, False,
         True),
    ])
def test_router_paths_agree_eta_beta_seeded(seed, n_cells, per_cell, cloud,
                                            policy, chunk, eta, beta,
                                            deadline, outage, spill):
    """Seed-pinned twin of the hypothesis sweep's eq. 16 action knobs:
    partial-offload eta columns and download-refusal beta columns (plus
    their interactions with the robustness knobs) through every router
    path — scan, chunked, speculative, mesh-sharded — against the
    scalar oracle."""
    from fuzz_paths import check_router_paths_agree

    check_router_paths_agree(seed, n_cells, per_cell, cloud, policy, chunk,
                             deadline=deadline, spill=spill, outage=outage,
                             eta=eta, beta=beta)
