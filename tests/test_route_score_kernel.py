"""Fused routing-score kernel vs the XLA reference — allclose, all modes.

``kernels/route_score.py`` (Pallas, interpret mode on CPU) must agree
with ``kernels/ref.route_score_xla`` — whose arithmetic is
``core.costs.edge_score_matrix`` — across dtypes (f32/bf16),
non-tile-multiple (B, N, K) shapes, cell masks on/off, and the
switch-free / queue-free base variants the chunked router uses. The
``+inf`` cell masking must match the reference exactly (same masked
set), and ``score_matrix``'s backend dispatch must expose the same
contraction through ``FleetParams``/``FleetState``.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import batch_router as br
from repro.core.catalog import build_catalog
from repro.core.router import CLOUD_CELL
from repro.kernels import ops, ref
from repro.kernels.route_score import route_score

CATALOG = build_catalog(
    ["smollm_135m", "starcoder2_3b", "mamba2_2p7b", "musicgen_medium"]
)


def _random_case(rng, b, n, k, dtype, cells=None):
    """Plain-array inputs in physically plausible ranges."""
    args = dict(
        prompt_bits=jnp.asarray(rng.uniform(1e5, 1e6, b), dtype),
        size_bits=jnp.asarray(rng.uniform(1e9, 1e10, b), dtype),
        flops_tok=jnp.asarray(rng.uniform(1e9, 1e10, b), dtype),
        work=jnp.asarray(rng.uniform(1e10, 1e12, b), dtype),
        uplink_bps=jnp.asarray(rng.uniform(5e7, 2e8, n), dtype),
        backhaul_bps=jnp.asarray(rng.uniform(5e8, 2e9, n), dtype),
        flops_per_s=jnp.asarray(rng.uniform(5e13, 2e14, n), dtype),
        queue_tokens=jnp.asarray(rng.uniform(0, 500, n), dtype),
        resident=jnp.asarray(rng.random((n, k)) < 0.5),
        model=jnp.asarray(rng.integers(0, k, b), jnp.int32),
    )
    if cells is not None:
        args["req_cell"] = jnp.asarray(rng.integers(0, cells, b), jnp.int32)
        srv = rng.integers(0, cells, n)
        srv[rng.random(n) < 0.2] = CLOUD_CELL  # sprinkle cloud columns
        args["srv_cell"] = jnp.asarray(srv, jnp.int32)
    return args


TOLS = {jnp.float32: dict(rtol=1e-6, atol=0.0),
        jnp.bfloat16: dict(rtol=2e-2, atol=0.0)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,n,k", [
    (5, 3, 4),          # everything below one tile
    (130, 65, 5),       # just past the tile boundary on both axes
    (128, 128, 4),      # exact tile multiples
    (257, 17, 9),       # ragged everywhere, K > catalogue
])
def test_kernel_matches_xla_reference(dtype, b, n, k):
    rng = np.random.default_rng(b * 1000 + n)
    args = _random_case(rng, b, n, k, dtype)
    expect = np.asarray(ref.route_score_xla(**args), np.float32)
    got = np.asarray(route_score(**args, interpret=True), np.float32)
    assert got.shape == (b, n)
    np.testing.assert_allclose(got, expect, **TOLS[dtype])


@pytest.mark.parametrize("b,n,k,cells", [(37, 9, 4, 3), (130, 33, 6, 5)])
def test_kernel_cell_mask_inf_exact(b, n, k, cells):
    """+inf lands on exactly the out-of-cell, non-cloud pairs."""
    rng = np.random.default_rng(7)
    args = _random_case(rng, b, n, k, jnp.float32, cells=cells)
    expect = np.asarray(ref.route_score_xla(**args))
    got = np.asarray(route_score(**args, interpret=True))
    np.testing.assert_array_equal(np.isinf(got), np.isinf(expect))
    visible = np.isfinite(expect)
    srv = np.asarray(args["srv_cell"]); req = np.asarray(args["req_cell"])
    assert ((srv[None, :] == req[:, None]) | (srv[None, :] == CLOUD_CELL)
            ).sum() == visible.sum()
    np.testing.assert_allclose(got[visible], expect[visible], rtol=1e-6)


@pytest.mark.parametrize("b,n,k,cells", [(41, 11, 4, 3), (130, 33, 5, 4)])
def test_kernel_spill_adjacency_matches_reference(b, n, k, cells):
    """Neighbour-cell spill: the (C, C) adjacency opens exactly the
    spilled pairs, priced at the no-mask score plus the backhaul
    surcharge, identically in the kernel and the XLA reference."""
    rng = np.random.default_rng(23)
    args = _random_case(rng, b, n, k, jnp.float32, cells=cells)
    adj = rng.random((cells, cells)) < 0.5
    np.fill_diagonal(adj, False)
    args["spill"] = jnp.asarray(adj)
    expect = np.asarray(ref.route_score_xla(**args))
    got = np.asarray(route_score(**args, interpret=True))
    np.testing.assert_array_equal(np.isinf(got), np.isinf(expect))
    fin = np.isfinite(expect)
    np.testing.assert_allclose(got[fin], expect[fin], rtol=1e-6)
    # the adjacency strictly widens the no-spill visibility...
    no_spill = np.asarray(
        ref.route_score_xla(**{**args, "spill": None}))
    widened = fin & ~np.isfinite(no_spill)
    assert widened.any()
    # ...and every widened pair pays prompt_bits/backhaul on top of the
    # unmasked eq. 11 score
    unmasked = np.asarray(ref.route_score_xla(
        **{**args, "spill": None, "req_cell": None, "srv_cell": None}))
    surcharge = (np.asarray(args["prompt_bits"])[:, None]
                 / np.asarray(args["backhaul_bps"])[None, :])
    np.testing.assert_allclose(expect[widened],
                               (unmasked + surcharge)[widened], rtol=1e-6)


def test_kernel_switch_free_and_queue_free_base():
    """The chunked router's phase-1 variants: size_bits=None drops
    eq. 7 entirely, queue_tokens=None the backlog term."""
    rng = np.random.default_rng(11)
    args = _random_case(rng, 33, 9, 4, jnp.float32)
    for drop in (("size_bits",), ("queue_tokens",),
                 ("size_bits", "queue_tokens", "resident", "model")):
        case = {**args, **{key: None for key in drop}}
        expect = np.asarray(ref.route_score_xla(**case))
        got = np.asarray(route_score(**case, interpret=True))
        np.testing.assert_allclose(got, expect, rtol=1e-6, err_msg=str(drop))


def test_ungated_when_resident_absent():
    """resident=None prices every pair at the full switch cost."""
    rng = np.random.default_rng(13)
    args = _random_case(rng, 16, 5, 4, jnp.float32)
    gated = np.asarray(route_score(**args, interpret=True))
    args["resident"] = None
    ungated = np.asarray(route_score(**args, interpret=True))
    assert (ungated >= gated - 1e-6).all()
    assert (ungated > gated).any()  # some pair actually was resident


@pytest.mark.parametrize("b,n,k", [(5, 3, 4), (130, 33, 5)])
def test_kernel_eta_scales_base(b, n, k):
    """eq. 16 eta scales the eq. 5/9 terms in kernel and reference
    alike; eta of ones is BITWISE the knob-absent call (the pre-scale
    multiplies by 1.0 — an IEEE identity)."""
    rng = np.random.default_rng(b + n)
    args = _random_case(rng, b, n, k, jnp.float32)
    eta = jnp.asarray(
        rng.choice([0.0, 0.25, 0.5, 0.75, 1.0], size=b), jnp.float32)
    expect = np.asarray(ref.route_score_xla(**args, eta=eta))
    got = np.asarray(route_score(**args, eta=eta, interpret=True))
    np.testing.assert_allclose(got, expect, rtol=1e-6)
    # eta scales ONLY the eq. 5 prompt and the eq. 9 new-work term; the
    # switch price and the queue backlog stay fixed, so the score is
    # affine in eta: score(eta) == score(0) + eta * (score(1) - score(0))
    base = np.asarray(ref.route_score_xla(**args))
    fixed = np.asarray(ref.route_score_xla(
        **args, eta=jnp.zeros(b, jnp.float32)))
    e = np.asarray(eta)[:, None]
    np.testing.assert_allclose(expect, fixed + e * (base - fixed),
                               rtol=1e-5)
    for backend_fn in (ref.route_score_xla,
                       lambda **kw: route_score(**kw, interpret=True)):
        ones = np.asarray(backend_fn(**args, eta=jnp.ones(b, jnp.float32)))
        absent = np.asarray(backend_fn(**args))
        np.testing.assert_array_equal(ones, absent)


@pytest.mark.parametrize("b,n,k", [(7, 5, 4), (130, 33, 5)])
def test_kernel_beta_refusal_masks_misses(b, n, k):
    """beta = False prices every NON-resident pair +inf (the residency
    gate is a select, so hits keep their finite score untouched);
    all-True beta is BITWISE the knob-absent call."""
    rng = np.random.default_rng(3 * b + n)
    args = _random_case(rng, b, n, k, jnp.float32)
    beta = jnp.asarray(rng.random(b) < 0.5)
    expect = np.asarray(ref.route_score_xla(**args, beta=beta))
    got = np.asarray(route_score(**args, beta=beta, interpret=True))
    np.testing.assert_array_equal(np.isinf(got), np.isinf(expect))
    fin = np.isfinite(expect)
    np.testing.assert_allclose(got[fin], expect[fin], rtol=1e-6)
    res = np.asarray(args["resident"])[
        :, np.asarray(args["model"])].T            # (B, N) hit map
    refused = ~np.asarray(beta)[:, None] & ~res
    np.testing.assert_array_equal(np.isinf(expect), refused)
    base = np.asarray(ref.route_score_xla(**args))
    np.testing.assert_array_equal(expect[~refused], base[~refused])
    for backend_fn in (ref.route_score_xla,
                       lambda **kw: route_score(**kw, interpret=True)):
        always = np.asarray(backend_fn(**args, beta=jnp.ones(b, bool)))
        absent = np.asarray(backend_fn(**args))
        np.testing.assert_array_equal(always, absent)


def test_beta_without_size_bits_raises():
    """The switch-free base has no eq. 7 term to refuse."""
    rng = np.random.default_rng(5)
    args = _random_case(rng, 9, 4, 4, jnp.float32)
    args["size_bits"] = None
    with pytest.raises(ValueError, match="beta"):
        ref.route_score_xla(**args, beta=jnp.ones(9, bool))
    with pytest.raises(ValueError, match="beta"):
        route_score(**args, beta=jnp.ones(9, bool), interpret=True)


def test_eta_beta_ragged_shapes_combined():
    """Both knobs together on a ragged (B, N, K) grid, with cells."""
    rng = np.random.default_rng(29)
    args = _random_case(rng, 257, 17, 9, jnp.float32, cells=3)
    eta = jnp.asarray(
        rng.choice([0.25, 0.5, 1.0], size=257), jnp.float32)
    beta = jnp.asarray(rng.random(257) < 0.5)
    expect = np.asarray(ref.route_score_xla(**args, eta=eta, beta=beta))
    got = np.asarray(route_score(**args, eta=eta, beta=beta,
                                 interpret=True))
    assert got.shape == (257, 17)
    np.testing.assert_array_equal(np.isinf(got), np.isinf(expect))
    fin = np.isfinite(expect)
    assert fin.any() and not fin.all()
    np.testing.assert_allclose(got[fin], expect[fin], rtol=1e-6)


def test_custom_block_shapes():
    """Tile sizes are knobs; odd blocks still reproduce the reference."""
    rng = np.random.default_rng(17)
    args = _random_case(rng, 70, 40, 4, jnp.float32)
    expect = np.asarray(ref.route_score_xla(**args))
    got = np.asarray(
        route_score(**args, interpret=True, block_b=32, block_n=16)
    )
    np.testing.assert_allclose(got, expect, rtol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
def test_score_matrix_backend_dispatch(backend):
    """``score_matrix`` exposes the same contraction per backend."""
    rng = np.random.default_rng(19)
    from repro.launch.serve import make_multicell_fleet

    fleet = make_multicell_fleet(3, 2, CATALOG)
    params, state = br.fleet_from_servers(fleet, CATALOG)
    b = 29
    reqs = br.RequestBatch(
        model=jnp.asarray(rng.integers(0, len(CATALOG), b), jnp.int32),
        prompt_bits=jnp.asarray(rng.uniform(1e5, 1e6, b), jnp.float32),
        gen_tokens=jnp.asarray(rng.integers(1, 64, b), jnp.float32),
        cell=jnp.asarray(rng.integers(0, 3, b), jnp.int32),
    )
    got = np.asarray(br.score_matrix(params, state, reqs, backend=backend))
    expect = np.asarray(br.score_matrix(params, state, reqs, backend="xla"))
    np.testing.assert_allclose(got, expect, rtol=1e-6)
    assert np.isinf(got).any()  # the cell mask reached the kernel


def test_ops_dispatch_rejects_unknown_backend():
    with pytest.raises(ValueError):
        br.resolve_backend("cuda")


def test_env_knob_resolves_backend(monkeypatch):
    monkeypatch.setenv(br.BACKEND_ENV, "pallas-interpret")
    assert br.resolve_backend(None) == "pallas-interpret"
    monkeypatch.delenv(br.BACKEND_ENV)
    assert br.resolve_backend(None) == "xla"
    assert br.resolve_backend("pallas") == "pallas"


def test_pallas_backend_never_emulates_on_cpu():
    """``backend="pallas"`` always compiles the kernel: on a CPU backend
    it raises instead of falling back to the interpreter."""
    args = _random_case(np.random.default_rng(23), 9, 5, 4, jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        ops.route_score(**args, backend="pallas")
    got = ops.route_score(**args, backend="pallas-interpret")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ops.route_score(**args, backend="xla")),
        rtol=1e-6)
