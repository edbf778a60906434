"""Fused (B, N) routing-score matrix as a Pallas TPU kernel.

The paper's eq. 11 offloading decision prices every request x server
pair with three terms — uplink transmission (eq. 5), a model-switch
download gated on residency (eq. 7), and FIFO compute against the queue
backlog (eq. 9). ``core.batch_router.score_matrix`` evaluates the full
(B, N) contraction; this kernel computes it in ONE VMEM pass, tiled over
(block_b, block_n) panels:

  * per-request columns ride in as a packed (8, B) feature strip and
    per-server columns as an (8, N) strip, so each tile reads two thin
    slabs instead of B x N scalars;
  * the residency gate ``resident[n, model_b]`` is an MXU contraction:
    one-hot(model) (B, K) @ resident.T (K, N) — the same score-panel
    trick the flash-attention kernel uses for its mask, so no (B, N)
    gather ever materialises in HBM;
  * the multi-cell visibility mask (in-cell servers + the fleet-wide
    ``cloud_cell`` column scoring everything else ``+inf``) is fused
    into the same pass.

Non-multiple (B, N, K) shapes are zero/one-padded up to the tile grid
and sliced back; padded lanes never reach the caller. Math runs in fp32
for fp32/bf16 inputs (output cast back) and in fp64 for fp64 inputs —
the x64 oracle-equivalence tier runs the kernel too, and interpret mode
(the only place fp64 occurs) supports it. ``interpret=True`` runs the
kernel under the Pallas interpreter on CPU (``backend="pallas-interpret"``
in ``kernels/ops.py``); ``interpret=False`` compiles it for the TPU and
is what ``backend="pallas"`` always uses. The XLA reference
lives in ``kernels/ref.route_score_xla`` (same arithmetic via
``core.costs.edge_score_matrix``) and the two are pinned allclose in
``tests/test_route_score_kernel.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(*refs, has_switch, has_resident, has_cells, has_spill,
            cloud_cell, out_dtype):
    refs = list(refs)
    req = refs.pop(0)[...]  # (8, bb) request strip (compute dtype)
    srv = refs.pop(0)[...]  # (8, bn) server strip
    prompt = req[0][:, None]
    size = req[1][:, None]
    flops_tok = req[2][:, None]
    work = req[3][:, None]
    uplink = srv[0][None, :]
    backhaul = srv[1][None, :]
    flops = srv[2][None, :]
    queue = srv[3][None, :]

    t_trans = prompt / uplink                      # eq. 5
    t_comp = (queue * flops_tok + work) / flops    # eq. 9
    if has_switch:
        t_switch = size / backhaul                 # eq. 7 (ungated price)
        if has_resident:
            onehot = refs.pop(0)[...]              # (bb, Kp)
            resident_t = refs.pop(0)[...]          # (Kp, bn)
            res = jax.lax.dot_general(             # resident[n, model_b]
                onehot, resident_t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) > 0.5
            t_switch = jnp.where(res, 0.0, t_switch)  # residency gate
        score = t_trans + t_switch + t_comp        # eq. 11
    else:
        score = t_trans + t_comp                   # switch-free base

    if has_cells:
        req_cell = refs.pop(0)[...]                # (1, bb) int32
        srv_cell = refs.pop(0)[...]                # (1, bn) int32
        home = srv_cell[0][None, :] == req_cell[0][:, None]
        visible = home | (srv_cell[0][None, :] == cloud_cell)
        if has_spill:
            # neighbour-cell spill: the adjacency row is gathered by the
            # same MXU trick as the residency gate — one-hot(req_cell)
            # (bb, Cp) @ adjacency columns (Cp, bn); OOB request cells
            # have all-zero one-hot rows, so orphans never spill
            oh_cell = refs.pop(0)[...]             # (bb, Cp)
            adj_srv = refs.pop(0)[...]             # (Cp, bn)
            spilled = jax.lax.dot_general(
                oh_cell, adj_srv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) > 0.5
            spilled = spilled & ~home
            # backhaul surcharge: the prompt crosses the inter-cell link
            score = score + jnp.where(spilled, prompt / backhaul, 0.0)
            visible = visible | spilled
        score = jnp.where(visible, score, jnp.inf)
    refs[0][...] = score.astype(out_dtype)


def _pack_rows(rows, width, pad_values, dtype):
    """(8, width) strip: each row right-padded with its pad value."""
    strip = jnp.zeros((8, width), dtype)
    for i, (row, fill) in enumerate(zip(rows, pad_values)):
        strip = strip.at[i, : row.shape[0]].set(row.astype(dtype))
        if fill != 0.0:
            strip = strip.at[i, row.shape[0]:].set(fill)
    return strip


def route_score(
    prompt_bits, size_bits, flops_tok, work,
    uplink_bps, backhaul_bps, flops_per_s,
    queue_tokens=None, resident=None, model=None,
    req_cell=None, srv_cell=None, spill=None, eta=None, beta=None,
    *, cloud_cell: int = -1, block_b: int = 128, block_n: int = 128,
    interpret: bool = False, out_dtype=None,
):
    """Fused eq. 11 cost matrix, (B,) request x (N,) server columns.

    ``resident`` (N, K) + ``model`` (B,) enable the residency gate
    (``None`` prices every pair at the full switch cost);
    ``size_bits=None`` drops the eq. 7 term entirely and
    ``queue_tokens=None`` the backlog term — the chunked router's
    switch-free base. ``req_cell``/``srv_cell`` fuse the block-diagonal
    visibility mask (out-of-cell pairs score ``+inf``); ``spill`` (a
    (C, C) bool adjacency) widens it with backhaul-priced neighbour-cell
    pairs (surcharge ``prompt_bits / backhaul_bps``).

    ``eta`` (B,) scales prompt and work before the strips are packed —
    ``(x * eta) / r`` is the IEEE grouping of eq. 5/9's ``x eta / r``,
    so the kernel body needs no eta lane and ``eta=None`` is bitwise
    today's path. ``beta`` (B,) False poisons ``size_bits`` to ``+inf``:
    the in-kernel residency gate (a select, never a multiply) still
    zeroes hits, and every refused miss prices ``+inf``.
    """
    from repro.core import costs  # leaf module (jnp-only): no cycle

    prompt_bits, size_bits, work = costs.apply_eta_beta(
        prompt_bits, size_bits, work, eta, beta
    )
    has_switch = size_bits is not None
    has_resident = has_switch and resident is not None
    has_cells = req_cell is not None and srv_cell is not None
    has_spill = has_cells and spill is not None
    if has_resident and model is None:
        raise ValueError("resident gating requires the request model ids")
    b, n = prompt_bits.shape[0], uplink_bps.shape[0]
    if out_dtype is None:
        out_dtype = jnp.result_type(prompt_bits, uplink_bps)
    # fp32 math for fp32/bf16 inputs; fp64 only for the x64 oracle tier
    compute_dtype = jnp.promote_types(out_dtype, jnp.float32)
    bp, np_ = _round_up(b, block_b), _round_up(n, block_n)

    # divisor columns pad with 1.0 so padded lanes stay finite garbage
    # (they are sliced away below, but NaNs trip interpret-mode checks)
    zero_s = jnp.zeros((b,), compute_dtype)
    req = _pack_rows(
        [prompt_bits, zero_s if size_bits is None else size_bits,
         flops_tok, work],
        bp, [0.0, 0.0, 0.0, 0.0], compute_dtype,
    )
    zero_q = jnp.zeros((n,), compute_dtype)
    srv = _pack_rows(
        [uplink_bps, backhaul_bps, flops_per_s,
         zero_q if queue_tokens is None else queue_tokens],
        np_, [1.0, 1.0, 1.0, 0.0], compute_dtype,
    )

    grid = (bp // block_b, np_ // block_n)
    in_specs = [
        pl.BlockSpec((8, block_b), lambda i, j: (0, i)),
        pl.BlockSpec((8, block_n), lambda i, j: (0, j)),
    ]
    inputs = [req, srv]
    if has_resident:
        kp = _round_up(resident.shape[1], 128)
        onehot = jax.nn.one_hot(model, kp, dtype=jnp.float32)  # (b, kp)
        onehot = jnp.pad(onehot, ((0, bp - b), (0, 0)))
        resident_t = jnp.pad(
            resident.T.astype(jnp.float32),
            ((0, kp - resident.shape[1]), (0, np_ - n)),
        )
        in_specs += [
            pl.BlockSpec((block_b, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, block_n), lambda i, j: (0, j)),
        ]
        inputs += [onehot, resident_t]
    if has_cells:
        rc = jnp.pad(req_cell.astype(jnp.int32), (0, bp - b))[None, :]
        sc = jnp.pad(srv_cell.astype(jnp.int32), (0, np_ - n))[None, :]
        in_specs += [
            pl.BlockSpec((1, block_b), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ]
        inputs += [rc, sc]
    if has_spill:
        ncell = spill.shape[0]
        cp = _round_up(ncell, 128)
        # one_hot maps OOB cells (orphans, CLOUD_CELL) to all-zero rows
        oh_cell = jax.nn.one_hot(req_cell.astype(jnp.int32), cp,
                                 dtype=jnp.float32)
        oh_cell = jnp.pad(oh_cell, ((0, bp - b), (0, 0)))
        sc_i = srv_cell.astype(jnp.int32)
        in_range = (sc_i >= 0) & (sc_i < ncell)
        adj_srv = spill.astype(jnp.float32)[:, jnp.clip(sc_i, 0, ncell - 1)]
        adj_srv = adj_srv * in_range[None, :].astype(jnp.float32)
        adj_srv = jnp.pad(adj_srv, ((0, cp - ncell), (0, np_ - n)))
        in_specs += [
            pl.BlockSpec((block_b, cp), lambda i, j: (i, 0)),
            pl.BlockSpec((cp, block_n), lambda i, j: (0, j)),
        ]
        inputs += [oh_cell, adj_srv]

    out = pl.pallas_call(
        functools.partial(
            _kernel, has_switch=has_switch, has_resident=has_resident,
            has_cells=has_cells, has_spill=has_spill,
            cloud_cell=cloud_cell, out_dtype=out_dtype,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), out_dtype),
        interpret=interpret,
        name="route_score",  # the kernel's name in HLO and device traces
    )(*inputs)
    return out[:b, :n]
