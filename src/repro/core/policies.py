"""Serving-side policy subsystem: trained MADDPG-MATO actors (and the
drain-aware greedy) behind ``route_batch``.

This module closes the loop between the four layers that previously never
touched: **training** (``core.maddpg`` / ``core.networks``),
**checkpointing** (``checkpoint.checkpointer``), the **batched router**
(``core.batch_router``) and the **serving driver** (``launch.serve``).
A checkpoint written by ``save_actor_checkpoint`` after a training run is
restored into a traceable policy callable that plugs straight into
``route_batch(policy=<callable>)`` — one jitted call still routes the
whole fleet.

Observation bridge (the heart of the subsystem)
-----------------------------------------------
A MADDPG-MATO actor was trained on the environment's per-agent eq. 16
observation (``core.env.observe``)::

    [ type one-hot K | x | rho | f_es N | compat N | own xy | es xy*N | cc xy | f_ed ]

The router carries a different native layout (``[resident, queue, flops]``
per server), so ``make_actor_policy`` rebuilds the eq. 16 row per request
from the fleet state the router already threads through its scan:

* ``type one-hot``   <- the request's tagged model index;
* ``x``              <- ``prompt_bits`` (the task payload);
* ``rho``            <- ``gen_tokens * flops_per_token / prompt_bits``
  (the request's compute density in FLOPs/bit, the serving analogue of
  the env's cycles/bit);
* ``f_es``           <- the candidate servers' ``flops_per_s``;
* ``compat``         <- live residency of the tagged model, **cell-masked**
  exactly like ``env.observe`` (out-of-cell servers read 0);
* positions / f_ed   <- static ``ObsDefaults`` (a serving fleet has no
  geometry; the defaults sit mid-distribution of the env's samplers).

Multi-cell transfer: ``cell_index_map`` precomputes, per request cell,
WHICH flat fleet columns the actor observes (and acts over):

* a policy trained at ``num_cells == 1`` with N servers serves a C-cell
  fleet of N servers per cell unchanged — each cell's servers are
  gathered into the actor's N observation slots;
* a policy trained at ``num_cells == C`` over N total servers serves the
  matching C-cell fleet — the actor sees all N servers with the compat
  columns cell-masked, exactly as during training.

Cloud-fallback columns (``CLOUD_CELL``) are never offered to the actor:
its action space is the env's {local, ES 1..N}, which has no cloud slot.
The actor's chosen ES maps back to a flat server index; serving always
places the request, so the ``local`` head is skipped.

Full eq. 16 action space
------------------------
The actor's output row is ``[target logits (N+1) | eta | beta]``. The
in-scan policy resolves the TARGET head live (residency drifts inside a
window); the continuous ``eta`` (partial-offload ratio, sigmoid as in
``maddpg._split_heads``) and binary ``beta`` (download decision,
``sigmoid > 0.5`` as executed by ``maddpg.policy_action``) must be
priced into the score matrix BEFORE routing, so
``actor_action_columns`` evaluates them once per window against the
window-entry residency snapshot and returns ``RequestBatch.eta`` /
``.beta`` columns. ``route_batch(..., actor=policy)`` plus those
columns serves the complete eq. 16 action ``(target, eta, beta)`` —
nothing from the trained head row is discarded anymore.

Checkpoint contract
-------------------
``save_actor_checkpoint`` stores the stacked actor pytree through the
atomic ``checkpoint.checkpointer`` and records the observation geometry
(``ObsSpec``) plus ``num_eds``/``hidden``/``model_aware`` in the manifest's
``extra`` dict, so ``load_actor_checkpoint`` can rebuild the parameter
template and the obs bridge with no side channel. ``launch.serve
--policy actor:<ckpt_dir>`` is exactly this restore path.
"""
from __future__ import annotations

import copy
import json
from pathlib import Path
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpointer
from repro.core import networks
from repro.core.router import CLOUD_CELL, ModelAwareRouter
from repro.core.types import MB_TO_BITS


class ObsSpec(NamedTuple):
    """Static geometry + normalisers of the eq. 16 observation the actor
    was trained on (everything ``build_obs`` needs, nothing else)."""

    num_models: int     # K — catalogue size == task types
    num_ess: int        # N — servers per actor decision (training fleet)
    num_cells: int      # C — training cell topology
    task_bits_hi: float  # x normaliser (env: task_mb_hi * MB_TO_BITS)
    rho_hi: float       # compute-density normaliser
    f_cc: float         # ES-capacity normaliser
    f_ed_hi: float      # device-capacity normaliser
    area_m: float       # position normaliser


def spec_from_env(p) -> ObsSpec:
    """ObsSpec of an ``EnvParams`` training setup."""
    return ObsSpec(
        num_models=p.num_models,
        num_ess=p.num_ess,
        num_cells=p.num_cells,
        task_bits_hi=p.task_mb_hi * MB_TO_BITS,
        rho_hi=p.rho_hi,
        f_cc=p.f_cc,
        f_ed_hi=p.f_ed_hi,
        area_m=p.area_m,
    )


def obs_dim(spec: ObsSpec) -> int:
    """Must equal ``env.obs_dim`` for the matching EnvParams (tested)."""
    return spec.num_models + 2 + 4 * spec.num_ess + 2 + 2 + 1


class ObsDefaults(NamedTuple):
    """Static stand-ins for the obs fields a serving fleet does not model
    (geometry, device capacity). Values sit mid-distribution of the env's
    samplers so a trained actor stays in-distribution."""

    ed_pos: jnp.ndarray   # (2,)
    es_pos: jnp.ndarray   # (n_es, 2)
    cc_pos: jnp.ndarray   # (2,)
    f_ed: jnp.ndarray     # ()


def default_obs_defaults(spec: ObsSpec) -> ObsDefaults:
    """Deterministic placement: ED at the area centre, ESs evenly spaced
    across the mid row, CC at the origin (as in ``env.reset``), device
    capacity at the env sampler's mean (U[f_lo, f_hi] with f_lo ~ hi/3)."""
    n = spec.num_ess
    xs = (jnp.arange(n, dtype=jnp.float32) + 1.0) / (n + 1.0) * spec.area_m
    es_pos = jnp.stack([xs, jnp.full((n,), 0.5 * spec.area_m)], axis=-1)
    return ObsDefaults(
        ed_pos=jnp.full((2,), 0.5 * spec.area_m),
        es_pos=es_pos,
        cc_pos=jnp.zeros((2,)),
        f_ed=jnp.asarray(2.0 / 3.0 * spec.f_ed_hi),
    )


def build_obs(spec: ObsSpec, *, model, x_bits, rho, f_es, compat,
              ed_pos, es_pos, cc_pos, f_ed) -> jnp.ndarray:
    """One eq. 16 observation row, field for field ``env.observe``'s layout.

    ``model``/``x_bits``/``rho``/``f_ed`` are scalars, ``f_es``/``compat``
    are (N,), positions are (2,)/(N, 2). The caller supplies ``compat``
    already cell-masked (see ``env.observe`` / ``make_actor_policy``).

    The per-request features (``x``, ``rho``) and the per-server
    capacity column (``f_es``) are clipped into the unit interval the
    actor saw during training: serving requests carry compute densities
    orders of magnitude beyond the env's ``rho_hi`` (decode FLOPs/token
    dwarf cycles/bit) and serving servers can out-muscle the training
    cloud's ``f_cc`` (the env's capacity normaliser), and unclipped
    either saturates the MLP and drowns the 0/1 compat signal. Inside
    the training ranges the clips are the identity, so this stays
    field-for-field ``env.observe``."""
    type_onehot = jax.nn.one_hot(model, spec.num_models)
    scalars = jnp.clip(jnp.stack([
        x_bits / spec.task_bits_hi,
        rho / spec.rho_hi,
    ]), 0.0, 1.0)
    return jnp.concatenate([
        type_onehot,
        scalars,
        jnp.clip(jnp.asarray(f_es) / spec.f_cc, 0.0, 1.0),
        jnp.asarray(compat, type_onehot.dtype),
        jnp.asarray(ed_pos) / spec.area_m,
        (jnp.asarray(es_pos) / spec.area_m).reshape(-1),
        jnp.asarray(cc_pos) / spec.area_m,
        jnp.asarray(f_ed)[None] / spec.f_ed_hi,
    ])


def cell_index_map(spec: ObsSpec, fleet_cell) -> tuple[np.ndarray, np.ndarray]:
    """Host-side (C, N) gather maps: which flat fleet columns the actor
    observes for a request in each cell.

    Returns ``(index_map, col_cell)`` — row ``c`` of ``index_map`` lists
    the server indices offered to cell-``c`` requests, ``col_cell`` their
    cell ids (for the env-style compat mask). Cloud columns
    (``CLOUD_CELL``) are excluded: the actor's action space has no cloud
    slot. Supported topologies:

    * trained single-cell (``spec.num_cells == 1``): every serving cell
      must hold exactly ``spec.num_ess`` edge servers; row ``c`` gathers
      cell ``c``'s servers;
    * matched topology (``spec.num_cells`` == serving cells, fleet-wide
      ``spec.num_ess`` edge servers total): every row is the full edge
      fleet, compat cell-masked exactly as in training.
    """
    cell = np.asarray(fleet_cell, np.int32)
    edge_idx = np.nonzero(cell != CLOUD_CELL)[0]
    cells = sorted(set(int(c) for c in cell[edge_idx]))
    if cells != list(range(len(cells))):
        raise ValueError(f"edge cell ids must be 0..C-1, got {cells}")
    n_cells = max(len(cells), 1)
    if spec.num_cells == n_cells and len(edge_idx) == spec.num_ess:
        rows = np.tile(edge_idx, (n_cells, 1))
    elif spec.num_cells == 1:
        rows = []
        for c in range(n_cells):
            members = edge_idx[cell[edge_idx] == c]
            if len(members) != spec.num_ess:
                raise ValueError(
                    f"cell {c} has {len(members)} edge servers; the actor "
                    f"was trained on num_ess={spec.num_ess}"
                )
            rows.append(members)
        rows = np.stack(rows)
    else:
        raise ValueError(
            f"cannot map an actor trained at num_cells={spec.num_cells}, "
            f"num_ess={spec.num_ess} onto a fleet with {n_cells} cells and "
            f"{len(edge_idx)} edge servers"
        )
    return rows.astype(np.int32), cell[rows]


#: Matmul precision of the served actor MLP. The TPU's default for
#: float32 matmuls is one bfloat16 pass, which keeps about three decimal
#: digits of the logits, so argmax decisions could differ from the CPU
#: oracle's; full float32 keeps the chip's choices equal to the CPU's.
SERVE_PRECISION = jax.lax.Precision.HIGHEST


def _agent_slice(stacked, agent: int):
    """One agent's MLP from the stacked (leading-axis) actor pytree."""
    return jax.tree.map(lambda x: jnp.asarray(x)[agent], stacked)


# ``optimization_barrier`` has no batching rule on this JAX, so the bare
# primitive breaks the mesh router's per-cell vmap; custom_vmap makes the
# barrier commute with vmap (it is the identity on values either way).
@jax.custom_batching.custom_vmap
def _fusion_barrier(x):
    return jax.lax.optimization_barrier(x)


@_fusion_barrier.def_vmap
def _fusion_barrier_vmap(axis_size, in_batched, x):
    return jax.lax.optimization_barrier(x), in_batched[0]


def make_actor_policy(actor_params, spec: ObsSpec, fleet_params, *,
                      agent: int = 0, defaults: Optional[ObsDefaults] = None,
                      model_aware: bool = True):
    """Turn (restored) stacked actor params into a ``route_batch`` policy.

    The returned callable follows the router's policy dispatch contract
    with ``needs_ctx = True`` (see ``core.batch_router``): per request it
    receives a ``PolicyCtx``, rebuilds the eq. 16 observation from the
    live fleet state, runs agent ``agent``'s MLP head and maps the argmax
    offload target back to a flat server index. Fully traceable — it runs
    inside the routing scan unchanged.
    """
    n_fleet = np.asarray(fleet_params.flops_per_s).shape[0]
    fleet_cell = (
        fleet_params.cell if fleet_params.cell is not None
        else np.zeros((n_fleet,), np.int32)
    )
    rows, row_cells = cell_index_map(spec, fleet_cell)
    index_map = jnp.asarray(rows)          # (C, N) flat server columns
    col_cell = jnp.asarray(row_cells)      # (C, N) their cell ids
    mlp = _agent_slice(actor_params, agent)
    dflt = defaults if defaults is not None else default_obs_defaults(spec)

    def _live_compat(ctx):
        c = jnp.int32(0) if ctx.cell is None else ctx.cell
        idx = index_map[c]                                   # (N,)
        # live residency of the tagged model, cell-masked like env.observe
        compat = ctx.resident[idx] & (col_cell[c] == c)
        if not model_aware:  # MADDPG-NoModel never sees the compat map
            compat = jnp.zeros_like(compat)
        return idx, compat

    def _decide(ctx):
        idx, compat = _live_compat(ctx)
        o = build_obs(
            spec,
            model=ctx.model,
            x_bits=ctx.prompt_bits,
            rho=ctx.gen_tokens * ctx.flops_tok / ctx.prompt_bits,
            f_es=ctx.params.flops_per_s[idx],
            compat=compat,
            ed_pos=dflt.ed_pos, es_pos=dflt.es_pos, cc_pos=dflt.cc_pos,
            f_ed=dflt.f_ed,
        )
        out = networks.mlp_apply(mlp, o, precision=SERVE_PRECISION)
        # head layout: [target logits (N+1) | eta | beta]; slot 0 is
        # "compute locally", which a routed request cannot do — serving
        # always places the request on the best ES head
        target = jnp.argmax(out[1: spec.num_ess + 1])
        return idx[target]

    def policy(lats, obs, queue, ctx):
        return _decide(ctx)

    n_ess = spec.num_ess
    # radius-1 compat variants: chunk-entry row + every single-bit flip.
    # MADDPG-NoModel's compat is identically zero — one variant suffices.
    flips = (np.concatenate([np.zeros((1, n_ess)), np.eye(n_ess)]) != 0
             if model_aware else np.zeros((1, n_ess), bool))
    flips = jnp.asarray(flips)                           # (V, N)

    def _obs_rows(cctx, idx, compat):
        """Batched eq. 16 observation build; ``compat`` may carry extra
        leading axes beyond the chunk axis (the variant axis below)."""
        row = lambda model, x_bits, rho, f_es, cm: build_obs(
            spec, model=model, x_bits=x_bits, rho=rho, f_es=f_es,
            compat=cm, ed_pos=dflt.ed_pos, es_pos=dflt.es_pos,
            cc_pos=dflt.cc_pos, f_ed=dflt.f_ed,
        )
        for _ in range(compat.ndim - 2):  # map the variant axis too
            row = jax.vmap(row, in_axes=(None, None, None, None, 0))
        return jax.vmap(row)(
            cctx.model, cctx.prompt_bits,
            cctx.gen_tokens * cctx.flops_tok / cctx.prompt_bits,
            cctx.params.flops_per_s[idx], compat)

    def chunk_precompute(cctx):
        """Chunk-level hook (``core.batch_router``): batch the eq. 16
        observation build AND the actor MLP over the whole chunk — one
        MXU contraction instead of c per-request matvecs.

        The actor reads the live fleet state ONLY through the n-bit
        compat row, and inside one chunk that row almost never drifts
        more than one bit from its chunk-entry value (a drift means some
        earlier request in the chunk loaded/evicted THIS request's
        tagged model inside THIS request's cell). So we price n+1
        residency variants per request — the entry row plus every
        single-bit flip — and the per-step hook becomes a table lookup;
        only a multi-bit drift replays the full per-request decision."""
        cells = (jnp.zeros_like(cctx.model) if cctx.cell is None
                 else cctx.cell)
        idx = index_map[cells]                               # (c, N)
        cell_ok = col_cell[cells] == cells[:, None]          # (c, N)
        # chunk-entry residency of each request's tagged model
        entry = jnp.take_along_axis(
            cctx.resident.T[cctx.model], idx, axis=1) & cell_ok
        if not model_aware:
            entry = jnp.zeros_like(entry)
        # live compat stays inside the cell mask, so masked flip
        # variants are unreachable duplicates — harmless
        compat = (entry[:, None, :] ^ flips[None, :, :]) \
            & cell_ok[:, None, :]                            # (c, V, N)
        # barrier: keep the concat-built obs rows OUT of the matmul
        # fusion — fused, XLA lowers the contraction as a loop nest
        # instead of one gemm call (measured ~4x slower end to end)
        rows = _fusion_barrier(_obs_rows(cctx, idx, compat))
        out = networks.mlp_apply(mlp, rows, precision=SERVE_PRECISION)
        target = jnp.argmax(out[..., 1: n_ess + 1], axis=-1)  # (c, V)
        choice = jnp.take_along_axis(idx, target, axis=1)    # (c, V)
        # idx/cell_ok ride along so the per-step resolve skips the
        # (state-independent) index_map/col_cell gathers
        return choice, entry, idx, cell_ok

    def chunk_apply(aux_b, ctx):
        """Resolve one request from its precomputed decisions: index the
        variant table by how the live compat row differs from the
        chunk-entry row it was priced against (0 bits -> entry variant,
        1 bit -> that flip's variant). A >=2-bit drift — rare, the
        chunk must churn the same (cell, model) residency row twice
        before this request's turn — is reported as inexact and the
        router replays the chunk through the per-request path."""
        table_b, entry, idx, cell_ok = aux_b
        compat = ctx.resident[idx] & cell_ok
        if not model_aware:
            compat = jnp.zeros_like(compat)
        diff = compat != entry
        d = jnp.sum(diff)
        k = jnp.where(d == 0, 0, 1 + jnp.argmax(diff)).astype(jnp.int32)
        return table_b[jnp.minimum(k, table_b.shape[0] - 1)], d <= 1

    policy.needs_obs = False
    policy.needs_ctx = True
    policy.chunk_precompute = chunk_precompute
    policy.chunk_apply = chunk_apply
    return policy


def actor_action_columns(actor_params, spec: ObsSpec, fleet_params, state,
                         reqs, *, agent: int = 0,
                         defaults: Optional[ObsDefaults] = None,
                         model_aware: bool = True):
    """Evaluate the actor's eta/beta heads for one request window.

    The eq. 16 action is ``(target, eta, beta)``; ``make_actor_policy``
    resolves the target head inside the routing scan, but the offload
    ratio and the download decision reshape the score matrix itself
    (eq. 5/9 scaling, eq. 7 gating) and so must be fixed per request
    BEFORE routing. This evaluates agent ``agent``'s MLP once over the
    window — same observation bridge as the in-scan policy, residency
    read from the WINDOW-ENTRY ``state`` — and squashes the two trailing
    heads exactly as training executes them (``maddpg.policy_action``
    sans exploration): ``eta = sigmoid``, ``beta = sigmoid(.) > 0.5``,
    beta forced off for MADDPG-NoModel.

    Returns ``(eta, beta)`` ready for ``RequestBatch``; route with::

        eta, beta = actor_action_columns(params, spec, fp, state, reqs)
        reqs = reqs._replace(eta=eta, beta=beta)
        route_batch(fp, state, reqs, policy=actor_policy)
    """
    n_fleet = np.asarray(fleet_params.flops_per_s).shape[0]
    fleet_cell = (
        fleet_params.cell if fleet_params.cell is not None
        else np.zeros((n_fleet,), np.int32)
    )
    rows, row_cells = cell_index_map(spec, fleet_cell)
    index_map = jnp.asarray(rows)
    col_cell = jnp.asarray(row_cells)
    mlp = _agent_slice(actor_params, agent)
    dflt = defaults if defaults is not None else default_obs_defaults(spec)

    model = jnp.asarray(reqs.model)
    cells = jnp.zeros_like(model) if reqs.cell is None else reqs.cell
    idx = index_map[cells]                                   # (B, N)
    cell_ok = col_cell[cells] == cells[:, None]              # (B, N)
    resident = jnp.asarray(state.resident)
    compat = jnp.take_along_axis(resident.T[model], idx, axis=1) & cell_ok
    if not model_aware:
        compat = jnp.zeros_like(compat)
    flops_tok = jnp.asarray(fleet_params.decode_flops_per_token)[model]
    row = lambda m, x, r, f, cm: build_obs(
        spec, model=m, x_bits=x, rho=r, f_es=f, compat=cm,
        ed_pos=dflt.ed_pos, es_pos=dflt.es_pos, cc_pos=dflt.cc_pos,
        f_ed=dflt.f_ed,
    )
    obs = jax.vmap(row)(
        model, reqs.prompt_bits,
        reqs.gen_tokens * flops_tok / reqs.prompt_bits,
        jnp.asarray(fleet_params.flops_per_s)[idx], compat,
    )
    out = networks.mlp_apply(mlp, obs,
                             precision=SERVE_PRECISION)  # (B, N+3)
    eta = jax.nn.sigmoid(out[..., spec.num_ess + 1])
    beta = jax.nn.sigmoid(out[..., spec.num_ess + 2]) > 0.5
    if not model_aware:  # download action forced off, as in training
        beta = jnp.zeros_like(beta)
    return eta, beta


# ---------------------------------------------------------------------------
# checkpoint round-trip
# ---------------------------------------------------------------------------
def save_actor_checkpoint(ckpt_dir, actor_params, p, cfg, *, step: int = 0,
                          keep: int = 3) -> Path:
    """Persist trained actor params + the obs geometry needed to serve them.

    ``p`` is the training ``EnvParams``, ``cfg`` the ``AlgoConfig``; both
    are reduced to plain scalars in the manifest's ``extra`` dict so the
    restore side needs no pickle and no source-of-truth beyond the
    checkpoint directory."""
    spec = spec_from_env(p)
    num_eds = int(np.asarray(jax.tree.leaves(actor_params)[0]).shape[0])
    extra = {
        "kind": "maddpg-actor",
        "num_eds": num_eds,
        "hidden": int(cfg.hidden),
        "model_aware": bool(cfg.model_aware),
        "spec": {k: (int(v) if isinstance(v, int) else float(v))
                 for k, v in spec._asdict().items()},
    }
    return checkpointer.save(ckpt_dir, step, actor_params, keep=keep,
                             extra=extra)


def load_actor_checkpoint(ckpt_dir, step: Optional[int] = None):
    """Restore ``(actor_params, ObsSpec, extra)`` from a checkpoint dir.

    The parameter template is rebuilt from the manifest metadata
    (``num_eds`` x MLP sizes), so this works in a fresh process with no
    access to the original ``EnvParams``/``AlgoConfig`` objects."""
    if step is None:
        step = checkpointer.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint under {ckpt_dir}")
    manifest = json.loads(
        (Path(ckpt_dir) / f"step_{step}" / "manifest.json").read_text()
    )
    extra = manifest["extra"]
    if extra.get("kind") != "maddpg-actor":
        raise ValueError(f"{ckpt_dir} step {step} is not an actor checkpoint")
    spec = ObsSpec(**extra["spec"])
    sizes = [obs_dim(spec), extra["hidden"], extra["hidden"],
             spec.num_ess + 1 + 2]
    like = networks.stacked_init(jax.random.key(0), extra["num_eds"], sizes)
    params, extra = checkpointer.restore(ckpt_dir, step, like)
    return params, spec, extra


def load_actor_policy(ckpt_dir, fleet_params, *, step: Optional[int] = None,
                      agent: int = 0):
    """One-call serve path: checkpoint dir -> ``route_batch`` policy."""
    params, spec, extra = load_actor_checkpoint(ckpt_dir, step)
    return make_actor_policy(
        params, spec, fleet_params, agent=agent,
        model_aware=extra.get("model_aware", True),
    )


def actor_policy_for_cell_blocks(actor_params, spec: ObsSpec, fleet_params,
                                 **kwargs):
    """Actor policy for the cell-major sharded router: ONE policy closure
    that serves EVERY cell block of ``core.mesh_router.route_batch_sharded``.

    Under the mesh the per-request ``PolicyCtx`` carries a LOCAL view — a
    single cell's server block (relabelled cell 0) plus the shared cloud
    columns — so the flat index map baked by ``make_actor_policy`` must be
    built against that local geometry, not the global fleet. Since the
    actor reads the fleet ONLY through live ctx values (residency, queue,
    flops all flow through ``PolicyCtx``; ``fleet_params`` fixes nothing
    but index geometry), the closure built on block 0's template is
    bitwise-correct for every other equal-size block too.

    Requires a single-cell-trained actor (``spec.num_cells == 1``) whose
    ``spec.num_ess`` matches the fleet's per-cell block size — the only
    topology where all blocks share one index map. The matched-topology
    mode of ``cell_index_map`` (actor sees ALL cells at once) cannot be
    served from per-cell shards; route those fleets unsharded.
    """
    from repro.core import batch_router as br

    layout = br.cell_layout(fleet_params)
    if spec.num_cells != 1:
        raise ValueError(
            f"sharded serving needs a single-cell-trained actor "
            f"(spec.num_cells == 1, one index map shared by every block); "
            f"got num_cells={spec.num_cells} — route this fleet unsharded"
        )
    if spec.num_ess != layout.per_cell:
        raise ValueError(
            f"actor was trained on num_ess={spec.num_ess} edge servers but "
            f"the fleet's cell blocks hold {layout.per_cell}"
        )
    local = br.local_block_params(fleet_params, layout, 0)
    return make_actor_policy(actor_params, spec, local, **kwargs)


# ---------------------------------------------------------------------------
# policy evaluation: drain-corrected realized latency
# ---------------------------------------------------------------------------
def drain_corrected_latencies(servers, catalog, requests, choices):
    """Reprice a routed stream under the drain-corrected cost model.

    The eq. 11 latency ``route_batch`` reports prices the queue backlog
    as pure compute (eq. 9) — a BIASED estimate whenever the fleet has a
    continuous ``drain_rate``, because the simulated queues genuinely
    decay between arrivals. This replays ``(requests, choices)`` through
    the scalar oracle (same commits, same wall clock) but records each
    request's latency with the backlog term discounted the way the drain
    policy prices it (``q*ftok/(f + r*ftok)``): the model-consistent
    realized latency. Comparing policies on THIS number is the fair
    fight — on raw eq. 11, greedy is the argmin of the metric itself.

    Requests carrying the eq. 16 knobs replay them: ``eta`` scales the
    edge share inside ``_candidate_latency`` and the recorded number is
    the eq. 13 max with the device's retained share (``_local_latency``
    is 0.0 for knob-free requests, so full-offload streams are priced
    exactly as before).

    ``choices`` must be feasible (no ``-1`` rejections). Returns a float
    list aligned with ``requests``.
    """
    script = iter(int(c) for c in choices)
    router = ModelAwareRouter(copy.deepcopy(servers), catalog,
                              policy="actor",
                              actor=lambda obs, lats: next(script))
    corrected = []
    for req, choice in zip(requests, choices):
        if choice < 0:
            raise ValueError("drain_corrected_latencies needs feasible "
                             "choices (got a rejection)")
        if req.arrival_s is not None:  # idempotent: route() advances again
            router.advance_time(req.arrival_s)
        srv = router.servers[int(choice)]
        lat = router._candidate_latency(srv, req)
        corrected.append(max(router._local_latency(req),
                             router._drain_score(srv, req, lat)))
        router.route(req)
    return corrected
