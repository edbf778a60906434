"""Batched fleet-scale request router — the paper's technique, jitted.

``core.router.ModelAwareRouter`` routes ONE request at a time through
Python dataclass mutation; it stays as the readable reference oracle.
This module is the production path: a whole batch of tagged generation
requests is dispatched across the server fleet in ONE jitted call.

Design
------
* **Array-resident fleet state** (``FleetState``): residency masks and
  LRU clocks as ``(N, K)`` arrays, queue depths as ``(N,)`` — no Python
  objects survive into the hot path.
* **Fused scoring kernel** (``score_matrix``): the paper's cost terms —
  transmission (eq. 5), model switch (eq. 7), FIFO-fair compute (eq. 9)
  — evaluated for ALL request x server pairs at once as a ``(B, N)``
  matrix. The arithmetic lives in ``core.costs.edge_score_matrix``; the
  contraction dispatches through ``kernels.ops.route_score`` to either
  the XLA reference (``backend="xla"``) or the tiled Pallas kernel
  (``kernels/route_score.py``, ``backend="pallas"`` /
  ``"pallas-interpret"``). ``backend=None`` reads the
  ``REPRO_ROUTER_BACKEND`` env knob (default ``"xla"``).
* **Sequential-commit semantics** (``route_batch``): requests within a
  batch still contend for queues and caches, so commits are applied in
  arrival order by a ``lax.scan`` whose per-step work is vectorised over
  the fleet. This reproduces the scalar router *exactly* — including
  LRU tie-breaking, which is preserved by encoding each initial
  resident's list position as a distinct negative clock (the scalar
  oracle breaks last-use ties by list order).
* **Chunked two-phase commit** (``route_batch(..., chunk=c)``): the
  serial region shrinks from B full scoring steps to B cheap correction
  steps. Phase 1 scores a whole chunk of ``c`` requests with one fused
  kernel call — the *switch-free base* ``t_trans + work/flops`` plus
  the cell mask, all state-independent. Phase 2 is a slimmed scan that
  only re-derives the state-dependent residue per step, from two
  per-request SCALARS (the model's size and FLOPs/token) against
  per-server constants:

      lats = base + where(resident[:, model], 0, size/backhaul)
                  + (queue * flops_tok)/flops

  i.e. the residency gate, the queue-backlog drift and the wall-clock
  drain — one fused elementwise chain; no transmission term, no cell
  compare, and no per-step (B, N) rows beyond the base left in the
  serial region. Integer decisions (choices, LRU
  evictions, residency, queues, fleet clock) stay bit-identical to the
  scalar oracle; reported latencies agree to a few ulps (the re-
  association of eq. 9 — ``q*ftok/f + w/f`` vs ``(q*ftok + w)/f`` —
  rounds differently). ``chunk=None`` (default) keeps the single-scan
  path whose latencies are bit-exact against the oracle.
* **Speculative parallel commit** (``route_batch(..., chunk=c)``, the
  default ``speculative=True``, greedy policy): phase 1 prices the whole
  chunk against the CHUNK-ENTRY residency (the fused kernel call gains
  the residency gate), so each request's provisional argmin depends on
  the fleet state only through the queue vector. A commit can invalidate
  a later provisional decision only by CHANGING a score it read —
  queue growth is carried exactly by a slimmed scan whose whole body is
  ``argmin(base + queue*qcoef)`` plus one masked add (one Pallas kernel
  call per chunk on the Pallas backends, ``kernels/route_spec_scan.py``;
  a ``lax.scan`` on ``"xla"``), and the only
  residency-mutating commits are misses (installs/evictions). Every
  decision up to the first committed miss is therefore the oracle
  decision; their LRU bookkeeping (hits only touch last-use clocks,
  which no score reads) is applied in ONE vectorised scatter, and the
  conflicting suffix from the first miss onward is replayed serially
  through the correction scan's own per-request step. Steady-state
  serving (hit rate near 1) commits whole chunks speculatively; cold
  caches degrade gracefully to the serial correction scan. Decisions
  and fleet state remain bit-identical to the scalar oracle;
  ``speculative=False`` forces the plain correction scan (the A/B
  baseline ``benchmarks/router_throughput.py`` records).
* **Pluggable policies**: ``greedy`` (argmin of the eq. 11 latency),
  ``drain`` (drain-aware greedy: the queue backlog is discounted by the
  server's ``drain_rate`` before eq. 9 pricing), ``actor`` (a trained
  MADDPG actor called with the same observation layout the scalar router
  exposes — restored checkpoints plug in via ``core.policies``),
  ``load`` (least-loaded server, switch-blind — a fleet-level baseline).

Policy dispatch contract
------------------------
A policy is any traceable callable ``policy_fn(lats, obs, queue) ->
server index`` evaluated once per request inside the routing scan:

* ``lats``  — (N,) eq. 11 latencies against the CURRENT fleet state,
  ``+inf`` on servers outside the request's cell;
* ``obs``   — (3N,) scalar-router observation (``[resident, queue,
  flops]`` per server), or ``None`` if the policy sets ``needs_obs =
  False`` (saves building it in the compiled scan);
* ``queue`` — (N,) queue depths, ``+inf``-masked like ``lats``.

Two opt-in attributes refine the contract:

* ``needs_obs`` (default True) — set False to skip the obs build;
* ``needs_ctx`` (default False) — set True to be called as
  ``policy_fn(lats, obs, queue, ctx)`` with a per-request ``PolicyCtx``
  (fleet params, tagged model, prompt/gen scalars, raw queues, the
  model's residency row and the request cell). ``core.policies`` builds
  the trained-actor policy on exactly this hook.

Chunk-level hook (the batched-actor fast path): a ``needs_ctx`` policy
may additionally define the attribute pair

* ``chunk_precompute(cctx: ChunkPolicyCtx) -> aux`` — called once per
  chunk (chunked path only) with the whole chunk's request columns and
  the CHUNK-ENTRY residency; returns any pytree of ``(c, ...)`` arrays
  (e.g. MLP decisions batched over the chunk on the MXU);
* ``chunk_apply(aux_b, ctx) -> (server index, exact)`` — called per
  step instead of ``policy_fn`` with that request's ``aux`` slice and
  the live ``PolicyCtx``; it resolves the precomputed table against the
  live state and FLAGS (rather than repairs) drift: ``exact=False``
  on any step makes the router rerun the whole chunk through the plain
  per-request path (one ``lax.cond`` per chunk — a per-step cond would
  tax every iteration of the compiled scan with the expensive branch's
  captured operands, even when never taken).

``core.policies.make_actor_policy`` uses exactly this pair: the MLP is
priced per chunk over the entry compat row plus every single-bit flip
(a radius-1 Hamming-ball table), ``chunk_apply`` is a branch-free table
lookup, and multi-bit residency drift — unobserved in steady serving —
falls back to the exact whole-chunk replay.

Whatever the policy returns is clamped to the request's cell (an
out-of-cell choice falls back to the masked greedy argmin) and committed
with full LRU/queue semantics; out-of-range indices — which a JAX gather
would silently clamp to server N-1 — fall back the same way even on
untopologied fleets, so a policy can never corrupt the fleet state, only
pick worse servers.

Multi-cell fleets
-----------------
Servers carry a ``cell`` id (``FleetParams.cell``) and requests a
``RequestBatch.cell``; the score matrix is masked block-diagonally so a
request only sees the servers of its own cell, plus every server in the
reserved ``CLOUD_CELL`` (-1) — the cloud-fallback column, visible
fleet-wide and priced through the backhaul (its effective uplink folds
the extra hop; see ``launch.serve.make_cloud_server``). One jitted
``route_batch`` call therefore routes an entire multi-cell fleet:
C cells x N servers x B requests, no per-cell Python loop. When
``RequestBatch.cell`` is ``None`` (the default) the mask is compiled
out entirely and the fleet behaves as one cell.

Time-based drain
----------------
Servers complete queued work continuously at ``FleetParams.drain_rate``
tokens/sec. Requests carry a wall-clock ``RequestBatch.arrival_s``; the
scan carry holds the fleet clock ``FleetState.time_s``, and before each
request is scored every queue decays by ``drain_rate * dt`` with ``dt``
the time elapsed since the carry clock last advanced. Queue decay thus
tracks wall clock rather than request count. ``drain_rate == 0`` (or
``arrival_s=None``) reproduces the synchronous behaviour exactly; the
legacy per-request ``drain_tokens`` argument is still honoured. Both
drains are written once, in ``core.queue``, and every commit path (the
scans here, both speculative scan backends and ``core.mesh_router``'s
window-close replays) calls them.

``launch/serve.py`` exposes all of this end to end (``--policy
{greedy,load,drain,actor:<ckpt>}``); ``docs/serving.md`` is the guide.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import costs
from repro.core.queue import advance_to_arrival, drain_after_commit
from repro.core.router import (
    CAUSE_ADMISSION, CAUSE_COMPLETED, CAUSE_INFEASIBLE, CAUSE_OUTAGE,
    CLOUD_CELL,
)
from repro.kernels import ops

_NEVER_USED = -(2**30)  # last-use clock for models that are not resident

#: Env knob for the scoring backend: "xla" | "pallas" | "pallas-interpret".
BACKEND_ENV = "REPRO_ROUTER_BACKEND"
_BACKENDS = ("xla", "pallas", "pallas-interpret")


def resolve_backend(backend: Optional[str] = None) -> str:
    """``None`` -> ``$REPRO_ROUTER_BACKEND`` (default ``"xla"``)."""
    backend = backend or os.environ.get(BACKEND_ENV, "xla")
    if backend not in _BACKENDS:
        raise ValueError(
            f"unknown router backend {backend!r}; expected one of {_BACKENDS}"
        )
    return backend


class FleetParams(NamedTuple):
    """Static per-server capabilities + per-model catalogue columns."""

    flops_per_s: jnp.ndarray          # (N,)
    uplink_bps: jnp.ndarray           # (N,)
    backhaul_bps: jnp.ndarray         # (N,)
    cache_slots: jnp.ndarray          # (N,) int32
    size_bits: jnp.ndarray            # (K,) model weights over the backhaul
    decode_flops_per_token: jnp.ndarray  # (K,)
    cell: Optional[jnp.ndarray] = None        # (N,) int32 cell id; CLOUD_CELL
    drain_rate: Optional[jnp.ndarray] = None  # (N,) tokens/sec drained
    #: (C, C) bool neighbour-cell adjacency: ``spill[rc, sc]`` makes cell
    #: ``sc``'s servers visible to cell ``rc``'s requests at a backhaul
    #: surcharge (``prompt_bits / backhaul_bps`` — the prompt crosses the
    #: inter-cell link). ``None`` compiles the spill column out.
    spill: Optional[jnp.ndarray] = None


class FleetState(NamedTuple):
    """Mutable routing state, one array per concern."""

    resident: jnp.ndarray    # (N, K) bool residency mask
    last_use: jnp.ndarray    # (N, K) int32 LRU clocks
    queue_tokens: jnp.ndarray  # (N,) outstanding decode work, FIFO
    clock: jnp.ndarray       # () int32, increments per routed request
    time_s: Optional[jnp.ndarray] = None  # () wall clock for the time drain


class RequestBatch(NamedTuple):
    """A batch of tagged generation requests (struct-of-arrays).

    ``cell``/``arrival_s`` are optional topology/timing columns: ``None``
    (the default) statically compiles the cell mask / time drain out of
    the scan, preserving the single-cell synchronous fast path.
    """

    model: jnp.ndarray        # (B,) int32 catalogue index
    prompt_bits: jnp.ndarray  # (B,)
    gen_tokens: jnp.ndarray   # (B,)
    cell: Optional[jnp.ndarray] = None       # (B,) int32 requesting cell
    arrival_s: Optional[jnp.ndarray] = None  # (B,) wall-clock arrivals
    #: (B,) per-request SLO deadline in seconds. A request whose BEST
    #: eq. 11 score exceeds its deadline is rejected (admission control);
    #: ``+inf`` entries have no SLO, ``None`` compiles the check out.
    deadline_s: Optional[jnp.ndarray] = None
    #: (B,) eq. 16 offload ratio in [0, 1]: the edge side transmits and
    #: computes the ``eta`` fraction (eq. 5/9 scale), the device keeps
    #: ``1 - eta`` (eq. 3, priced via ``local_flops_per_s``), and the
    #: commit queues only ``eta * gen_tokens``. ``None`` compiles the
    #: knob out — bit-identical to pricing every request at eta = 1.
    eta: Optional[jnp.ndarray] = None
    #: (B,) eq. 16 download decision: ``False`` refuses the eq. 7 model
    #: fetch on a residency miss, so non-resident candidates price
    #: ``+inf`` and a committed request is always a hit. ``None`` (or
    #: ``True``) downloads on miss as before.
    beta: Optional[jnp.ndarray] = None
    #: (B,) requesting device's compute speed for the eq. 3 local share
    #: under partial offload; ``None`` (or entries <= 0) prices the
    #: local side at zero. Only read when ``eta`` is present.
    local_flops_per_s: Optional[jnp.ndarray] = None


class RouteOutcome(NamedTuple):
    choice: jnp.ndarray     # (B,) int32 chosen server; -1 == rejected
    latency: jnp.ndarray    # (B,) predicted eq. 11 latency at choice
    hit: jnp.ndarray        # (B,) bool — model resident at decision time
    #: (B,) int32 rejection cause: CAUSE_COMPLETED (0) for routed
    #: requests, else CAUSE_INFEASIBLE / CAUSE_ADMISSION / CAUSE_OUTAGE
    #: (see ``rejection_cause``). ``None`` only on hand-built outcomes.
    cause: Optional[jnp.ndarray] = None


# ---------------------------------------------------------------------------
# fleet construction
# ---------------------------------------------------------------------------
def make_fleet_params(servers, catalog, spill=None) -> FleetParams:
    """Build array fleet params from ``EdgeServer``s + ``CatalogEntry``s.

    ``spill`` — an optional (C, C) bool neighbour-cell adjacency — lands
    verbatim in ``FleetParams.spill`` (see the field doc)."""
    entries = sorted(catalog, key=lambda e: e.index)
    return FleetParams(
        spill=None if spill is None else jnp.asarray(np.asarray(spill, bool)),
        flops_per_s=jnp.asarray(np.array([s.flops_per_s for s in servers])),
        uplink_bps=jnp.asarray(np.array([s.uplink_bps for s in servers])),
        backhaul_bps=jnp.asarray(np.array([s.backhaul_bps for s in servers])),
        cache_slots=jnp.asarray(
            np.array([s.cache_slots for s in servers], np.int32)
        ),
        size_bits=jnp.asarray(np.array([e.size_bits for e in entries])),
        decode_flops_per_token=jnp.asarray(
            np.array([e.decode_flops_per_token for e in entries])
        ),
        cell=jnp.asarray(
            np.array([getattr(s, "cell", 0) for s in servers], np.int32)
        ),
        drain_rate=jnp.asarray(
            np.array([getattr(s, "drain_rate", 0.0) for s in servers])
        ),
    )


def make_fleet_state(servers, num_models: int, clock: int = 0,
                     time_s: float = 0.0) -> FleetState:
    """Array state mirroring the scalar servers' residency/queues.

    The scalar oracle breaks LRU ties (several never-used residents, all
    ``last_use == -1``) by position in the ``resident`` list; we encode
    position ``i`` of a list of length L as clock ``i - L`` so ties become
    a strict order that an argmin resolves identically."""
    n = len(servers)
    resident = np.zeros((n, num_models), bool)
    last_use = np.full((n, num_models), _NEVER_USED, np.int32)
    for si, s in enumerate(servers):
        for pos, m in enumerate(s.resident):
            resident[si, m] = True
            last_use[si, m] = s.last_use.get(m, pos - len(s.resident))
        for m, t in s.last_use.items():
            last_use[si, m] = t
    queue = np.array([s.queue_tokens for s in servers])
    return FleetState(
        resident=jnp.asarray(resident),
        last_use=jnp.asarray(last_use),
        queue_tokens=jnp.asarray(queue),
        clock=jnp.asarray(clock, jnp.int32),
        time_s=jnp.asarray(time_s, jnp.asarray(queue).dtype),
    )


def fleet_from_servers(servers, catalog, clock: int = 0, time_s: float = 0.0,
                       spill=None):
    """(FleetParams, FleetState) snapshot of a scalar router's fleet.

    ``clock`` must be the scalar router's current clock when snapshotting
    mid-stream (its ``last_use`` values are in [1, clock]; starting the
    batched clock below them would invert LRU order). Fresh fleets use 0.
    ``time_s`` likewise carries the oracle's wall clock (``router.time_s``)
    so the time-based drain resumes from the same instant. ``spill``
    mirrors the oracle's neighbour-cell adjacency.
    """
    return (
        make_fleet_params(servers, catalog, spill=spill),
        make_fleet_state(servers, len(catalog), clock=clock, time_s=time_s),
    )


# ---------------------------------------------------------------------------
# cell-major layout
# ---------------------------------------------------------------------------
class CellLayout(NamedTuple):
    """Block shape of a CELL-MAJOR fleet.

    The canonical multi-cell server ordering (what
    ``launch.serve.make_multicell_fleet`` produces): edge cells
    ``0..C-1`` laid out as equal-size contiguous server blocks, with
    every fleet-wide ``CLOUD_CELL`` column trailing. In this layout each
    cell's slice of ``FleetParams``/``FleetState`` is one contiguous
    block — ``params.flops_per_s[c*n:(c+1)*n]`` etc. — so per-cell state
    is directly reshapeable to ``(C, n, ...)`` and vmappable, which is
    what ``core.mesh_router`` shards over a device mesh."""

    num_cells: int   # C edge cells
    per_cell: int    # n servers in every edge cell block
    num_cloud: int   # trailing CLOUD_CELL servers (shared, fleet-wide)

    @property
    def num_edge(self) -> int:
        return self.num_cells * self.per_cell

    @property
    def num_servers(self) -> int:
        return self.num_edge + self.num_cloud


def cell_major_order(cell) -> np.ndarray:
    """Server permutation into cell-major order: edge cells ascending
    (each keeping its internal order, so per-cell LRU tie-breaks are
    preserved), all ``CLOUD_CELL`` servers last. ``order[i]`` is the OLD
    index landing at new position ``i`` (numpy argsort convention)."""
    cell = np.asarray(cell)
    key = np.where(cell == CLOUD_CELL, np.iinfo(np.int64).max,
                   cell.astype(np.int64))
    return np.argsort(key, kind="stable")


def cell_layout(params: FleetParams) -> CellLayout:
    """Validate that ``params`` is cell-major and return its block shape.

    Requirements: edge cell ids are exactly ``0..C-1``, every cell owns
    the same number of servers in one contiguous ascending block, and
    all ``CLOUD_CELL`` servers trail the edge blocks. Raises
    ``ValueError`` otherwise — ``cell_major_order`` produces the fixing
    permutation (see ``permute_fleet``); unequal cell sizes cannot be
    blocked and need the fleet padded to a common size. An untopologied
    fleet (``params.cell is None``) is one cell with no cloud."""
    if params.cell is None:
        return CellLayout(num_cells=1,
                          per_cell=int(params.flops_per_s.shape[0]),
                          num_cloud=0)
    cell = np.asarray(params.cell)
    n_total = int(cell.shape[0])
    is_cloud = cell == CLOUD_CELL
    num_cloud = int(is_cloud.sum())
    if num_cloud and not is_cloud[n_total - num_cloud:].all():
        raise ValueError(
            "fleet is not cell-major: CLOUD_CELL servers must trail the "
            "edge blocks (apply cell_major_order/permute_fleet)"
        )
    edge = cell[: n_total - num_cloud]
    if edge.size == 0:
        raise ValueError("fleet has no edge servers")
    c = int(edge.max()) + 1
    counts = np.bincount(edge, minlength=c) if edge.min() >= 0 else None
    if counts is None or (counts == 0).any():
        raise ValueError(
            f"edge cell ids must be exactly 0..C-1, got "
            f"{sorted(set(edge.tolist()))}"
        )
    if not (counts == counts[0]).all():
        raise ValueError(
            "cells must be equal-sized for the blocked layout, got "
            f"per-cell counts {counts.tolist()}; pad the fleet"
        )
    per = int(counts[0])
    if not np.array_equal(edge, np.repeat(np.arange(c), per)):
        raise ValueError(
            "edge servers are not grouped into contiguous ascending cell "
            "blocks (apply cell_major_order/permute_fleet)"
        )
    return CellLayout(num_cells=c, per_cell=per, num_cloud=num_cloud)


def permute_fleet(params: FleetParams, state: FleetState, order):
    """Apply a server permutation to every per-server axis of
    ``(params, state)`` — e.g. ``cell_major_order(params.cell)`` to bring
    an arbitrary fleet into the blocked layout. Choices reported against
    the permuted fleet map back through ``order[choice]``. Per-CELL
    arrays (``spill``) ride through unchanged: cell ids are preserved."""
    order = jnp.asarray(np.asarray(order), jnp.int32)
    new_params = params._replace(
        flops_per_s=params.flops_per_s[order],
        uplink_bps=params.uplink_bps[order],
        backhaul_bps=params.backhaul_bps[order],
        cache_slots=params.cache_slots[order],
        cell=None if params.cell is None else params.cell[order],
        drain_rate=(None if params.drain_rate is None
                    else params.drain_rate[order]),
    )
    new_state = state._replace(
        resident=state.resident[order],
        last_use=state.last_use[order],
        queue_tokens=state.queue_tokens[order],
    )
    return new_params, new_state


def local_block_params(params: FleetParams, layout: CellLayout,
                       block: int = 0) -> FleetParams:
    """One cell block's LOCAL fleet view: its ``per_cell`` edge servers
    relabeled to cell 0, plus the shared cloud columns (cell stays
    ``CLOUD_CELL``). Every block shares this geometry, so a policy built
    against the block-0 template (``core.policies.
    actor_policy_for_cell_blocks``) serves all cells under
    ``core.mesh_router.route_batch_sharded``."""
    c, n, nc = layout.num_cells, layout.per_cell, layout.num_cloud
    lo, hi = block * n, (block + 1) * n
    edge_total = c * n

    def take(x):
        blk = x[lo:hi]
        return jnp.concatenate([blk, x[edge_total:edge_total + nc]]) if nc \
            else blk

    local_cell = jnp.asarray(np.concatenate(
        [np.zeros(n, np.int32), np.full(nc, CLOUD_CELL, np.int32)]
    ))
    return params._replace(
        flops_per_s=take(params.flops_per_s),
        uplink_bps=take(params.uplink_bps),
        backhaul_bps=take(params.backhaul_bps),
        cache_slots=take(params.cache_slots),
        cell=local_cell,
        drain_rate=(None if params.drain_rate is None
                    else take(params.drain_rate)),
        # the local view relabels cells to {0, CLOUD_CELL}: the global
        # adjacency is meaningless here (spill fleets take the
        # full-replication sharded path instead)
        spill=None,
    )


# ---------------------------------------------------------------------------
# vectorised scoring
# ---------------------------------------------------------------------------
def _spill_adjacency(params: FleetParams, reqs: RequestBatch):
    """(B, N) bool: server reachable through the neighbour-cell spill
    adjacency (``None`` when the fleet carries no ``spill``). May overlap
    the home cell when the adjacency has a true diagonal — callers that
    price the surcharge must exclude home pairs. Out-of-range cells on
    either side (orphan requests, ``CLOUD_CELL`` servers) never spill."""
    if params.spill is None or params.cell is None or reqs.cell is None:
        return None
    nc = params.spill.shape[0]
    rc, sc = reqs.cell, params.cell
    rok = (rc >= 0) & (rc < nc)
    sok = (sc >= 0) & (sc < nc)
    adj = params.spill[jnp.clip(rc, 0, nc - 1)][:, jnp.clip(sc, 0, nc - 1)]
    return adj & rok[:, None] & sok[None, :]


def cell_mask(params: FleetParams, reqs: RequestBatch):
    """(B, N) block-diagonal visibility mask, or ``None`` when untopologied.

    True where the server is in the request's cell OR in the reserved
    ``CLOUD_CELL`` (the fleet-wide cloud-fallback column) OR reachable
    through the ``FleetParams.spill`` neighbour-cell adjacency. ``None``
    — returned when either side carries no cell ids — means "everything
    visible" and lets callers compile the mask away statically. Any
    batch with a ``cell`` column will do (the scans pass ``_Columns``)."""
    if params.cell is None or reqs.cell is None:
        return None
    visible = (params.cell[None, :] == reqs.cell[:, None]) | (
        params.cell[None, :] == CLOUD_CELL
    )
    adj = _spill_adjacency(params, reqs)
    return visible if adj is None else visible | adj


def score_matrix(params: FleetParams, state: FleetState, reqs: RequestBatch,
                 *, backend: Optional[str] = None):
    """Full (B, N) eq. 11 cost matrix against the CURRENT fleet state.

    One shot over all request x server pairs: eq. 5 transmission +
    eq. 7 switch (gated on residency) + eq. 9 compute against the
    present queue backlog. Out-of-cell pairs score ``+inf`` when the
    batch carries cell ids (block-diagonal mask + cloud column).

    ``backend`` picks the contraction: ``"xla"`` (the reference path,
    arithmetic in ``costs.edge_score_matrix``) or ``"pallas"`` /
    ``"pallas-interpret"`` (the fused ``kernels/route_score.py`` tile
    kernel). ``None`` reads ``$REPRO_ROUTER_BACKEND``. Policy studies,
    admission control, and ``route_batch``'s chunked phase-1 all target
    exactly this contraction. ``reqs.eta``/``reqs.beta`` ride through to
    the backend (eq. 16 partial offload / download refusal); the matrix
    stays EDGE-SIDE — the eq. 3 local share never enters the scores
    (``max`` with it is monotone, so edge argmins are eq. 13 argmins)."""
    backend = resolve_backend(backend)
    flops_tok = params.decode_flops_per_token[reqs.model]
    has_cells = params.cell is not None and reqs.cell is not None
    return ops.route_score(
        reqs.prompt_bits, params.size_bits[reqs.model], flops_tok,
        reqs.gen_tokens * flops_tok,
        params.uplink_bps, params.backhaul_bps, params.flops_per_s,
        queue_tokens=state.queue_tokens, resident=state.resident,
        model=reqs.model,
        req_cell=reqs.cell if has_cells else None,
        srv_cell=params.cell if has_cells else None,
        spill=params.spill if has_cells else None,
        eta=reqs.eta, beta=reqs.beta,
        cloud_cell=CLOUD_CELL, backend=backend,
    )


def rejection_cause(params: FleetParams, reqs: RequestBatch, outage,
                    choice) -> jnp.ndarray:
    """(B,) int32 cause codes for a routed batch, derived POST-HOC.

    Whether a rejection was *structural* never depends on the fleet
    state — only on visibility (cells + spill + cloud) and the outage
    mask — so the channel is a pure function of the routed choices:

    * ``CAUSE_COMPLETED`` (0)  — ``choice >= 0``;
    * ``CAUSE_ADMISSION`` (2)  — some visible server was up, so a finite
      eq. 11 score existed: the request was refused because its best
      score exceeded ``deadline_s`` (SLO admission control);
    * ``CAUSE_OUTAGE``   (3)  — servers were visible but every one of
      them was outaged;
    * ``CAUSE_INFEASIBLE`` (1) — no server was visible at all (empty
      cell with no cloud column).

    Every router path shares this helper, so the per-cause rates in
    ``stats``/``window_stats`` agree bitwise across scan / chunked /
    speculative / sharded."""
    b = reqs.model.shape[0]
    completed = choice >= 0
    vis = cell_mask(params, reqs)
    if vis is None:
        any_vis = jnp.ones((b,), bool)
        any_up = (any_vis if outage is None
                  else jnp.broadcast_to(jnp.any(~outage), (b,)))
    else:
        any_vis = vis.any(axis=1)
        any_up = (any_vis if outage is None
                  else (vis & ~outage[None, :]).any(axis=1))
    rejected = jnp.where(
        any_up, CAUSE_ADMISSION,
        jnp.where(any_vis, CAUSE_OUTAGE, CAUSE_INFEASIBLE),
    )
    return jnp.where(completed, CAUSE_COMPLETED, rejected).astype(jnp.int32)


# ---------------------------------------------------------------------------
# policies: (latencies (N,), obs (3N,), queue (N,)[, ctx]) -> server index
# (full contract in the module docstring)
# ---------------------------------------------------------------------------
class PolicyCtx(NamedTuple):
    """Per-request context handed to policies with ``needs_ctx = True``.

    Everything is as of DECISION time: after the wall-clock queue decay,
    before the commit. ``queue`` is the raw (unmasked) depth vector —
    ``lats`` already carries the cell mask as ``+inf``."""

    params: FleetParams
    model: jnp.ndarray        # () int32 tagged catalogue index
    prompt_bits: jnp.ndarray  # ()
    gen_tokens: jnp.ndarray   # ()
    flops_tok: jnp.ndarray    # () decode FLOPs/token of the tagged model
    resident: jnp.ndarray     # (N,) bool residency of the tagged model
    queue: jnp.ndarray        # (N,) raw queue depths
    cell: Optional[jnp.ndarray] = None  # () int32, None when untopologied


class ChunkPolicyCtx(NamedTuple):
    """Chunk-level context for policies with a ``chunk_precompute`` hook.

    The request columns cover one whole chunk; ``resident`` is the fleet
    residency AT CHUNK ENTRY — decisions precomputed against it are
    provisional, and ``chunk_apply`` must detect drift per request."""

    params: FleetParams
    model: jnp.ndarray        # (c,) int32 tagged catalogue indices
    prompt_bits: jnp.ndarray  # (c,)
    gen_tokens: jnp.ndarray   # (c,)
    flops_tok: jnp.ndarray    # (c,)
    resident: jnp.ndarray     # (N, K) bool chunk-entry residency
    cell: Optional[jnp.ndarray] = None  # (c,) int32, None when untopologied


def _greedy_policy(lats, obs, queue):
    return jnp.argmin(lats)


def _load_policy(lats, obs, queue):
    return jnp.argmin(queue)


def _drain_policy(lats, obs, queue, ctx):
    """Drain-aware greedy: discount the queue backlog by the server's
    continuous ``drain_rate`` before the eq. 9 pricing.

    Eq. 9 prices the backlog as pure compute, ``q * ftok / f``. With a
    continuous drain of ``r`` tokens/sec the backlog is also being
    consumed while the request waits, so the self-consistent wait
    ``t_q = (q - r * t_q) * ftok / f`` solves to

        t_q = q * ftok / (f + r * ftok)

    i.e. the backlog is discounted by ``f / (f + r * ftok)``. The policy
    swaps that term into the eq. 11 score and argmins; the REPORTED
    latency stays the undiscounted eq. 11 value at the chosen server, so
    outcomes remain comparable across policies. ``drain_rate == 0`` (or
    absent) makes the score identical to greedy's."""
    rate = ctx.params.drain_rate
    if rate is None:
        return jnp.argmin(lats)
    f = ctx.params.flops_per_s
    backlog = ctx.queue * ctx.flops_tok
    return jnp.argmin(lats - backlog / f + backlog / (f + rate * ctx.flops_tok))


_greedy_policy.needs_obs = False
_load_policy.needs_obs = False
_drain_policy.needs_obs = False
_drain_policy.needs_ctx = True

#: Builtin argmin policies whose score is +inf exactly where the cell
#: mask is: they can only land out of cell when the whole row is
#: infeasible (-> rejected either way), so the chunked path skips the
#: out-of-cell clamp for them.
_ARGMIN_POLICIES = (_greedy_policy, _load_policy, _drain_policy)


def _make_actor_policy(actor: Callable[[Any, Any], Any]):
    def policy(lats, obs, queue):
        return jnp.asarray(actor(obs, lats), jnp.int32)

    policy.needs_obs = True
    return policy


def _resolve_policy(policy, actor):
    if callable(policy):
        return policy
    if policy == "greedy":
        return _greedy_policy
    if policy == "load":
        return _load_policy
    if policy == "drain":
        return _drain_policy
    if policy == "actor":
        if actor is None:
            raise ValueError("policy='actor' requires an actor callable")
        return _make_actor_policy(actor)
    raise ValueError(f"unknown policy {policy!r}")


# ---------------------------------------------------------------------------
# batched routing with sequential-commit semantics
# ---------------------------------------------------------------------------
class _Plan(NamedTuple):
    """How one router call compiles: the policy (a name or a callable,
    and the actor a name may wrap) and ``route_batch``'s performance
    knobs. Hashable, so a jitted entry point takes it as one static
    argument."""

    policy: Any
    actor: Any
    chunk: Optional[int]
    unroll: int
    backend: str
    speculative: bool


class _Columns(NamedTuple):
    """A call's request columns, prepared once (``_columns``) for either
    commit scan: cast to the fleet's float dtype, padded to whole chunks
    and scaled by the eq. 16 knobs. A ``None`` field is an absent knob
    and compiles out. Every field has the request axis first, so a
    chunk's columns are one reshape of each and a request's one index.
    The field order is the chunk loop's operand order in the compiled
    program."""

    model: jnp.ndarray     # (B,) int32 catalogue index
    #: (B, 3): the committed tokens (eta-scaled), the eq. 7 model size
    #: (+inf where beta refuses the download) and the decode FLOPs a
    #: token, in one strip: a step reads its scalars in one slice
    scal: jnp.ndarray
    prompt: jnp.ndarray    # (B,) prompt bits the edge receives (eta-scaled)
    work: jnp.ndarray      # (B,) decode FLOPs on the edge (eta-scaled)
    drain: Optional[jnp.ndarray]     # (B,) per-request drain tokens
    cell: Optional[jnp.ndarray]      # (B,) int32; None: untopologied
    arrival: Optional[jnp.ndarray]   # (B,); None: no time drain
    valid: Optional[jnp.ndarray]     # (B,) bool; False on padding
    deadline: Optional[jnp.ndarray]  # (B,) SLO admission deadline
    prompt_raw: Optional[jnp.ndarray]  # (B,) unscaled, for policies
    gen_raw: Optional[jnp.ndarray]     # (B,) unscaled, for policies
    tloc: Optional[jnp.ndarray]      # (B,) eq. 3 time of the 1 - eta


def _columns(params, reqs, drain_tokens, dtype, chunk):
    """``reqs`` as ``_Columns``. With a ``chunk`` the batch is padded to
    whole chunks first, so each column is padded before it is scaled;
    padded requests are inert (``valid`` False: no commit, no clock or
    time advance)."""
    b = reqs.model.shape[0]
    pad = 0 if chunk is None else -b % max(1, min(int(chunk), b))

    def pad1(x):
        if not pad:
            return x
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))

    model = pad1(reqs.model)
    prompt = pad1(reqs.prompt_bits.astype(dtype))
    gen = pad1(reqs.gen_tokens.astype(dtype))
    flops_tok = params.decode_flops_per_token[model]
    size_bits = params.size_bits[model]
    work = gen * flops_tok
    # eq. 16 knobs: eta pre-scales the edge share (prompt, work and the
    # committed tokens — same IEEE grouping as the oracle), beta=False
    # poisons the eq. 7 size to +inf (refused downloads never win), and
    # `tloc` prices the device's eq. 3 share, entering only the reported
    # eq. 13 latency and the SLO check — never the argmin
    praw = graw = tloc = None
    if reqs.eta is not None:
        eta = pad1(reqs.eta.astype(dtype))
        if reqs.local_flops_per_s is not None:
            # eq. 3 on the UNSCALED work; a rate <= 0 means no device
            local = pad1(reqs.local_flops_per_s.astype(dtype))
            tloc = jnp.where(local > 0, ((1.0 - eta) * work) / local, 0.0)
        praw, graw = prompt, gen  # policies still see the raw columns
        prompt, work, gen = prompt * eta, work * eta, gen * eta
    if reqs.beta is not None:
        # padding pads False -> +inf size on pad rows; `valid` rejects them
        beta = pad1(jnp.asarray(reqs.beta).astype(bool))
        size_bits = jnp.where(beta, size_bits, jnp.inf)
    has_cells = params.cell is not None and reqs.cell is not None
    has_time = params.drain_rate is not None and reqs.arrival_s is not None
    return _Columns(
        model=model, scal=jnp.stack([gen, size_bits, flops_tok], axis=1),
        prompt=prompt, work=work,
        drain=None if drain_tokens is None else pad1(jnp.broadcast_to(
            jnp.asarray(drain_tokens, dtype), reqs.model.shape)),
        cell=pad1(reqs.cell) if has_cells else None,
        arrival=pad1(reqs.arrival_s.astype(dtype)) if has_time else None,
        valid=(jnp.arange(b + pad) < b) if pad else None,
        # padded deadline lanes are 0.0 — harmless, `valid` rejects them
        deadline=(None if reqs.deadline_s is None
                  else pad1(reqs.deadline_s.astype(dtype))),
        prompt_raw=praw, gen_raw=graw, tloc=tloc,
    )


def _policy_view(cols):
    """The ``PolicyCtx``/``ChunkPolicyCtx`` columns of one request or one
    chunk of ``_Columns``: policies see the unscaled prompt and tokens."""
    return dict(
        model=cols.model,
        prompt_bits=cols.prompt if cols.prompt_raw is None
        else cols.prompt_raw,
        gen_tokens=cols.scal[..., 0] if cols.gen_raw is None
        else cols.gen_raw,
        flops_tok=cols.scal[..., 2], cell=cols.cell,
    )


def _observe(resident_m, queue, flops_per_s):
    """The scalar router's (3N,) observation: ``[resident, queue,
    flops]`` per server."""
    return jnp.stack([resident_m.astype(queue.dtype), queue, flops_per_s],
                     axis=-1).reshape(-1)


def _clamped(choice, lats, has_mask):
    """A policy's ``choice``, or the masked greedy argmin where that is
    not a server the request sees. An actor may ignore the inf-masked
    inputs or return an index outside [0, N), which a JAX gather would
    silently clamp to server N-1: an out-of-cell or out-of-range choice
    is never committed."""
    safe = jnp.clip(choice, 0, lats.shape[0] - 1)
    choice_ok = choice == safe
    if has_mask:
        # lats' finiteness (not the transmission panel's): a
        # beta-refused pick falls back to the resident-only argmin, like
        # the oracle
        choice_ok &= jnp.isfinite(lats[safe])
    return jnp.where(choice_ok, safe, jnp.argmin(lats).astype(jnp.int32))


def _commit(params, fleet, clock, model, gen_b, choice, ok):
    """LRU residency + queue fold of one request committed at ``choice``,
    mirroring the scalar oracle; ``fleet`` is ``(resident, last_use,
    queue)``. ``ok=None`` commits unconditionally (the single-cell
    un-padded fast path); a boolean ``ok`` gates every mutation — False
    leaves the fleet untouched. Returns ``(fleet, was_resident)``. The
    single scan commits through it, and so does ``core.mesh_router``'s
    window-close replay."""
    resident, last_use, queue = fleet
    row = resident[choice]
    was_resident = row[model]
    full = row.sum() >= params.cache_slots[choice]
    evict_idx = jnp.argmin(
        jnp.where(row, last_use[choice], jnp.iinfo(jnp.int32).max)
    )
    if ok is None:
        evict = ~was_resident & full
        row = row.at[evict_idx].set(row[evict_idx] & ~evict)
        row = row.at[model].set(True)
        resident = resident.at[choice].set(row)
        last_use = last_use.at[choice, model].set(clock)
        queue = queue.at[choice].add(gen_b)
    else:
        evict = ~was_resident & full & ok
        row = row.at[evict_idx].set(row[evict_idx] & ~evict)
        row = row.at[model].set(row[model] | ok)
        resident = resident.at[choice].set(row)
        last_use = last_use.at[choice, model].set(
            jnp.where(ok, clock, last_use[choice, model])
        )
        queue = queue.at[choice].add(jnp.where(ok, gen_b, 0.0))
    return (resident, last_use, queue), was_resident


def route_batch(
    params: FleetParams,
    state: FleetState,
    reqs: RequestBatch,
    drain_tokens=None,
    *,
    policy="greedy",
    actor=None,
    chunk: Optional[int] = None,
    unroll: int = 8,
    backend: Optional[str] = None,
    speculative: bool = True,
    outage=None,
):
    """Route a whole request batch in one jitted call; returns
    ``(state, outcome)``.

    Requests commit in arrival order (queue growth, LRU insert/evict)
    exactly like B sequential ``ModelAwareRouter.route`` calls, each
    followed by ``drain(drain_tokens)`` (scalar or (B,); None — the
    default — skips the drain update entirely in the compiled scan).

    Cell/drain knobs (both compiled out of the scan when absent):
      * ``reqs.cell`` + ``params.cell`` — block-diagonal visibility:
        each request scores ``+inf`` on out-of-cell servers, with
        ``CLOUD_CELL`` servers visible fleet-wide, so one call routes a
        whole multi-cell fleet.
      * ``reqs.arrival_s`` + ``params.drain_rate`` — time-based drain:
        before a request is scored, every queue decays by
        ``drain_rate * dt`` where ``dt`` is the wall-clock gap since the
        carry clock ``state.time_s`` last advanced.

    Robustness knobs (likewise compiled out when absent; see
    ``docs/robustness.md``):
      * ``reqs.deadline_s`` — SLO admission control: a request whose
        BEST eq. 11 score exceeds its deadline is rejected without
        committing (``+inf`` deadlines have no SLO).
      * ``params.spill`` — neighbour-cell spill: adjacent cells become
        visible at a backhaul surcharge, so overload spills to
        neighbours before the cloud column.
      * ``outage`` — (N,) bool fault mask: an outaged server's column
        scores ``+inf`` and its queue freezes (no drain) for this call.

    Eq. 16 action knobs (likewise compiled out when absent — ``None``
    stays bitwise today's path):
      * ``reqs.eta`` — partial offload: the edge share (eq. 5
        transmission, eq. 9 work, the committed queue tokens) scales by
        ``eta``; the device's retained ``1 - eta`` share is priced by
        ``reqs.local_flops_per_s`` (eq. 3) and enters the REPORTED
        latency (eq. 13's max) and the SLO check, never the argmin.
      * ``reqs.beta`` — download refusal: ``False`` rows price every
        non-resident server at ``+inf`` (the eq. 7 fetch is refused),
        so a refused request either lands on a resident server or is
        rejected (CAUSE_ADMISSION) — a committed refusal is always a
        residency hit and never mutates residency.

    ``outcome.cause`` labels every rejection (``rejection_cause``), so
    ``stats``/``window_stats`` can report honest per-cause rates.

    Performance knobs (all static — each combination compiles once):
      * ``chunk`` — two-phase commit: score ``chunk`` requests per fused
        kernel call, then run the slimmed correction scan (see module
        docstring). ``None`` keeps the one-scan path whose latencies are
        bit-exact against the oracle; integer decisions and fleet state
        are identical either way. Batches that don't divide evenly are
        padded with inert requests that never touch the fleet.
      * ``unroll`` — lax.scan unroll factor for the sequential region.
      * ``backend`` — backend of the chunked path's kernels: the
        phase-1 score panel and the speculative commit scan (``"xla"``
        | ``"pallas"`` | ``"pallas-interpret"``; ``None`` reads
        ``$REPRO_ROUTER_BACKEND``).
      * ``speculative`` — on the chunked greedy path, commit each
        chunk's provisional decisions speculatively and replay only the
        suffix after the first residency-mutating commit (see module
        docstring). Decisions and fleet state are identical either way;
        ``False`` forces the plain correction scan (the A/B baseline).
    """
    backend = resolve_backend(backend)  # env read stays outside the jit cache
    with obs.span("repro.route", requests=int(reqs.model.shape[0])):
        return _route_batch(params, state, reqs, drain_tokens, outage,
                            policy=policy, actor=actor, chunk=chunk,
                            unroll=unroll, backend=backend,
                            speculative=speculative)


@functools.partial(
    jax.jit, static_argnames=("policy", "actor", "chunk", "unroll", "backend",
                              "speculative")
)
def _route_batch(params, state, reqs, drain_tokens, outage, *, policy, actor,
                 chunk, unroll, backend, speculative=True):
    return _route_core(params, state, reqs, drain_tokens, outage,
                       _Plan(policy, actor, chunk, unroll, backend,
                             speculative))


def _route_core(params, state, reqs, drain_tokens, outage, plan):
    """The traceable body of :func:`route_batch` under a ``_Plan`` —
    ``core.mesh_router`` vmaps exactly this over cell blocks, so it must
    stay jit-free."""
    policy_fn = _resolve_policy(plan.policy, plan.actor)
    dtype = jnp.result_type(reqs.prompt_bits, params.uplink_bps)
    cols = _columns(params, reqs, drain_tokens, dtype, plan.chunk)
    if outage is not None:
        outage = jnp.asarray(outage, bool)
    rate = None  # the time drain's rate, per server
    if cols.arrival is not None:
        rate = params.drain_rate.astype(dtype)
        if outage is not None:
            # frozen queue: an outaged server stops draining for this call
            rate = jnp.where(outage, 0.0, rate)
    # some score may be +inf: out of cell, outaged or a refused download
    has_mask = (cols.cell is not None or outage is not None
                or reqs.beta is not None)
    time0 = state.time_s if state.time_s is not None else 0.0
    carry = (state.resident, state.last_use,
             state.queue_tokens.astype(dtype), state.clock,
             jnp.asarray(time0, dtype))

    if plan.chunk is None:
        with jax.named_scope("route.commit_scan"):
            carry, outs = _scan_full(params, cols, carry, policy_fn, rate,
                                     outage, has_mask, plan.unroll)
    else:
        carry, outs = _scan_chunked(params, cols, carry, policy_fn, rate,
                                    outage, has_mask, plan)
    resident, last_use, queue, clock, time_s = carry
    b = reqs.model.shape[0]
    choice, latency, hit = (x[:b] for x in outs)  # drop the chunk padding
    new_state = FleetState(
        resident=resident, last_use=last_use, queue_tokens=queue, clock=clock,
        time_s=time_s,
    )
    return new_state, RouteOutcome(
        choice=choice, latency=latency, hit=hit,
        cause=rejection_cause(params, reqs, outage, choice),
    )


def _scan_full(params, cols, carry, policy_fn, rate, outage, has_mask,
               unroll):
    """Single-scan path: full eq. 11 re-derivation per step (bit-exact
    latencies vs the scalar oracle — same term order, same rounding).

    Visibility (cells + spill), the spill surcharge and the outage mask
    are all state-independent, so they fold into the precomputed
    ``t_trans`` panel — masked pairs carry ``+inf`` and the scan body
    stays a pure add chain. The surcharge lands ON the eq. 5 term
    before the eq. 7/9 adds, matching the oracle's term order bitwise.

    Eq. 16 knobs arrive folded into ``cols``: the eta-scaled eq. 5/9
    edge share (and committed tokens), the beta-poisoned eq. 7 size (a
    refused download can never win — and a committed refusal is always
    a residency hit by construction), and ``tloc``, the device's
    retained ``1 - eta`` share (eq. 3), which enters only the reported
    eq. 13 latency and the SLO check — never the argmin (``max`` with a
    constant is monotone in the edge score, so the edge argmin is
    already an eq. 13 argmin)."""
    t_trans = costs.trans_latency(
        cols.prompt[:, None], 1.0, params.uplink_bps[None, :]
    )
    switch_price = costs.switch_latency(
        cols.scal[:, 1][:, None], params.backhaul_bps[None, :]
    )
    adj = _spill_adjacency(params, cols)
    if adj is not None:
        spilled = adj & (params.cell[None, :] != cols.cell[:, None])
        t_trans = t_trans + jnp.where(
            spilled, cols.prompt[:, None] / params.backhaul_bps[None, :], 0.0,
        )
    vis = cell_mask(params, cols)
    if vis is not None:
        t_trans = jnp.where(vis, t_trans, jnp.inf)
    if outage is not None:
        t_trans = jnp.where(outage[None, :], jnp.inf, t_trans)
    needs_ctx = getattr(policy_fn, "needs_ctx", False)
    # the builtin argmins return indices in [0, N) by construction and
    # can only land out of cell when the whole row is +inf (-> rejected
    # either way): skip the fallback clamp for them
    needs_clamp = policy_fn not in _ARGMIN_POLICIES

    def step(carry, xs):
        resident, last_use, queue, clock, time_s = carry
        row, t_trans_b, switch_b = xs
        gen_b, ftok_b = row.scal[0], row.scal[2]

        if rate is not None:  # wall-clock queue decay since the last arrival
            queue, time_s = advance_to_arrival(queue, time_s, row.arrival,
                                               rate)
        clock = clock + 1

        resident_m = resident[:, row.model]                     # (N,)
        t_switch = jnp.where(resident_m, 0.0, switch_b)
        t_comp = (queue * ftok_b + row.work) / params.flops_per_s
        lats = t_trans_b + t_switch + t_comp                    # eq. 11
        queue_vis = queue
        if has_mask:  # masked servers can never win the argmin
            queue_vis = jnp.where(jnp.isfinite(t_trans_b), queue, jnp.inf)

        obs = (_observe(resident_m, queue, params.flops_per_s)
               if getattr(policy_fn, "needs_obs", True) else None)
        if needs_ctx:
            ctx = PolicyCtx(params=params, resident=resident_m, queue=queue,
                            **_policy_view(row))
            choice = jnp.asarray(policy_fn(lats, obs, queue_vis, ctx),
                                 jnp.int32)
        else:
            choice = jnp.asarray(policy_fn(lats, obs, queue_vis), jnp.int32)
        if needs_clamp:
            choice = _clamped(choice, lats, has_mask)

        # a cell with no members and no cloud column (or fully outaged)
        # leaves every candidate at inf: reject without committing; the
        # SLO check compares the BEST score — policy-independent, so an
        # admission rejection never depends on which server was picked
        ok = jnp.isfinite(lats[choice]) if has_mask else None
        if row.deadline is not None:
            best = jnp.min(lats)
            if row.tloc is not None:  # eq. 13: the device share bounds below
                best = jnp.maximum(row.tloc, best)
            admit = best <= row.deadline
            ok = admit if ok is None else ok & admit
        (resident, last_use, queue), hit = _commit(
            params, (resident, last_use, queue), clock, row.model, gen_b,
            choice, ok,
        )
        lat_b = lats[choice]
        if row.tloc is not None:  # reported latency is eq. 13's max
            lat_b = jnp.maximum(row.tloc, lat_b)
        if ok is not None:
            choice, hit = jnp.where(ok, choice, -1), hit & ok
        if row.drain is not None:  # None is static: compiled out of the scan
            queue = drain_after_commit(queue, row.drain, frozen=outage)
        return (resident, last_use, queue, clock, time_s), (choice, lat_b, hit)

    return jax.lax.scan(step, carry, (cols, t_trans, switch_price),
                        unroll=unroll)


_LRU_FREE = jnp.iinfo(jnp.int32).max  # lru_key for a non-resident slot


def _static_argmin(col, k):
    """First-min argmin over the leading ``k`` scalars of ``col``,
    unrolled as a select tournament (k is tiny and static: the model
    catalogue). Ties break to the LOWEST index, exactly like
    ``jnp.argmin`` and the scalar oracle's list-order scan — the left
    operand wins every ``<=`` and lower indices always sit left."""
    vals = [col[i] for i in range(k)]
    idxs = [jnp.int32(i) for i in range(k)]
    while len(vals) > 1:
        nxt_v, nxt_i = [], []
        for i in range(0, len(vals) - 1, 2):
            left = vals[i] <= vals[i + 1]
            nxt_v.append(jnp.where(left, vals[i], vals[i + 1]))
            nxt_i.append(jnp.where(left, idxs[i], idxs[i + 1]))
        if len(vals) % 2:
            nxt_v.append(vals[-1])
            nxt_i.append(idxs[-1])
        vals, idxs = nxt_v, nxt_i
    return idxs[0]


def _scan_chunked(params, cols, carry, policy_fn, rate, outage, has_mask,
                  plan):
    """Two-phase commit: fused chunk scoring + slimmed correction scan,
    with the speculative parallel commit on top for the greedy policy
    (``plan.speculative``; see the module docstring for the argument).
    ``cols`` holds whole chunks (``_columns`` padded it).

    The serial region also runs on a denser state encoding than the
    public ``FleetState`` (converted at entry/exit):

      * ``lru_ext`` — residency, LRU clocks AND spare-slot counts
        collapsed into ONE transposed (K+1, N) int32 array: rows
        ``0..K-1`` hold ``where(resident, last_use, INT32_MAX)``, row
        ``K`` the free cache slots. Residency becomes a compare, the
        eq. 7 gate reads one CONTIGUOUS row per step (the model axis is
        major), and a single column slice at the chosen server yields
        the hit bit, the eviction candidates and the capacity check in
        one read. The LRU victim is a first-min select tournament down
        the column — non-residents sort last automatically, and ties
        still break by model index exactly like the scalar oracle's
        list order.
      * the commit is a dense one-hot ``where`` over (K+1, N) — no
        scatter in the loop body at all — and the three per-step
        outputs ride in ONE stacked (3,) vector so the scan performs a
        single output write per request.

    ``last_use`` entries of models that leave residency mid-batch come
    back as their pre-batch values (the single-scan path keeps the
    eviction-time clock); those entries are dead state — the oracle
    never reads a non-resident clock."""
    b = cols.model.shape[0]
    n = params.flops_per_s.shape[0]
    c = max(1, min(int(plan.chunk), b))
    dtype = cols.prompt.dtype
    has_cells = cols.cell is not None
    needs_obs = getattr(policy_fn, "needs_obs", True)
    needs_ctx = getattr(policy_fn, "needs_ctx", False)
    # the builtin argmins can only land on an invisible server when the
    # whole row is +inf (-> rejected either way), so the out-of-cell
    # clamp is skipped for them; every other policy gets clamped,
    # matching the single-scan path decision for decision
    needs_clamp = policy_fn not in _ARGMIN_POLICIES
    has_hook = needs_ctx and hasattr(policy_fn, "chunk_precompute")
    # speculative parallel commit: greedy only — its provisional argmin
    # depends on state only through (queue, residency), which the cheap
    # scan + drift replay reproduce exactly; other policies read obs/ctx
    use_spec = plan.speculative and policy_fn is _greedy_policy
    iota_n = jnp.arange(n, dtype=jnp.int32)
    num_k = params.size_bits.shape[0]
    iota_k = jnp.arange(num_k + 1, dtype=jnp.int32)  # +1: free-slot row

    resident0, last_use0, queue, clock, time_s = carry
    free = (params.cache_slots
            - resident0.sum(axis=1).astype(jnp.int32))       # (N,)
    lru = jnp.concatenate(
        [jnp.where(resident0, last_use0, _LRU_FREE).T, free[None, :]]
    )                                                        # (K+1, N)
    carry = (lru, queue, clock, time_s)

    def dense_commit(lru, queue, clock, model_b, gen_b, choice, ok):
        """Dense one-hot LRU/queue commit at ``choice``: ONE column slice
        yields hit bit, eviction candidates and capacity check."""
        lru_col = jax.lax.dynamic_slice(
            lru, (jnp.int32(0), choice), (num_k + 1, 1)
        )[:, 0]
        was_resident = lru_col[model_b] < _LRU_FREE
        evict_idx = _static_argmin(lru_col, num_k)
        full = lru_col[num_k] <= 0                              # free slots
        evict = ~was_resident & full
        touch_n = iota_n == choice                              # (N,)
        if ok is None:
            out_choice, hit = choice, was_resident
        else:
            evict &= ok
            touch_n &= ok
            out_choice, hit = jnp.where(ok, choice, -1), was_resident & ok
        taken = (~was_resident).astype(jnp.int32) - evict.astype(jnp.int32)
        pair_set = (iota_k == model_b)[:, None] & touch_n[None, :]
        pair_evict = ((iota_k == evict_idx) & evict)[:, None] & touch_n[None, :]
        pair_free = (iota_k == num_k)[:, None] & touch_n[None, :]
        lru = jnp.where(
            pair_set, clock,
            jnp.where(pair_evict, _LRU_FREE,
                      lru - jnp.where(pair_free, taken, 0)),
        )
        queue = queue + jnp.where(touch_n, gen_b, 0.0)
        return lru, queue, out_choice, hit

    def step(carry, xs):
        """One request on live state: the correction scan's body, and the
        speculative suffix replay's. ``xs`` is the request's row of
        ``_Columns``, its row of the chunk's base panel, and its slice of
        a chunk hook's table (or None). Returns the carry and ``(choice,
        latency, hit, exact)``; ``exact`` is None without the hook."""
        lru, queue, clock, time_s = carry
        row, base_b, aux_b = xs
        # scal[0] is the COMMITTED gen (eta-scaled); policies see raw
        gen_b, size_b, ftok_b = row.scal[0], row.scal[1], row.scal[2]
        valid_b = row.valid

        if rate is not None:  # wall-clock residue: decay since last arrival
            queue, time_s = advance_to_arrival(queue, time_s, row.arrival,
                                               rate, valid_b)
        clock = clock + (1 if valid_b is None
                         else valid_b.astype(clock.dtype))

        # state-dependent residue only: residency-gated switch (eq. 7)
        # + queue-backlog drift (eq. 9) on top of the precomputed
        # switch-free base (phase 1). Both residue terms are scalar x
        # (N,)-constant expressions, so the whole chain fuses into one
        # elementwise kernel — no per-step (N,) input rows beyond base.
        rm_key = jax.lax.dynamic_slice(
            lru, (row.model, jnp.int32(0)), (1, n)
        )[0]
        resident_m = rm_key < _LRU_FREE                         # (N,)
        lats = (
            base_b
            + jnp.where(resident_m, 0.0, size_b / params.backhaul_bps)
        ) + (queue * ftok_b) / params.flops_per_s

        obs = (_observe(resident_m, queue, params.flops_per_s)
               if needs_obs else None)
        queue_vis = queue
        if has_mask:
            # visibility/outage is already folded into base as +inf; XLA
            # DCEs this for policies that never read the queue (greedy)
            queue_vis = jnp.where(jnp.isfinite(base_b), queue, jnp.inf)
        exact_b = None
        if needs_ctx:
            ctx = PolicyCtx(params=params, resident=resident_m, queue=queue,
                            **_policy_view(row))
            if aux_b is not None:
                # chunk-level hook: the per-chunk precompute already did
                # the batched work; the per-step call only resolves the
                # precomputed decision against the live state. `exact`
                # flags whether that resolution matches what the policy
                # would decide per request — chunk_step replays the
                # whole chunk through the per-request path otherwise.
                choice, exact_b = policy_fn.chunk_apply(aux_b, ctx)
                choice = jnp.asarray(choice, jnp.int32)
                if valid_b is not None:  # inert pad rows never replay
                    exact_b |= ~valid_b
            else:
                choice = jnp.asarray(policy_fn(lats, obs, queue_vis, ctx),
                                     jnp.int32)
        else:
            choice = jnp.asarray(policy_fn(lats, obs, queue_vis), jnp.int32)
        if needs_clamp:
            choice = _clamped(choice, lats, has_mask)

        lat_b = lats[choice]
        if row.tloc is not None:  # reported latency is eq. 13's max
            lat_b = jnp.maximum(row.tloc, lat_b)
        ok = jnp.isfinite(lat_b) if has_mask else None
        if row.deadline is not None:  # SLO admission: best score vs deadline
            best = jnp.min(lats)  # greedy: lats[choice], the replay's case
            if row.tloc is not None:
                best = jnp.maximum(row.tloc, best)
            admit = best <= row.deadline
            ok = admit if ok is None else ok & admit
        if valid_b is not None:
            ok = valid_b if ok is None else ok & valid_b

        # dense one-hot commit on the (K+1, N) lru encoding
        lru, queue, out_choice, hit = dense_commit(
            lru, queue, clock, row.model, gen_b, choice, ok
        )
        if row.drain is not None:
            queue = drain_after_commit(queue, row.drain, valid_b, outage)
        return (lru, queue, clock, time_s), (out_choice, lat_b, hit, exact_b)

    def scan_step(carry, xs):
        carry, (choice, lat, hit, exact) = step(carry, xs)
        # one stacked output vector -> one scan write per request
        outs = [choice.astype(dtype), lat, hit.astype(dtype)]
        if exact is not None:
            outs.append(exact.astype(dtype))
        return carry, jnp.stack(outs)

    def score(cols_c):
        # phase 1 — ONE fused kernel call scores the whole chunk: the
        # switch-free base (eq. 5 + zero-backlog eq. 9) with the cell
        # mask (incl. spill surcharge) folded in as +inf. Everything
        # here is state-independent; the switch price stays OUT of the
        # base because re-subtracting it on residency would cancel
        # catastrophically (the download price dwarfs the served
        # latencies) — the scan re-gates it.
        with jax.named_scope("route.score"):
            base = ops.route_score(
                cols_c.prompt, None, cols_c.scal[:, 2], cols_c.work,
                params.uplink_bps, params.backhaul_bps, params.flops_per_s,
                req_cell=cols_c.cell,
                srv_cell=params.cell if has_cells else None,
                spill=params.spill if has_cells else None,
                cloud_cell=CLOUD_CELL, backend=plan.backend,
            )                                                   # (c, N)
            if outage is not None:
                base = jnp.where(outage[None, :], jnp.inf, base)
        return base

    def chunk_step(carry, cols_c):
        base = score(cols_c)
        if not has_hook:
            with jax.named_scope("route.commit_scan"):
                return jax.lax.scan(scan_step, carry, (cols_c, base, None),
                                    unroll=min(plan.unroll, c))
        # chunk-level policy hook: batch the expensive per-request work
        # (e.g. the actor MLP) over the whole chunk against the
        # CHUNK-ENTRY residency; the scan resolves each step against
        # the live state and flags any it could not resolve exactly.
        # The replay for those lives HERE, per chunk, not per step: an
        # expensive per-step cond branch taxes every iteration just by
        # existing (its captured operands defeat the scan-body fusion),
        # while a chunk that never drifts past the precomputed variants
        # pays only one predicate for the whole chunk.
        aux = policy_fn.chunk_precompute(ChunkPolicyCtx(
            params=params, resident=(carry[0][:num_k] < _LRU_FREE).T,
            **_policy_view(cols_c)))
        with jax.named_scope("route.commit_scan"):
            fast_carry, fast_outs = jax.lax.scan(
                scan_step, carry, (cols_c, base, aux),
                unroll=min(plan.unroll, c))

            def keep(_):
                return fast_carry, fast_outs[:, :3]

            def replay(_):  # rerun the chunk through the per-request path
                return jax.lax.scan(scan_step, carry, (cols_c, base, None),
                                    unroll=min(plan.unroll, c))

            return jax.lax.cond(jnp.all(fast_outs[:, 3] != 0.0),
                                keep, replay, None)

    def spec_chunk_step(carry, cols_c):
        lru, queue, clock, time_s = carry
        gen_c, size_c, ftok_c = (cols_c.scal[:, 0], cols_c.scal[:, 1],
                                 cols_c.scal[:, 2])
        valid_c, tloc_c = cols_c.valid, cols_c.tloc
        idx_c = jnp.arange(c, dtype=jnp.int32)

        # phase 1 — the same switch-free base the correction scan uses...
        base = score(cols_c)
        with jax.named_scope("route.score"):
            # ... plus the eq. 7 switch gate priced against the CHUNK-ENTRY
            # residency, applied with the per-step expression verbatim: the
            # speculative scores stay bitwise equal to the correction
            # scan's on every step where residency has not yet drifted
            hitrow = (lru[:num_k] < _LRU_FREE)[cols_c.model]     # (c, N)
            basez = base + jnp.where(
                hitrow, 0.0, size_c[:, None] / params.backhaul_bps[None, :]
            )

        # the whole speculative recurrence: residency is FROZEN at chunk
        # entry, so only the queue backlog rides the carry — one kernel
        # call per chunk on the Pallas backends, a lax.scan on "xla"
        with jax.named_scope("route.spec_scan"):
            q_ext, choices, lat, t_ext = ops.route_spec_scan(
                basez, ftok_c, gen_c, queue, time_s, params.flops_per_s,
                drain_rate=rate, arrival=cols_c.arrival, drain=cols_c.drain,
                outage=outage, valid=valid_c, deadline=cols_c.deadline,
                tloc=tloc_c, has_mask=has_mask, unroll=plan.unroll,
                backend=plan.backend,
            )                                           # q_ext: (c+1, N)
        with jax.named_scope("route.rederive"):
            # `lat` is the score every commit gate compared, on every
            # backend, so `ok` and the trajectory's adds never disagree
            col = choices[:, None]
            if tloc_c is not None:  # eq. 13: reported latency and SLO floor
                lat = jnp.maximum(tloc_c, lat)
            hits = jnp.take_along_axis(hitrow, col, axis=1)[:, 0]
            ok = jnp.isfinite(lat) if has_mask else jnp.ones((c,), bool)
            if cols_c.deadline is not None:
                ok &= lat <= cols_c.deadline
            okv = ok if valid_c is None else ok & valid_c
            # first conflicting commit: a committed MISS mutates residency
            # (install + possible eviction), invalidating later frozen
            # scores; committed HITS only touch LRU clocks, which no score
            # reads — everything before the first miss is oracle-exact
            miss = okv & ~hits
            i0 = jnp.where(miss.any(), jnp.argmax(miss).astype(jnp.int32),
                           jnp.int32(c))
            # clock advances per VALID request, committed or not
            cum = (idx_c + 1 if valid_c is None
                   else jnp.cumsum(valid_c.astype(jnp.int32)))
            clocks = clock + cum                                 # (c,)
            # parallel commit of the speculative prefix: ONE scatter-max
            # applies every prefix hit's LRU clock (clocks grow with the
            # stream index, so duplicate (model, server) slots resolve to
            # the LATEST write — exactly the serial order); prefix queue
            # adds already live in the trajectory
            in_prefix = okv & hits & (idx_c < i0)
            scat_col = jnp.where(in_prefix, choices, n)          # n: dump lane
            lru = jnp.pad(lru, ((0, 0), (0, 1)))
            lru = lru.at[cols_c.model, scat_col].max(clocks)[:, :n]
            # rewind carried state to the first conflicting commit ...
            queue = jnp.take(q_ext, i0, axis=0)
            clock = clock + jnp.where(i0 > 0, cum[jnp.maximum(i0 - 1, 0)], 0)
            if rate is not None:
                time_s = jnp.take(t_ext, i0, axis=0)
            och = jnp.where(okv, choices, -1)
            ohit = hits & okv

        def replay_body(i, st):
            # ... and replay the conflicting suffix serially through the
            # correction scan's own step, on live residency
            carry, outs = st
            # the strip is read column by column, as everywhere in this
            # chunk: a row read would change the strip's layout
            row = jax.tree.map(lambda x: x[i], cols_c._replace(scal=None))
            row = row._replace(
                scal=jnp.stack([gen_c[i], size_c[i], ftok_c[i]]))
            carry, got = step(carry, (row, base[i], None))
            return carry, tuple(o.at[i].set(g) for o, g in zip(outs, got))

        with jax.named_scope("route.replay"):
            return jax.lax.fori_loop(
                i0, c, replay_body,
                ((lru, queue, clock, time_s), (och, lat, ohit)))

    xs = jax.tree.map(lambda x: x.reshape((b // c, c) + x.shape[1:]), cols)
    carry, outs = jax.lax.scan(spec_chunk_step if use_spec else chunk_step,
                               carry, xs)
    lru, queue, clock, time_s = carry
    lru = lru[:num_k]                                        # drop free row
    resident = (lru < _LRU_FREE).T
    # non-resident clocks are dead state; restore pre-batch values so a
    # model that was evicted mid-batch doesn't surface a bogus clock
    last_use = jnp.where(resident, lru.T, last_use0)
    carry = (resident, last_use, queue, clock, time_s)
    if use_spec:                                             # unpack
        return carry, tuple(o.reshape(b) for o in outs)
    outs = outs.reshape(b, 3)
    return carry, (outs[:, 0].astype(jnp.int32), outs[:, 1], outs[:, 2] != 0)


def stats(outcome: RouteOutcome, *, cloud_index: Optional[int] = None) -> dict:
    """Fleet-level summary of one routed batch.

    Rejected requests (``choice == -1``, ``inf`` latency) would poison
    the latency mean, so they are masked out of ``mean_latency`` and
    reported separately as ``completion_rate`` — the fraction of
    requests that found a feasible server (the paper's third headline
    metric alongside latency and hit rate). ``residency_hit_rate`` is
    masked the same way: rejected requests are forced ``hit=False`` by
    the router, so counting them in the mean would deflate the hit rate
    exactly in the rejection-heavy scenarios where it matters — it is
    the hit fraction OVER COMPLETED requests (``nan`` when none
    complete). ``download_rate`` is its complement over the same
    denominator — the fraction of completed requests whose commit
    fetched the model over the backhaul (an eq. 7/8 download; under
    ``beta=False`` refusal it is structurally 0, since a committed
    refusal is always a residency hit), so ``residency_hit_rate +
    download_rate == 1`` whenever any request completes.
    ``cloud_index`` — the cloud column's server index
    (conventionally the last) — adds the ``cloud_fallback_rate``, so
    call sites stop re-deriving it from raw choices.

    When the outcome carries a ``cause`` channel, the per-cause
    rejection rates (``infeasible_rate`` / ``admission_rate`` /
    ``outage_rate``) are reported over ALL requests — the same
    denominator as ``completion_rate``, so the four always sum to 1.
    """
    ok = outcome.choice >= 0
    n_ok = jnp.maximum(ok.sum(), 1)
    mean_lat = jnp.where(
        ok.any(),
        jnp.where(ok, outcome.latency, 0.0).sum() / n_ok,
        jnp.inf,
    )
    hit_rate = jnp.where(
        ok.any(),
        (outcome.hit & ok).sum() / n_ok,
        jnp.nan,
    )
    dl_rate = jnp.where(
        ok.any(),
        (ok & ~outcome.hit).sum() / n_ok,
        jnp.nan,
    )
    out = {
        "mean_latency": float(mean_lat),
        "residency_hit_rate": float(hit_rate),
        "download_rate": float(dl_rate),
        "completion_rate": float(ok.mean()),
    }
    if cloud_index is not None:
        out["cloud_fallback_rate"] = float(
            (outcome.choice == cloud_index).mean()
        )
    if outcome.cause is not None:
        for name, code in (("infeasible_rate", CAUSE_INFEASIBLE),
                           ("admission_rate", CAUSE_ADMISSION),
                           ("outage_rate", CAUSE_OUTAGE)):
            out[name] = float((outcome.cause == code).mean())
    return out


def window_stats(outcome: RouteOutcome, window_id, num_windows: int, *,
                 cloud_index: Optional[int] = None,
                 completed_means: Optional[dict] = None) -> dict:
    """Per-window ``stats`` over one routed stream: the same rejection
    masking, applied ONCE for all windows, so time-series aggregation
    (``workloads.simulate``) doesn't re-mask per call site.

    ``window_id`` assigns each request to a window in ``[0,
    num_windows)`` — any segmentation works (request-count chunks, wall-
    clock buckets). Returns ``(num_windows,)`` numpy arrays; a window
    with no completed requests reports ``inf`` mean latency and ``nan``
    hit rate / completed means (there is nothing to average — ``0.0``
    would read as an impossibly perfect measurement), an empty window
    zero rates. ``residency_hit_rate`` is the hit fraction over the
    window's COMPLETED requests, matching :func:`stats`.
    ``completed_means`` adds extra columns: each ``name -> (B,)``
    per-request value is averaged over the window's COMPLETED requests
    (values at rejected requests must already be zero — e.g.
    ``workloads.simulate.request_energy_j``). A ``cause`` channel on the
    outcome adds per-window ``infeasible_rate`` / ``admission_rate`` /
    ``outage_rate`` over the SAME all-requests denominator as
    ``completion_rate`` (the four sum to 1 in every window)."""
    wid = np.asarray(window_id)
    choice = np.asarray(outcome.choice)
    ok = choice >= 0
    count = np.bincount(wid, minlength=num_windows).astype(float)
    n_ok = np.bincount(wid, weights=ok, minlength=num_windows)
    lat_sum = np.bincount(
        wid, weights=np.where(ok, np.asarray(outcome.latency), 0.0),
        minlength=num_windows,
    )
    hits = np.bincount(wid, weights=np.asarray(outcome.hit) & ok,
                       minlength=num_windows)
    denom = np.maximum(count, 1.0)
    denom_ok = np.maximum(n_ok, 1.0)
    out = {
        "requests": count.astype(np.int64),
        "mean_latency": np.where(n_ok > 0, lat_sum / denom_ok, np.inf),
        "completion_rate": n_ok / denom,
        "residency_hit_rate": np.where(n_ok > 0, hits / denom_ok, np.nan),
        # complement of the hit rate over completed requests: commits
        # that fetched the model over the backhaul (eq. 7/8 downloads)
        "download_rate": np.where(n_ok > 0, (n_ok - hits) / denom_ok,
                                  np.nan),
    }
    if cloud_index is not None:
        out["cloud_fallback_rate"] = np.bincount(
            wid, weights=(choice == cloud_index), minlength=num_windows
        ) / denom
    if outcome.cause is not None:
        cz = np.asarray(outcome.cause)
        for name, code in (("infeasible_rate", CAUSE_INFEASIBLE),
                           ("admission_rate", CAUSE_ADMISSION),
                           ("outage_rate", CAUSE_OUTAGE)):
            out[name] = np.bincount(
                wid, weights=(cz == code), minlength=num_windows
            ) / denom
    for name, vals in (completed_means or {}).items():
        out[name] = np.where(
            n_ok > 0,
            np.bincount(wid, weights=np.asarray(vals),
                        minlength=num_windows) / denom_ok,
            np.nan,
        )
    return out
