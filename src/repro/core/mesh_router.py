"""Mesh-sharded fleet routing: cell blocks over devices, cloud reconciled.

``core.batch_router.route_batch`` routes a whole multi-cell fleet in one
jitted call — on ONE device. This module shards that call across a
device mesh: the fleet's cell blocks (the cell-major layout,
``batch_router.CellLayout``) and their request buckets are partitioned
over a 1-axis ``("cells",)`` mesh built by ``distributed.sharding.
make_mesh``; each device routes its cells' traffic locally through the
UNCHANGED ``_route_core`` (same scan / chunked two-phase / speculative
parallel commit), and only the shared ``CLOUD_CELL`` columns are
reconciled afterwards.

Window semantics
----------------
One ``route_batch_sharded`` call is one serving WINDOW (what
``workloads.simulate`` already feeds ``route_batch``). Within a window:

* each cell's requests commit sequentially, in arrival order, against
  the cell's own server block — exactly the single-device semantics,
  because cells are invisible to each other's requests;
* each cell prices the shared cloud columns against the WINDOW-ENTRY
  snapshot of the cloud queue plus the cell's OWN cloud commits. Cells
  do not observe each other's cloud backlog until the window closes —
  the one relaxation that makes the batch parallel across cells.

At window close the shared columns are reconciled:

* **cloud backlog** — the per-cell backlog commits are gathered (an
  all-reduce-sized exchange: the committed choices plus one queue row
  per cell) and replayed in global arrival order by a cheap masked-add
  scan, so the carried cloud queue is the EXACT sequential fold of
  every committed token — bitwise what the single-device path computes
  for the same choices, including the wall-clock drain;
* **cloud LRU** — per-cell ``last_use`` copies hold globally-ordered
  clocks (see below), so an elementwise max is the exact latest-use;
* **cloud residency** — validated full (``launch.serve.
  make_cloud_server`` guarantees it), hence immutable: a full row can
  never install or evict, so the per-cell copies cannot diverge.

Exactness
---------
Decisions, residency, LRU clocks, queues, rejections and the carried
clock are BIT-IDENTICAL to single-device ``route_batch`` (and hence the
scalar oracle) whenever the window's cloud feedback does not cross
cells — cloud-free fleets, or streams whose cloud commits all originate
in one cell — and independent of the device count ALWAYS: the same
window routed on 1, 2, 4 or 8 devices produces identical bits, because
per-cell work is data-independent across cells and the reconciliation
reduces in a fixed, device-count-free order. With cross-cell cloud
contention the carried state is still exact for the committed choices;
the choices themselves follow the window semantics above. With a
nonzero ``drain_rate`` the per-cell decay composes the same real
arithmetic in fewer floating-point steps (one ``dt`` per own-cell
arrival instead of one per global arrival), so edge queues agree to
float tolerance rather than bitwise; ``drain_rate == 0`` (with or
without arrival stamps) is exact.

LRU clocks stay globally ordered through a post-scan remap: each cell
routes with LOCAL clocks ``clock0 + 1 .. clock0 + Bc`` (monotone in its
own stream, so every eviction argmin is unchanged), and committed
entries — recognisable as ``last_use > clock0`` — are rewritten to
``clock0 + 1 + global_position`` through the bucket's request-position
map before the blocks are reassembled.

The legacy per-request ``drain_tokens`` argument is rejected: it drains
EVERY server after EVERY request — a globally-sequential semantics that
cannot be cell-partitioned. Use the time-based ``FleetParams.
drain_rate`` instead.

Robustness knobs
----------------
The single-device robustness knobs (``docs/robustness.md``) thread
through unchanged: ``RequestBatch.deadline_s`` rides the buckets
(padding rows carry ``+inf`` — no SLO), the ``outage`` mask is
cell-blocked like every server column (an outaged cloud column is seen
outaged by every cell, and the reconciliation replay freezes its
drain), and ``outcome.cause`` is derived post-hoc from the scattered
choices by the shared ``batch_router.rejection_cause`` — bitwise the
single-device channel.

The eq. 16 action knobs (``RequestBatch.eta`` / ``beta`` /
``local_flops_per_s``, see ``batch_router.route_batch``) ride the
buckets the same way — padding rows carry ``eta = 1`` (so the ``+inf``
prompt pad never multiplies to NaN), ``beta = True`` and a zero local
rate — and the inner ``_route_core`` applies them per cell untouched.
The only shared-column consequence is the cloud backlog: a partial
offload commits ``eta * gen_tokens``, so the window-close replay folds
the SAME eta-scaled token count (one exact-rounded multiply — bitwise
the per-cell commit). Downloads (``beta``) never need reconciling: the
cloud columns are validated full-residency, so every cross-cell model
fetch lands on a per-cell edge block no other cell can touch.

Neighbour-cell spill (``FleetParams.spill``) breaks the premise of the
cell-blocked path — a request may commit OUTSIDE its home block — so
spill fleets take a FULL-REPLICATION variant instead: every device row
holds the whole fleet, routes its cells' request buckets against the
window-entry snapshot (same window semantics as the cloud columns,
now applied to every server), and the carried state is rebuilt by one
close-replay scan over the committed choices in global arrival order —
the exact sequential fold of ``batch_router._commit``, decay included.
Choices are bit-identical to single-device whenever a window's
cross-cell feedback stays within one bucket (e.g. all real traffic in
one cell), and the carried state is always the exact fold of the
committed choices.

Layout contract
---------------
The fleet must be cell-major (``batch_router.cell_layout``): equal-size
edge cell blocks ``0..C-1`` contiguous, cloud columns trailing —
``launch.serve.make_multicell_fleet`` builds exactly this. Fleets in
any other server order are permuted in (``cell_major_order``) and the
returned state/choices permuted back, so the call is order-preserving
for the caller. Requests need ``RequestBatch.cell`` when C > 1;
out-of-range cells (and requests arriving when no cell matches) see
only the cloud columns, exactly like the single-device mask. Cells
that don't divide the device count are padded with inert all-padding
blocks; padding requests carry ``prompt_bits = +inf`` so every score is
infeasible and the commit machinery provably never touches state.

``benchmarks/fleet_scale.py`` measures req/s vs device count at fleet
scale; ``docs/sharding.md`` is the guide; ``tests/
test_multicell_router.py`` locks the equivalences down on a forced
8-device host.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import batch_router as br
from repro.core.queue import advance_to_arrival
from repro.core.router import CLOUD_CELL
from repro.distributed import sharding

#: Inner cell id for requests that must see ONLY the cloud columns:
#: orphans (out-of-range cells) and bucket padding. Edge blocks are
#: relabeled to cell 0 and the cloud keeps CLOUD_CELL (-1), so -2 can
#: never match a server.
_ORPHAN_CELL = -2

#: Request buckets are padded to a multiple of this so window-to-window
#: jitter in the per-cell request count doesn't recompile the mesh call.
_BUCKET_ROUND = 16


@functools.lru_cache(maxsize=None)
def cells_mesh(num_devices: int):
    """1-axis ``("cells",)`` mesh over the first ``num_devices`` local
    devices (an explicit subset: ``make_mesh`` refuses to silently
    undersubscribe the platform)."""
    return sharding.make_mesh(
        (num_devices,), ("cells",),
        devices=tuple(jax.devices()[:num_devices]),
    )


def local_template_params(params: br.FleetParams) -> br.FleetParams:
    """The block-0 local fleet view every cell shares geometrically:
    ``per_cell`` edge servers relabeled cell 0 + the cloud columns.
    Build policies for the sharded router against THIS template (see
    ``core.policies.actor_policy_for_cell_blocks``)."""
    return br.local_block_params(params, br.cell_layout(params), 0)


def _bucket_requests(reqs: br.RequestBatch, layout: br.CellLayout,
                     c_pad: int, time0: float, has_time: bool,
                     keep_cells: bool = False):
    """Host-side bucketing of a (B,) request stream into dense
    ``(c_pad, bc)`` per-cell buckets (numpy; the result feeds the jitted
    mesh call). Returns ``(buckets, gpos)``: a ``RequestBatch`` of
    ``(c_pad, bc)`` columns, and the bucket slots' stream positions.

    Real requests keep their arrival order inside their cell's bucket
    and carry inner cell 0; orphans (out-of-range ``cell``) are spread
    deterministically (global index mod C — device-count independent)
    and carry ``_ORPHAN_CELL`` so they see only the cloud. With
    ``keep_cells`` (the full-replication spill path, which routes each
    bucket against GLOBAL params) every request keeps its true cell id
    instead — orphans included, so the global mask prices them exactly
    like the single-device call. Trailing
    padding rows carry ``prompt_bits = +inf`` (every score infeasible →
    rejected → zero state mutation), a ``+inf`` deadline (no SLO) and an
    arrival stamp no later than
    the bucket's running clock (``dt = 0`` → the wall-clock decay is a
    bitwise no-op). ``gpos`` maps each bucket slot back to its global
    stream position (-1 on padding) — the outcome scatter and the LRU
    clock remap both key off it."""
    c = layout.num_cells
    b = int(reqs.model.shape[0])
    if reqs.cell is not None:
        rcell = np.asarray(reqs.cell).astype(np.int64)
    else:
        rcell = np.zeros(b, np.int64)
    in_range = (rcell >= 0) & (rcell < c)
    bucket = np.where(in_range, rcell, np.arange(b, dtype=np.int64) % c)
    counts = np.bincount(bucket, minlength=c)
    bc = -(-max(int(counts.max()), 1) // _BUCKET_ROUND) * _BUCKET_ROUND
    order = np.argsort(bucket, kind="stable")
    starts = np.zeros(c + 1, np.int64)
    starts[1:] = np.cumsum(counts)
    sortedb = bucket[order]
    slot = np.arange(b, dtype=np.int64) - starts[sortedb]

    def bucketed(col, fill, dtype=None):
        """``col`` in bucket slots, padding rows ``fill``."""
        col = np.asarray(col, dtype)
        out = np.full((c_pad, bc), fill, col.dtype)
        out[sortedb, slot] = col[order]
        return out

    gpos = np.full((c_pad, bc), -1, np.int32)
    gpos[sortedb, slot] = order
    if keep_cells:
        icell = rcell.astype(np.int32)
    else:
        icell = np.where(in_range, 0, _ORPHAN_CELL).astype(np.int32)
    # eq. 16 action columns: padding carries eta = 1 (the +inf prompt
    # pad must not multiply to NaN), beta = True and a zero local rate
    # (t_local is where-guarded on local > 0) — all inert
    has_local = reqs.eta is not None and reqs.local_flops_per_s is not None
    buckets = br.RequestBatch(
        model=bucketed(reqs.model, 0),
        prompt_bits=bucketed(reqs.prompt_bits, np.inf),
        gen_tokens=bucketed(reqs.gen_tokens, 0),
        cell=bucketed(icell, _ORPHAN_CELL),
        deadline_s=(None if reqs.deadline_s is None
                    else bucketed(reqs.deadline_s, np.inf)),
        eta=None if reqs.eta is None else bucketed(reqs.eta, 1),
        beta=None if reqs.beta is None else bucketed(reqs.beta, True, bool),
        local_flops_per_s=(bucketed(reqs.local_flops_per_s, 0)
                           if has_local else None),
    )
    if has_time:
        arr = np.asarray(reqs.arrival_s)
        # padding arrivals: the bucket's latest stamp (or the fleet
        # clock) — never ahead of the inner running time, so dt == 0
        bmax = np.full(c_pad, time0, arr.dtype)
        if b:
            np.maximum.at(bmax, sortedb, arr[order])
        pad_counts = np.zeros(c_pad, np.int64)
        pad_counts[:c] = counts
        padmask = np.arange(bc)[None, :] >= pad_counts[:, None]
        buckets = buckets._replace(arrival_s=np.where(
            padmask, bmax[:, None], bucketed(arr, 0)))
    return buckets, gpos


def _scatter_outcome(gpos_b, ch, lat, hit, b, dtype):
    """Per-bucket ``(choice, latency, hit)`` back in stream order: bucket
    slot ``s`` lands at ``gpos_b[s]``; padding slots (-1) are dropped."""
    gposf = gpos_b.reshape(-1)
    safe = jnp.where(gposf >= 0, gposf, b)  # b: out of bounds -> dropped
    choice = jnp.zeros((b,), jnp.int32).at[safe].set(ch.reshape(-1),
                                                      mode="drop")
    latency = jnp.zeros((b,), dtype).at[safe].set(
        lat.reshape(-1).astype(dtype), mode="drop")
    hit = jnp.zeros((b,), bool).at[safe].set(hit.reshape(-1), mode="drop")
    return choice, latency, hit


def _committed_tokens(reqs, dtype):
    """The tokens each request of the stream commits: a partial offload
    commits eta * gen_tokens (one exact-rounded multiply — the same bits
    every per-cell scan folded)."""
    gen = reqs.gen_tokens.astype(dtype)
    return gen if reqs.eta is None else gen * reqs.eta.astype(dtype)


@functools.partial(jax.jit, static_argnames=("mesh", "layout", "plan"))
def _sharded_route(params, state, bucketed, reqs, outage, *, mesh, layout,
                   plan):
    buckets, gpos_b = bucketed  # _bucket_requests' pair
    axis = mesh.axis_names[0]
    c, n, nc = layout.num_cells, layout.per_cell, layout.num_cloud
    c_pad, bc = buckets.model.shape
    ne, m = layout.num_edge, layout.per_cell + layout.num_cloud
    b = int(reqs.model.shape[0])
    dtype = jnp.result_type(buckets.prompt_bits, params.uplink_bps)
    has_time = (params.drain_rate is not None
                and buckets.arrival_s is not None)
    clock0 = state.clock
    time0 = jnp.asarray(
        state.time_s if state.time_s is not None else 0.0, dtype
    )
    queue0 = state.queue_tokens.astype(dtype)

    def blocks(x):
        """(N, ...) server-major -> (c_pad, n+nc, ...) cell blocks, the
        cloud rows replicated into every block, padded cells inert
        copies of block 0 (their requests are all padding)."""
        if x is None:
            return None
        blk = x[:ne].reshape((c, n) + x.shape[1:])
        if nc:
            cloud = jnp.broadcast_to(x[ne:][None], (c, nc) + x.shape[1:])
            blk = jnp.concatenate([blk, cloud], axis=1)
        if c_pad > c:
            blk = jnp.concatenate(
                [blk, jnp.broadcast_to(blk[:1], (c_pad - c,) + blk.shape[1:])]
            )
        return blk

    local_cell = jnp.concatenate([
        jnp.zeros((n,), jnp.int32),
        jnp.full((nc,), CLOUD_CELL, jnp.int32),
    ]) if nc else jnp.zeros((n,), jnp.int32)

    # per-block server columns (the shared ones ride replicated)
    blk_params = params._replace(
        flops_per_s=blocks(params.flops_per_s),
        uplink_bps=blocks(params.uplink_bps),
        backhaul_bps=blocks(params.backhaul_bps),
        cache_slots=blocks(params.cache_slots),
        size_bits=None, decode_flops_per_token=None, cell=None,
        drain_rate=blocks(params.drain_rate), spill=None,
    )
    blk_state = br.FleetState(resident=blocks(state.resident),
                              last_use=blocks(state.last_use),
                              queue_tokens=blocks(queue0), clock=None)
    sharded = (blk_params, blk_state, buckets, gpos_b, blocks(outage))
    repl = (params.size_bits, params.decode_flops_per_token, clock0, time0,
            local_cell)

    def device_fn(sharded, repl):
        size_bits, dflops, clk0, t0, lcell = repl

        def one_cell(cell_args):
            bp, bs, r, gp, og = cell_args
            p = bp._replace(size_bits=size_bits,
                            decode_flops_per_token=dflops, cell=lcell)
            s = bs._replace(clock=clk0, time_s=t0)
            st, out = br._route_core(p, s, r, None, og, plan)
            # local -> global LRU clock remap: commits from THIS window
            # (> clock0 — stale entries, including pre-window values,
            # never exceed the entry clock) are rewritten to clock0 + 1
            # + global stream position through the bucket position map
            cmap = clk0 + 1 + gp
            lu2 = st.last_use
            fresh = lu2 > clk0
            lu2 = jnp.where(
                fresh, cmap[jnp.clip(lu2 - clk0 - 1, 0, bc - 1)], lu2
            )
            return (st.resident, lu2, st.queue_tokens.astype(dtype),
                    out.choice, out.latency, out.hit)

        return jax.vmap(one_cell)(sharded)

    routed = jax.shard_map(
        device_fn, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False,
    )(sharded, repl)
    res_o, lu_o, q_o, ch_o, lat_o, hit_o = routed

    # --- reassemble the cell-major fleet state (real cells only) ---
    num_k = int(params.size_bits.shape[0])
    resident = res_o[:c, :n].reshape(ne, num_k)
    last_use = lu_o[:c, :n].reshape(ne, num_k)
    queue = q_o[:c, :n].reshape(ne)

    # --- scatter outcomes back to the caller's stream order ---
    imap = (jnp.arange(c_pad, dtype=jnp.int32) * n)[:, None] \
        + jnp.arange(n, dtype=jnp.int32)[None, :]
    if nc:
        imap = jnp.concatenate([
            imap,
            jnp.broadcast_to(ne + jnp.arange(nc, dtype=jnp.int32),
                             (c_pad, nc)),
        ], axis=1)
    ch_glob = jnp.where(
        ch_o >= 0,
        jnp.take_along_axis(imap, jnp.clip(ch_o, 0, m - 1), axis=1),
        -1,
    )
    choice, latency, hit = _scatter_outcome(gpos_b, ch_glob, lat_o, hit_o,
                                            b, dtype)

    # --- cloud reconciliation ---
    if nc:
        # residency: validated full at entry -> immutable; carry as-is
        resident = jnp.concatenate([resident, state.resident[ne:]])
        # LRU: per-cell copies hold globally-ordered clocks after the
        # remap, so the elementwise max IS the latest use
        lu_cloud = jnp.maximum(jnp.max(lu_o[:c, n:], axis=0),
                               state.last_use[ne:])
        last_use = jnp.concatenate([last_use, lu_cloud])
        # backlog: replay the committed cloud choices in global arrival
        # order — the exact sequential fold the single-device scan
        # computes, decay included (see module docstring)
        cloud_ids = ne + jnp.arange(nc, dtype=jnp.int32)
        rate_cloud = (params.drain_rate[ne:].astype(dtype)
                      if has_time else None)
        if has_time and outage is not None:
            # frozen queue: an outaged cloud column stops draining, in
            # the replay exactly as in every per-cell scan
            rate_cloud = jnp.where(outage[ne:], 0.0, rate_cloud)

        def replay_step(carry, xs):
            qc, trun = carry
            ch_i, g_i, a_i = xs
            if has_time:
                qc, trun = advance_to_arrival(qc, trun, a_i, rate_cloud)
            qc = qc + jnp.where(cloud_ids == ch_i, g_i, 0.0)
            return (qc, trun), None

        xs = (choice, _committed_tokens(reqs, dtype),
              reqs.arrival_s.astype(dtype) if has_time else None)
        (q_cloud, _), _ = jax.lax.scan(
            replay_step, (queue0[ne:], time0), xs, unroll=min(64, b))
        queue = jnp.concatenate([queue, q_cloud])

    clock_f = clock0 + jnp.asarray(b, clock0.dtype)
    if has_time:
        time_f = jnp.maximum(time0, jnp.max(reqs.arrival_s.astype(dtype)))
    else:
        time_f = time0
    new_state = br.FleetState(resident=resident, last_use=last_use,
                              queue_tokens=queue, clock=clock_f,
                              time_s=time_f)
    return new_state, br.RouteOutcome(choice=choice, latency=latency,
                                      hit=hit)


@functools.partial(jax.jit, static_argnames=("mesh", "plan"))
def _sharded_route_spill(params, state, bucketed, reqs, outage, *, mesh,
                         plan):
    """Full-replication sharded route for spill fleets (module docstring:
    robustness knobs). Every device row holds the WHOLE fleet; each cell
    bucket routes against the window-entry snapshot with the GLOBAL
    params (true cell ids, global spill adjacency — choices come out in
    global server indices, so no LRU remap and no index map), and the
    carried state is rebuilt by one close-replay scan over the committed
    choices in global arrival order: the exact ``batch_router._commit``
    fold, wall-clock decay and outage freeze included."""
    buckets, gpos_b = bucketed  # _bucket_requests' pair
    axis = mesh.axis_names[0]
    b = int(reqs.model.shape[0])
    dtype = jnp.result_type(buckets.prompt_bits, params.uplink_bps)
    has_time = (params.drain_rate is not None
                and buckets.arrival_s is not None)
    clock0 = state.clock
    time0 = jnp.asarray(
        state.time_s if state.time_s is not None else 0.0, dtype
    )
    queue0 = state.queue_tokens.astype(dtype)

    def device_fn(buckets, full):
        p_full, s_full, og = full

        def one_bucket(r):
            _, out = br._route_core(p_full, s_full, r, None, og, plan)
            # per-bucket state is discarded: the close replay below is
            # the single source of truth for the carried fleet
            return out.choice, out.latency, out.hit

        return jax.vmap(one_bucket)(buckets)

    ch_o, lat_o, hit_o = jax.shard_map(
        device_fn, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False,
    )(buckets, (params, state, outage))

    # --- scatter outcomes back to the caller's stream order ---
    choice, latency, hit = _scatter_outcome(gpos_b, ch_o, lat_o, hit_o, b,
                                            dtype)

    # --- close replay: sequential fold of the committed choices ---
    drain_rate = params.drain_rate.astype(dtype) if has_time else None
    if drain_rate is not None and outage is not None:
        drain_rate = jnp.where(outage, 0.0, drain_rate)
    nsrv = int(params.flops_per_s.shape[0])

    def commit_step(carry, xs):
        fleet, clock, time_s = carry
        model, gen_i, ch_i, a_i = xs
        queue = fleet[2]
        if has_time:
            queue, time_s = advance_to_arrival(queue, time_s, a_i,
                                               drain_rate)
        clock = clock + 1
        fleet, _ = br._commit(params, fleet[:2] + (queue,), clock, model,
                              gen_i, jnp.clip(ch_i, 0, nsrv - 1), ch_i >= 0)
        return (fleet, clock, time_s), None

    xs = (reqs.model, _committed_tokens(reqs, dtype), choice,
          reqs.arrival_s.astype(dtype) if has_time else None)
    carry = ((state.resident, state.last_use, queue0), clock0, time0)
    ((resident, last_use, queue), clock_f, time_f), _ = jax.lax.scan(
        commit_step, carry, xs, unroll=min(64, b))

    new_state = br.FleetState(resident=resident, last_use=last_use,
                              queue_tokens=queue, clock=clock_f,
                              time_s=time_f)
    return new_state, br.RouteOutcome(choice=choice, latency=latency,
                                      hit=hit)


def route_batch_sharded(
    params: br.FleetParams,
    state: br.FleetState,
    reqs: br.RequestBatch,
    drain_tokens=None,
    *,
    outage=None,
    mesh=None,
    num_devices: Optional[int] = None,
    policy="greedy",
    actor=None,
    chunk: Optional[int] = None,
    unroll: int = 8,
    backend: Optional[str] = None,
    speculative: bool = True,
):
    """Route one request window across a device mesh; returns
    ``(state, outcome)`` with the same pytrees as ``route_batch``.

    The fleet's cell blocks and their request buckets are partitioned
    over the mesh's leading axis; each device routes its cells locally
    through the unchanged scan/chunked/speculative machinery, and the
    shared cloud columns are reconciled at window close (module
    docstring: window semantics, exactness, layout contract).

    Robustness knobs match ``route_batch``: ``reqs.deadline_s`` (SLO
    admission), ``outage`` ((N,) bool fault mask in the caller's server
    order) and ``params.spill`` — the last switches to the
    full-replication path (module docstring: robustness knobs). The
    eq. 16 action knobs (``reqs.eta`` / ``beta`` /
    ``local_flops_per_s``) ride the buckets and the cloud replay folds
    the eta-scaled commit (module docstring). ``outcome.cause`` labels
    every rejection.

    Mesh selection: pass ``mesh`` (leading axis = the cell axis) or
    ``num_devices`` (a 1-axis ``("cells",)`` mesh over the first that
    many local devices); the default uses every local device. Policy /
    ``chunk`` / ``unroll`` / ``backend`` / ``speculative`` knobs match
    ``route_batch`` and configure the per-cell inner path.
    """
    if drain_tokens is not None:
        raise ValueError(
            "drain_tokens drains every server after every request — a "
            "globally-sequential semantics the sharded router cannot "
            "honour; use the time-based FleetParams.drain_rate instead"
        )
    backend = br.resolve_backend(backend)
    if mesh is None:
        d = int(num_devices) if num_devices else len(jax.devices())
        mesh = cells_mesh(d)
    else:
        d = int(mesh.shape[mesh.axis_names[0]])

    order = None
    try:
        layout = br.cell_layout(params)
    except ValueError:
        if params.cell is None:
            raise
        order = br.cell_major_order(params.cell)
        params, state = br.permute_fleet(params, state, order)
        layout = br.cell_layout(params)  # unequal cells still raise here
    c = layout.num_cells
    if outage is not None:
        outage = np.asarray(outage, bool)
        if order is not None:  # follow the cell-major server permutation
            outage = outage[order]
        outage = jnp.asarray(outage)

    if layout.num_cells > 1 and reqs.cell is None:
        raise ValueError("multi-cell sharded routing needs RequestBatch.cell")
    if layout.num_cloud and not np.asarray(
            state.resident)[layout.num_edge:].all():
        raise ValueError(
            "sharded routing requires full-residency cloud columns (see "
            "launch.serve.make_cloud_server): a cloud row that can still "
            "install or evict would diverge across its per-cell copies"
        )

    b = int(reqs.model.shape[0])
    if b == 0:  # nothing to shard; keep the single-device fast path
        return br.route_batch(params, state, reqs, policy=policy,
                              actor=actor, chunk=chunk, unroll=unroll,
                              backend=backend, speculative=speculative,
                              outage=outage)

    c_pad = -(-c // d) * d
    has_time = params.drain_rate is not None and reqs.arrival_s is not None
    time0 = float(np.asarray(state.time_s)) if state.time_s is not None \
        else 0.0
    has_spill = params.spill is not None and params.cell is not None
    bucketed = jax.tree.map(jnp.asarray, _bucket_requests(
        reqs, layout, c_pad, time0, has_time, keep_cells=has_spill))
    plan = br._Plan(policy, actor, chunk, unroll, backend, speculative)
    if has_spill:
        new_state, out = _sharded_route_spill(params, state, bucketed, reqs,
                                              outage, mesh=mesh, plan=plan)
    else:
        new_state, out = _sharded_route(params, state, bucketed, reqs, outage,
                                        mesh=mesh, layout=layout, plan=plan)
    # the cause channel is a post-hoc pure function of visibility, the
    # outage mask and the scattered choices — shared with every other
    # path, so the sharded rates agree bitwise (docs/robustness.md)
    out = out._replace(
        cause=br.rejection_cause(params, reqs, outage, out.choice))

    if order is not None:  # restore the caller's server ordering
        inv = np.argsort(order)
        _, new_state = br.permute_fleet(params, new_state, inv)
        order_j = jnp.asarray(order, jnp.int32)
        ch = out.choice
        out = out._replace(choice=jnp.where(
            ch >= 0, order_j[jnp.clip(ch, 0, order_j.shape[0] - 1)], -1))
    return new_state, out
