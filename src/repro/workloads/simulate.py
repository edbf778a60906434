"""Long-horizon serving simulator: an arbitrarily long request stream
windowed into chunked ``route_batch`` calls.

``route_batch`` already owns the semantics (sequential commit, cell
mask, time-based drain); the simulator's job is the EPISODE: it slices
the stream into fixed-size request windows, routes each window with the
``FleetState`` carried from the previous one (LRU residency, queues,
``time_s`` — nothing resets between windows), and builds a per-window
time series (``core.batch_router.window_stats``, one row a window) plus
queue-depth percentiles sampled at every window boundary — the only
instants the queues are observable from outside the jitted call.

The window loop is a one-deep pipeline: the call's columns are split
into windows once, window i+1 is dispatched before the host waits on
window i, and window i's percentiles, stats row and energy are taken
while i+1 runs, so the device waits only on the last window's
bookkeeping. ``np.bincount`` sums each window in stream order, so a row
taken alone equals the whole-stream call's row bit for bit.

Because the scan commits requests strictly in stream order, windowing
is a pure re-chunking: for a drain-free stream the W-window episode
bit-matches ONE ``route_batch`` call on the whole stream (choices,
latencies, final state — pinned by ``tests/test_workloads.py``).
Fixed-size windows also keep the jit cache small: every window shares
one compiled program (+1 for a ragged tail).

Each call is one ``repro.simulate`` span (``repro.obs``) with a
``window``, ``wait`` and ``sample`` span a window and one ``stats`` span
at the end, so the host's share of an episode can be told from the
device's. Window i's ``wait`` and ``sample`` follow window i+1's
``window``; ``sample`` counts ``in_flight``, the later windows already
dispatched (1, and 0 for a call's last window).

``benchmarks/scenario_suite.py`` runs this over the full policies x
scenarios matrix; ``examples/serve_edge.py`` prints one time series.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import batch_router as br
from repro.core import costs


def request_energy_j(params: br.FleetParams, reqs: br.RequestBatch,
                     outcome: br.RouteOutcome, *, p_tx: float = 0.5,
                     p_bh: float = 2.0, kappa: float = 1e-29) -> np.ndarray:
    """Per-request serving energy (J), the eq. 6/8/10 analogue through
    the ``core.costs`` functions (the single home of the cost
    arithmetic): uplink transmission + model switch (when the request
    missed residency) + edge compute (``kappa * f^2 * work/f``). Zero
    for rejected requests.

    Under partial offload (``reqs.eta``) the edge side only transmits
    and computes the ``eta`` fraction, so both the eq. 6 and the eq. 10
    analogue scale with it — a committed ``beta = False`` request is
    necessarily a residency hit (refused misses price ``+inf`` and are
    never chosen), so the eq. 8 hit-gating already covers the download
    decision. The shared metric under ``benchmarks/policy_serving.py``
    and the per-window series here."""
    choice = np.asarray(outcome.choice)
    ok = choice >= 0
    ch = np.maximum(choice, 0)
    model = np.asarray(reqs.model)
    flops = np.asarray(params.flops_per_s)[ch]
    prompt = np.asarray(reqs.prompt_bits)
    work = (np.asarray(reqs.gen_tokens)
            * np.asarray(params.decode_flops_per_token)[model])
    if reqs.eta is not None:  # eq. 16 offload ratio: edge share only
        eta = np.asarray(reqs.eta)
        prompt = prompt * eta
        work = work * eta
    t_trans = costs.trans_latency(
        prompt, 1.0, np.asarray(params.uplink_bps)[ch]
    )
    t_switch = np.where(
        np.asarray(outcome.hit), 0.0,
        costs.switch_latency(np.asarray(params.size_bits)[model],
                             np.asarray(params.backhaul_bps)[ch]),
    )
    e = costs.edge_total_energy(
        costs.trans_energy(p_tx, t_trans),
        costs.switch_energy(p_bh, t_switch),
        kappa * flops**2 * (work / flops),
    )
    return np.where(ok, np.asarray(e), 0.0)


def mean_request_energy_j(params: br.FleetParams, reqs: br.RequestBatch,
                          outcome: br.RouteOutcome, **kw) -> float:
    """Mean eq. 6/8/10 serving energy over COMPLETED requests — the
    aggregate both ``benchmarks/policy_serving.py`` and
    ``benchmarks/scenario_suite.py`` record."""
    ok = np.asarray(outcome.choice) >= 0
    return float(request_energy_j(params, reqs, outcome, **kw).sum()
                 / max(ok.sum(), 1))


class SimResult(NamedTuple):
    """Per-window time series of one simulated episode (arrays of length
    W = number of windows). Latency/completion/hit/cloud come from
    ``batch_router.window_stats``; the queue percentiles are over the
    EDGE servers' outstanding tokens at each window's end (the cloud
    column, when present, is excluded — its depth only dilutes the edge
    signal). The per-cause rejection rates share the window-size
    denominator with ``completion_rate``, so the four series sum to 1
    in every window (``docs/robustness.md``)."""

    window_start_s: np.ndarray    # first arrival in the window
    window_end_s: np.ndarray      # last arrival in the window
    requests: np.ndarray          # (W,) int — window sizes
    mean_latency: np.ndarray      # completed requests only
    mean_energy_j: np.ndarray     # completed requests only (eq. 6/8/10)
    completion_rate: np.ndarray
    residency_hit_rate: np.ndarray
    cloud_fallback_rate: Optional[np.ndarray]  # None without a cloud column
    queue_p50: np.ndarray         # edge queue depth percentiles at window end
    queue_p90: np.ndarray
    queue_max: np.ndarray
    infeasible_rate: Optional[np.ndarray] = None  # no visible server
    admission_rate: Optional[np.ndarray] = None   # best score > deadline_s
    outage_rate: Optional[np.ndarray] = None      # all visible servers down


def _fault_mask(windows, n: int, t: float) -> np.ndarray:
    """(n,) bool: servers whose ``(server, start_s, end_s)`` fault
    window is active at wall clock ``t`` (half-open, ``start <= t <
    end``)."""
    mask = np.zeros(n, bool)
    for srv, start, end in windows:
        if start <= t < end:
            mask[int(srv)] = True
    return mask


# the request columns ``request_energy_j`` reads, fetched once a call
_ENERGY_COLUMNS = ("model", "prompt_bits", "gen_tokens", "eta")


@functools.partial(jax.jit, static_argnames="w")
def _split_windows(cols, w: int):
    """``cols``, a pytree of (B,) columns, as its windows of ``w``
    requests (full windows, then the ragged tail) in one program per
    ``(B, w)``: one dispatch a call in place of one a column a window."""
    b = jax.tree.leaves(cols)[0].shape[0]
    return [jax.tree.map(lambda x: x[i:i + w], cols)
            for i in range(0, max(b, 1), w)]


def simulate(params: br.FleetParams, state: br.FleetState,
             reqs: br.RequestBatch, *, policy="greedy", actor=None,
             window_requests: int = 256, drain_tokens=None,
             chunk: Optional[int] = None, unroll: int = 8,
             backend: Optional[str] = None,
             cloud_index: Optional[int] = None,
             mesh=None, num_devices: Optional[int] = None,
             faults=None):
    """Route ``reqs`` through W sequential windows, carrying the fleet
    state across window boundaries; returns ``(state, outcome, series)``
    with ``outcome`` the concatenated ``RouteOutcome`` of the whole
    stream and ``series`` the per-window ``SimResult``.

    All ``route_batch`` knobs pass through (``policy``/``actor``,
    ``chunk``/``unroll``/``backend``, per-request ``drain_tokens``);
    ``cloud_index`` (the cloud column's server index, conventionally the
    last) adds the cloud-fallback rate to the series and excludes that
    column from the queue percentiles.

    ``mesh``/``num_devices`` switch each window to the mesh-sharded
    router (``core.mesh_router.route_batch_sharded``): a simulator
    window IS the sharded router's reconciliation window, so cells see
    each other's cloud commits at exactly the boundaries the series
    samples. Mutually exclusive with ``drain_tokens`` (a cross-cell
    sequential coupling the sharded window model cannot honour).

    ``faults`` (a ``workloads.scenario.FaultSpec``) injects server
    faults: each window is routed under the fault masks active at its
    FIRST arrival — full ``outages`` become the router's ``outage``
    mask (``+inf`` column, frozen queue, ``CAUSE_OUTAGE`` rejections),
    ``drain_outages`` zero the affected servers' ``drain_rate`` (they
    keep accepting work). Fault-free windows compile the knobs out, so
    a schedule costs at most one extra jit program."""
    sharded = mesh is not None or num_devices is not None
    if sharded:
        if drain_tokens is not None:
            raise ValueError(
                "drain_tokens couples every request to the previous one "
                "fleet-wide; the mesh-sharded windows cannot honour it — "
                "drop the mesh or use params.drain_rate time-based drain"
            )
        from repro.core import mesh_router
    n_srv = int(np.asarray(params.flops_per_s).shape[0])
    if faults is not None and (faults.outages or faults.drain_outages):
        for srv, _, _ in (*faults.outages, *faults.drain_outages):
            if not 0 <= int(srv) < n_srv:
                raise ValueError(
                    f"fault window names server {srv} but the fleet has "
                    f"{n_srv} servers"
                )
        if reqs.arrival_s is None:
            raise ValueError(
                "fault windows are scheduled against wall-clock arrival "
                "stamps; the request stream carries none (arrival_s=None)"
            )
        if faults.drain_outages and params.drain_rate is None:
            raise ValueError(
                "drain_outages stall FleetParams.drain_rate, but this "
                "fleet has no continuous drain configured"
            )
    else:
        faults = None
    b = int(reqs.model.shape[0])
    w = max(1, int(window_requests))
    n_windows = max(1, math.ceil(b / w))
    per_request_drain = drain_tokens is not None and np.ndim(drain_tokens) == 1
    with obs.span("repro.simulate", requests=b, windows=n_windows):
        outs, rows, q50, q90, qmax = [], [], [], [], []
        arr_np = np.asarray(reqs.arrival_s) if faults is not None else None

        def read_back(j, queue, out):
            """Window ``j``'s queue percentiles and its row of the series,
            on the host, while the windows after it run."""
            with obs.span("repro.simulate.wait"):
                jax.block_until_ready((queue, out))
            with obs.span("repro.simulate.sample",
                          in_flight=len(outs) - j - 1):
                q = np.asarray(queue)
                if cloud_index is not None:
                    q = np.delete(q, cloud_index)
                q50.append(np.percentile(q, 50))
                q90.append(np.percentile(q, 90))
                qmax.append(q.max())
                out = jax.device_get(out)
                sl = slice(j * w, min((j + 1) * w, b))
                win = br.RequestBatch(**{f: None if v is None else v[sl]
                                         for f, v in host.items()})
                rows.append(br.window_stats(
                    out, np.zeros(sl.stop - sl.start, np.int64), 1,
                    cloud_index=cloud_index,
                    completed_means={"mean_energy_j": request_energy_j(
                        host_params, win, out)},
                ))

        wins = _split_windows(
            (reqs, jnp.asarray(drain_tokens) if per_request_drain else None),
            w)
        for i, (win, dw) in enumerate(wins):
            with obs.span("repro.simulate.window"):
                if not per_request_drain:
                    dw = drain_tokens
                params_w, outage = params, None
                if faults is not None:
                    t = float(arr_np[i * w])  # the window's first arrival
                    om = _fault_mask(faults.outages, n_srv, t)
                    if om.any():
                        outage = jnp.asarray(om)
                    dm = _fault_mask(faults.drain_outages, n_srv, t)
                    # stalled drain: still routable, backlog grows
                    if dm.any():
                        params_w = params._replace(drain_rate=jnp.where(
                            jnp.asarray(dm), 0.0, params.drain_rate))
                if sharded:
                    state, out = mesh_router.route_batch_sharded(
                        params_w, state, win, mesh=mesh,
                        num_devices=num_devices, policy=policy, actor=actor,
                        chunk=chunk, unroll=unroll, backend=backend,
                        outage=outage)
                else:
                    state, out = br.route_batch(params_w, state, win, dw,
                                                policy=policy, actor=actor,
                                                chunk=chunk, unroll=unroll,
                                                backend=backend, outage=outage)
                outs.append(out)
            if i == 0:  # the host's copy of the call, behind window 0
                host_params = jax.device_get(params)
                host = jax.device_get(
                    {f: getattr(reqs, f) for f in _ENERGY_COLUMNS})
                if reqs.arrival_s is not None:
                    arr = (arr_np if arr_np is not None
                           else np.asarray(reqs.arrival_s))
                else:  # no wall clock: request indices as the time axis
                    arr = np.arange(b, dtype=float)
                t0 = np.minimum.reduceat(arr, np.arange(0, b, w))
                t1 = np.maximum.reduceat(arr, np.arange(0, b, w))
            else:
                read_back(i - 1, *pending)
            pending = (state.queue_tokens, out)
        read_back(n_windows - 1, *pending)

        with obs.span("repro.simulate.stats"):
            outcome = br.RouteOutcome(
                *(jnp.concatenate([getattr(o, f) for o in outs])
                  for f in br.RouteOutcome._fields)
            )
            stats = {k: np.concatenate([r[k] for r in rows])
                     for k in rows[0]}
            series = SimResult(
                window_start_s=t0, window_end_s=t1,
                requests=stats["requests"],
                mean_latency=stats["mean_latency"],
                mean_energy_j=stats["mean_energy_j"],
                completion_rate=stats["completion_rate"],
                residency_hit_rate=stats["residency_hit_rate"],
                cloud_fallback_rate=stats.get("cloud_fallback_rate"),
                queue_p50=np.asarray(q50), queue_p90=np.asarray(q90),
                queue_max=np.asarray(qmax),
                infeasible_rate=stats.get("infeasible_rate"),
                admission_rate=stats.get("admission_rate"),
                outage_rate=stats.get("outage_rate"),
            )
    return state, outcome, series
