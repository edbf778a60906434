"""The speculative commit scan of the chunked router as one Pallas TPU
kernel per chunk.

``core.batch_router``'s speculative path prices a whole chunk of ``c``
requests against the chunk-entry residency (``basez``, a ``(c, N)``
panel), so the only state its serial recurrence carries is the ``(N,)``
queue backlog. Each step is one row of work: decay the queues by the
drain since the last arrival, score ``lats = basez_b + (queue * ftok_b)
/ flops``, take the first-min argmin, and add the request's tokens to the
chosen queue when the gates pass. As an XLA ``lax.scan`` every step is a
chain of separate device ops (the argmin's reduce-to-scalar feeds the
next step's compare, which XLA cannot fuse across), each paying a launch
inside the while loop. This kernel runs the whole chunk in one call: the
queue row lives in vector registers, ``basez`` and the queue trajectory
in VMEM, and the per-request scalars in SMEM.

Layout: the server axis is padded to ``N_pad``, a multiple of 128 lanes,
and every row is laid out as ``(N_pad // 128, 128)`` (one ``(8, 128)``
f32 vreg at N = 1024). Pad lanes score ``+inf`` (``basez``), divide by
1.0 (``flops``) and never drain, so they are never chosen and their
queues stay 0. The per-request scalars are (1, c) rows in SMEM, so the
call batches under ``vmap`` (the sharded router maps it over cell
blocks): Pallas then runs one grid step per batch row.

The body runs the step of ``kernels.ref.spec_scan_xla``: the queue
moves through ``core.queue``'s two functions, applied to the values
loaded from the refs, and the score and gates are the same expressions
in the same order and precision. Its argmin is the row's min, then the
min of ``where(lats == m, lane, N_pad)``: ``jnp.argmin``'s lowest-index
tie-break, and lane 0 for a row that is all ``+inf``. Because ``lats >=
basez`` lane by lane, the chosen ``basez`` is finite exactly when ``m``
is, or, when ``m`` is ``+inf`` (the argmin is then lane 0), when
``basez[0]`` is; scores are never NaN for a fleet with positive rates.
So the finiteness gate needs no third reduction. The kernel also emits
``lats[choice]`` (``m``), the value its own gates compared, so the
caller never re-derives it with a division that might round otherwise.

Math runs in the inputs' dtype: float32 on the chip, float64 under the
interpreter for the x64 oracle tier (``interpret=True``,
``backend="pallas-interpret"`` in ``kernels/ops.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.queue import advance_to_arrival, drain_after_commit

_LANES = 128


def _rows(x, n_pad, fill):
    """(N,) -> (N_pad // 128, 128), right-padded with ``fill``."""
    x = jnp.pad(x, (0, n_pad - x.shape[0]), constant_values=fill)
    return x.reshape(n_pad // _LANES, _LANES)


def _row(x):
    """(c,) -> (1, c). Under ``vmap`` Pallas adds a leading grid axis
    and blocks the array one batch row at a time; Mosaic accepts a block
    only if its last two dims are whole (or (8, 128)-aligned), which a
    (1, c) row's are and a (c,) vector's are not."""
    return jnp.reshape(x, (1,) + x.shape)


def _kernel(*refs, c, has_mask, has_time, has_drain, has_outage, has_valid,
            has_deadline, has_tloc, unroll):
    refs = list(refs)
    take = lambda flag=True: refs.pop(0) if flag else None  # noqa: E731
    basez_ref, flops_ref, queue_ref, ftok_ref, gen_ref = (take() for _ in
                                                          range(5))
    time_ref, rate_ref, arrival_ref = (take(has_time) for _ in range(3))
    drain_ref = take(has_drain)
    outage_ref = take(has_outage)
    valid_ref = take(has_valid)
    deadline_ref = take(has_deadline)
    tloc_ref = take(has_tloc)
    q_out, choice_out, lat_out = take(), take(), take()
    t_out = take(has_time)

    flops = flops_ref[...]
    shape = flops.shape
    lane = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    n_pad = shape[0] * _LANES
    rate = rate_ref[...] if has_time else None
    frozen = outage_ref[...] != 0 if has_outage else None

    queue = queue_ref[...]
    q_out[0] = queue
    time_s = time_ref[0, 0] if has_time else None
    if has_time:
        t_out[0, 0] = time_s

    def body(i, carry):
        queue, time_s = carry
        valid_b = valid_ref[0, i] != 0 if has_valid else None
        if has_time:
            queue, time_s = advance_to_arrival(queue, time_s,
                                               arrival_ref[0, i], rate,
                                               valid_b)
        basez_b = basez_ref[i]
        lats = basez_b + (queue * ftok_ref[0, i]) / flops
        m = jnp.min(lats)
        choice = jnp.min(jnp.where(lats == m, lane, n_pad))
        gate = jnp.bool_(True)
        if has_mask:
            gate = jnp.isfinite(m) | jnp.isfinite(basez_b[0, 0])
        if has_deadline:
            best = jnp.maximum(tloc_ref[0, i], m) if has_tloc else m
            gate &= best <= deadline_ref[0, i]
        if has_valid:
            gate &= valid_b
        queue = queue + jnp.where((lane == choice) & gate, gen_ref[0, i], 0.0)
        if has_drain:
            queue = drain_after_commit(queue, drain_ref[0, i], valid_b,
                                       frozen)
        q_out[i + 1] = queue
        choice_out[0, i] = choice
        lat_out[0, i] = m
        if has_time:
            t_out[0, i + 1] = time_s
        return queue, time_s

    # Mosaic unrolls a fori_loop fully or not at all: unroll by hand,
    # ``unroll`` steps a trip, and run the remainder after the loop
    def trip(j, carry):
        for k in range(unroll):
            carry = body(j * unroll + k, carry)
        return carry

    carry = jax.lax.fori_loop(0, c // unroll, trip, (queue, time_s))
    for i in range(c - c % unroll, c):
        carry = body(i, carry)


def route_spec_scan(basez, ftok, gen, queue, time_s, flops_per_s, *,
                    drain_rate=None, arrival=None, drain=None, outage=None,
                    valid=None, deadline=None, tloc=None, has_mask=False,
                    unroll=1, interpret=False):
    """One chunk of the speculative greedy commit scan.

    ``basez`` (c, N) is the chunk's score panel against the chunk-entry
    residency; ``ftok``/``gen`` (c,) the model's FLOPs per token and the
    committed tokens; ``queue`` (N,) and ``time_s`` () the entry state.
    ``drain_rate`` (N,, outage already folded in) with ``arrival`` (c,)
    turn on the time drain; ``drain`` (c,) the per-request drain, frozen
    on ``outage`` (N,) bool; ``valid`` (c,) bool marks the live requests
    of a padded tail; ``deadline`` (c,) the SLO admission, floored by
    ``tloc`` (c,). ``has_mask`` gates each commit on a finite score.

    Returns ``(queues, choices, lats, times)``: the queue trajectory
    (c+1, N) with the entry row first, the choices (c,)
    int32, the chosen scores ``lats[choice]`` (c,), and the time
    trajectory (c+1,) (``None`` without the time drain).
    """
    c, n = basez.shape
    dtype = basez.dtype
    n_pad = -(-n // _LANES) * _LANES
    has_time = drain_rate is not None
    has_drain = drain is not None
    has_outage = has_drain and outage is not None

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    panel = jnp.pad(basez, ((0, 0), (0, n_pad - n)),
                    constant_values=jnp.inf).reshape(c, n_pad // _LANES,
                                                     _LANES)
    inputs = [panel, _rows(flops_per_s.astype(dtype), n_pad, 1.0),
              _rows(queue, n_pad, 0.0), _row(ftok), _row(gen)]
    specs = [vmem, vmem, vmem, smem, smem]
    if has_time:
        inputs += [jnp.reshape(time_s, (1, 1)),
                   _rows(drain_rate, n_pad, 0.0), _row(arrival)]
        specs += [smem, vmem, smem]
    if has_drain:
        inputs.append(_row(drain))
        specs.append(smem)
    if has_outage:
        inputs.append(_rows(outage.astype(jnp.int32), n_pad, 0))
        specs.append(vmem)
    for col in (valid, deadline, None if deadline is None else tloc):
        if col is not None:
            inputs.append(_row(col.astype(jnp.int32) if col.dtype == bool
                               else col))
            specs.append(smem)

    out_shape = [jax.ShapeDtypeStruct((c + 1, n_pad // _LANES, _LANES), dtype),
                 jax.ShapeDtypeStruct((1, c), jnp.int32),
                 jax.ShapeDtypeStruct((1, c), dtype)]
    out_specs = [vmem, smem, smem]
    if has_time:
        out_shape.append(jax.ShapeDtypeStruct((1, c + 1), dtype))
        out_specs.append(smem)

    outs = pl.pallas_call(
        functools.partial(
            _kernel, c=c, has_mask=has_mask, has_time=has_time,
            has_drain=has_drain, has_outage=has_outage,
            has_valid=valid is not None, has_deadline=deadline is not None,
            has_tloc=deadline is not None and tloc is not None,
            unroll=max(1, min(unroll, c)),
        ),
        in_specs=specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="route_spec_scan",  # the kernel's name in HLO and device traces
    )(*inputs)
    queues = outs[0].reshape(c + 1, n_pad)[:, :n]
    times = outs[3][0] if has_time else None
    return queues, outs[1][0], outs[2][0], times
