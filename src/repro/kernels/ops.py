"""Jit'd dispatch layer: every hot-spot op routes to the Pallas TPU
kernel or to the memory-sane XLA implementation, by ``backend``:

* ``"pallas"`` compiles the kernel for the accelerator. It never falls
  back to emulation: on a CPU backend the Pallas lowering raises.
* ``"pallas-interpret"`` runs the same kernel through the Pallas
  interpreter — the CPU test path, never a timing target.
* ``"xla"`` is the reference implementation in ``ref.py``.

All backends share the oracles in ``ref.py``; tests assert allclose.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref

_PALLAS = ("pallas", "pallas-interpret")


def rmsnorm(x, scale, *, eps: float = 1e-6, backend: str = "xla"):
    if backend in _PALLAS:
        from repro.kernels import rmsnorm as _k

        return _k.rmsnorm(x, scale, eps=eps,
                          interpret=backend == "pallas-interpret")
    return ref.rmsnorm_naive(x, scale, eps)


def attention(q, k, v, *, causal=True, window=0, q_offset=0, backend: str = "xla"):
    if backend in _PALLAS:
        from repro.kernels import flash_attention as _k

        return _k.flash_attention(
            q, k, v, causal, window, q_offset, 128, 128,
            backend == "pallas-interpret",
        )
    return ref.attention_xla(q, k, v, causal=causal, window=window, q_offset=q_offset)


def decode_attention(q, k, v, pos, *, window=0, backend: str = "xla"):
    if backend in _PALLAS:
        from repro.kernels import flash_decode as _k

        return _k.flash_decode(q, k, v, pos, window=window,
                               interpret=backend == "pallas-interpret")
    return ref.decode_attention_naive(q, k, v, pos, window=window)


def ssd(x, dt, a_log, b, c, d_skip, *, chunk: int = 256, backend: str = "xla"):
    if backend in _PALLAS:
        from repro.kernels import ssd_scan as _k

        return _k.ssd(x, dt, a_log, b, c, d_skip, chunk,
                      backend == "pallas-interpret")
    return ref.ssd_chunked_xla(x, dt, a_log, b, c, d_skip, chunk=chunk)


def ssd_decode(state, xt, dtt, a_log, bt, ct, d_skip, *, backend: str = "xla"):
    # single recurrent step is bandwidth-trivial; always the jnp path
    del backend
    return ref.ssd_decode_naive(state, xt, dtt, a_log, bt, ct, d_skip)


def route_score(
    prompt_bits, size_bits, flops_tok, work,
    uplink_bps, backhaul_bps, flops_per_s,
    queue_tokens=None, resident=None, model=None,
    req_cell=None, srv_cell=None, spill=None, eta=None, beta=None,
    *, cloud_cell: int = -1, backend: str = "xla",
):
    """Fused (B, N) eq. 11 routing-score matrix (see ``route_score.py``).

    Backends: ``"xla"`` (reference contraction), ``"pallas"`` (the
    compiled TPU kernel; raises on a CPU backend) and
    ``"pallas-interpret"`` (the kernel under the Pallas interpreter —
    the value CPU tests use). ``eta``/``beta``
    are the eq. 16 partial-offload / download-refusal columns; both
    backends fold them through ``costs.apply_eta_beta`` so the
    transform (and its ``None`` bitwise no-op) is shared.
    """
    if backend in _PALLAS:
        from repro.kernels import route_score as _k

        return _k.route_score(
            prompt_bits, size_bits, flops_tok, work,
            uplink_bps, backhaul_bps, flops_per_s,
            queue_tokens=queue_tokens, resident=resident, model=model,
            req_cell=req_cell, srv_cell=srv_cell, spill=spill,
            eta=eta, beta=beta, cloud_cell=cloud_cell,
            interpret=backend == "pallas-interpret",
        )
    return ref.route_score_xla(
        prompt_bits, size_bits, flops_tok, work,
        uplink_bps, backhaul_bps, flops_per_s,
        queue_tokens=queue_tokens, resident=resident, model=model,
        req_cell=req_cell, srv_cell=srv_cell, spill=spill,
        eta=eta, beta=beta, cloud_cell=cloud_cell,
    )


def route_spec_scan(basez, ftok, gen, queue, time_s, flops_per_s, *,
                    drain_rate=None, arrival=None, drain=None, outage=None,
                    valid=None, deadline=None, tloc=None, has_mask=False,
                    unroll=1, backend: str = "xla"):
    """One chunk of the chunked router's speculative greedy commit scan
    (see ``route_spec_scan.py``); returns ``(queues, choices, lats,
    times)``: the queue trajectory (c+1, N) with the entry row first, the
    choices, the score each commit gate compared, and the time trajectory.

    Backends: ``"xla"`` (the reference ``lax.scan``), ``"pallas"`` (one
    compiled TPU kernel call for the whole chunk) and
    ``"pallas-interpret"`` (that kernel under the Pallas interpreter).
    Both give the same results bit for bit."""
    kw = dict(drain_rate=drain_rate, arrival=arrival, drain=drain,
              outage=outage, valid=valid, deadline=deadline, tloc=tloc,
              has_mask=has_mask, unroll=unroll)
    if backend in _PALLAS:
        from repro.kernels import route_spec_scan as _k

        return _k.route_spec_scan(
            basez, ftok, gen, queue, time_s, flops_per_s, **kw,
            interpret=backend == "pallas-interpret",
        )
    return ref.spec_scan_xla(basez, ftok, gen, queue, time_s, flops_per_s,
                             **kw)
