"""The router's queue model: how the ``(queue, time_s)`` carry evolves.

Every commit path evolves the fleet's queue backlog through these two
functions: the single and the chunked scans of ``core.batch_router``,
the speculative commit scan (``kernels.ref.spec_scan_xla`` and the
Pallas body of ``kernels.route_spec_scan``, which applies them to values
already loaded from its refs) and the window-close replays of
``core.mesh_router``. They are pure elementwise ``jnp``, so each path
compiles the same ops in the same order. The scalar oracle
``core.router`` keeps its own copy: it is what they are checked against.
"""
from __future__ import annotations

import jax.numpy as jnp


def advance_to_arrival(queue, time_s, arrival, rate, valid=None):
    """Decay ``queue`` (N,) to a request's ``arrival``: each server has
    drained ``rate * dt`` tokens, ``dt`` the time since the carry clock
    ``time_s``, and no queue goes below zero. ``rate`` (N,) is already
    zero on outaged servers (a frozen queue). ``valid`` False (a padded
    row) leaves both the queue and the clock as they were. Returns
    ``(queue, time_s)``."""
    dt = jnp.maximum(arrival - time_s, 0.0)
    if valid is not None:
        dt = jnp.where(valid, dt, 0.0)
        time_s = jnp.where(valid, jnp.maximum(time_s, arrival), time_s)
    else:
        time_s = jnp.maximum(time_s, arrival)
    return jnp.maximum(queue - rate * dt, 0.0), time_s


def drain_after_commit(queue, tokens, valid=None, frozen=None):
    """The per-request drain (``route_batch``'s ``drain_tokens``): after
    each commit every queue loses ``tokens``, down to zero. ``valid``
    False (a padded row) drains nothing, nor does a ``frozen`` (N,)
    (outaged) server."""
    if valid is not None:
        tokens = jnp.where(valid, tokens, 0.0)
    if frozen is not None:
        tokens = jnp.where(frozen, 0.0, tokens)
    return jnp.maximum(queue - tokens, 0.0)
