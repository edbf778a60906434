"""Minimal functional NN layer-kit (no flax in this environment).

Convention used across the framework: ``init(key, ...) -> params`` pytree,
``apply(params, x) -> y``. Per-agent networks are *stacked* parameter
pytrees (leading axis = agent) driven through ``jax.vmap`` — this realises
the paper's "each ED has its own actor/critic" with MXU-friendly batched
matmuls instead of M python-level modules.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def mlp_init(key, sizes: Sequence[int], final_scale: float = 1.0):
    params = []
    keys = jax.random.split(key, len(sizes) - 1)
    for i, k in enumerate(keys):
        fan_in, fan_out = sizes[i], sizes[i + 1]
        scale = jnp.sqrt(2.0 / fan_in)
        if i == len(keys) - 1:
            scale = scale * final_scale
        w = jax.random.normal(k, (fan_in, fan_out), jnp.float32) * scale
        b = jnp.zeros((fan_out,), jnp.float32)
        params.append({"w": w, "b": b})
    return params


def mlp_apply(params, x, precision=None):
    """``precision`` is the matmul precision (``jax.lax.Precision``);
    ``None`` takes the platform default, one bfloat16 pass on a TPU."""
    for i, layer in enumerate(params):
        x = jnp.matmul(x, layer["w"], precision=precision) + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


def stacked_init(key, num: int, sizes: Sequence[int], final_scale: float = 1.0):
    """num independent MLPs stacked on a leading axis."""
    keys = jax.random.split(key, num)
    return jax.vmap(lambda k: mlp_init(k, sizes, final_scale))(keys)


def stacked_apply(params, x):
    """params leading axis = agents; x: (num, ..., in) -> (num, ..., out)."""
    return jax.vmap(mlp_apply)(params, x)


def soft_update(target, online, tau: float):
    return jax.tree.map(lambda t, o: (1.0 - tau) * t + tau * o, target, online)
