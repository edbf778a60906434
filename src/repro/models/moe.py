"""Mixture-of-Experts FFN — sorted-token ragged dispatch, TPU-adapted.

GPU MoE implementations scatter tokens through global memory (megablocks);
the TPU-native adaptation here sorts tokens by expert id *locally on each
data shard* and drives ``jax.lax.ragged_dot`` over the contiguous groups —
MXU-friendly, no (tokens, experts, capacity) one-hot dispatch tensors, and
fully dropless. Expert weights are sharded tensor-parallel on the expert
ff dimension over the ``model`` axis; the contraction is completed with a
single psum (identical collective pattern to the dense FFN, so MoE and
dense cells are directly comparable in the roofline table).

Two entry points:
  * ``moe_apply_local``  — pure-jnp, no collectives (unit tests, 1 device)
  * ``moe_apply``        — wraps the local fn in shard_map over the mesh
    (the data-shard-local sort is what makes this legal: no cross-device
    token traffic, unlike an auto-pjit argsort over a sharded axis).

Router aux loss (load balancing, Switch-style) is returned alongside.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig


def moe_init(key, cfg: ArchConfig):
    d, e, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    nrm = lambda k, shape, s: (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)
    return {
        "router": nrm(ks[0], (d, e), d**-0.5).astype(jnp.float32),
        "wg": nrm(ks[1], (e, d, ff), d**-0.5),
        "wu": nrm(ks[2], (e, d, ff), d**-0.5),
        "wd": nrm(ks[3], (e, ff, d), ff**-0.5),
    }


def moe_apply_local(params, x, cfg: ArchConfig, axis_name: str | None = None,
                    impl: str | None = None, capacity_factor: float = 1.25):
    """x: (B, S, d) shard-local. Returns (y, aux_loss).

    ``impl="ragged"`` drives jax.lax.ragged_dot over the sorted groups —
    the TPU-native path. ``impl="scan"`` (default here) scans the experts
    with a static per-expert capacity (ceil(cf * T * k / E)) and dense
    MXU panels; tokens past capacity drop (cf=1.25 keeps drops ~0 under
    the aux-balanced router). The CPU dry-run must use "scan":
    ragged_dot's CPU decomposition materialises (E, T*k, d) masks —
    observed 1 TiB+ buffers at prefill_32k on qwen3-moe.
    """
    impl = impl or cfg.moe_impl
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    xt = x.reshape(t, d).astype(cd)

    # --- routing (fp32) ---
    logits = xt.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)  # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)  # renormalise over top-k

    # Switch aux loss: E * sum_e f_e * p_e  (f = token fraction, p = mean prob)
    f = jnp.zeros((e,)).at[ids.reshape(-1)].add(1.0) / (t * k)
    p_mean = probs.mean(axis=0)
    aux = e * jnp.sum(f * p_mean)

    # --- sort token-replicas by expert id ---
    flat_ids = ids.reshape(-1)  # (T*k,)
    sort_idx = jnp.argsort(flat_ids)
    token_of = sort_idx // k  # original token for each sorted slot
    xs = xt[token_of]  # (T*k, d) grouped by expert
    group_sizes = jnp.bincount(flat_ids, length=e).astype(jnp.int32)

    if impl == "ragged":
        g = jax.lax.ragged_dot(xs, params["wg"].astype(cd), group_sizes)
        u = jax.lax.ragged_dot(xs, params["wu"].astype(cd), group_sizes)
        h = jax.nn.silu(g) * u
        out = jax.lax.ragged_dot(h, params["wd"].astype(cd), group_sizes)
    elif impl == "group":
        out = _group_experts(params, xs, flat_ids, sort_idx, group_sizes, cfg,
                             capacity_factor, cd)
    else:
        out = _scan_experts(params, xs, group_sizes, cfg, capacity_factor, cd)
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)  # complete the ff contraction (TP)

    # --- unsort + gate-weighted combine ---
    gate_sorted = gate.reshape(-1)[sort_idx].astype(cd)
    y = jnp.zeros((t, d), cd).at[token_of].add(out * gate_sorted[:, None])
    return y.reshape(b, s, d), aux


def _group_experts(params, xs, flat_ids, sort_idx, group_sizes, cfg,
                   capacity_factor, cd):
    """§Perf iteration: fixed-slot capacity layout + ONE batched einsum.

    Scatter each sorted row into slot (expert*cap + rank-in-group), run
    (E, cap, d) x (E, d, ff) batched matmuls (one MXU-friendly einsum, no
    128-step scan, no dynamic-slice read-modify-write traffic), gather
    rows back. Same drop semantics as the scan impl (rank >= cap drops).
    """
    rows, d = xs.shape
    e = cfg.num_experts
    cap = int(capacity_factor * rows / e + 0.5)
    cap = max(8, -(-cap // 8) * 8)
    cap = min(cap, rows)
    sorted_ids = flat_ids[sort_idx]                      # (rows,) grouped
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)[:-1]]
    )
    rank = jnp.arange(rows) - starts[sorted_ids]         # rank within group
    slot = sorted_ids * cap + jnp.minimum(rank, cap - 1)
    keep = (rank < cap)[:, None]

    buf = jnp.zeros((e * cap, d), cd).at[slot].set(jnp.where(keep, xs, 0.0))
    xg = buf.reshape(e, cap, d)
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xg, params["wg"].astype(cd))
    ) * jnp.einsum("ecd,edf->ecf", xg, params["wu"].astype(cd))
    og = jnp.einsum("ecf,efd->ecd", h, params["wd"].astype(cd))
    out = og.reshape(e * cap, d)[slot]                   # (rows, d)
    return jnp.where(keep, out, 0.0)


def _scan_experts(params, xs, group_sizes, cfg, capacity_factor, cd):
    """Static-capacity expert scan over the sorted token stream."""
    rows, d = xs.shape
    e = cfg.num_experts
    cap = int(capacity_factor * rows / e + 0.5)
    cap = max(8, -(-cap // 8) * 8)  # round up to 8
    cap = min(cap, rows)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)[:-1]]
    )
    # pad so dynamic_slice(start, cap) never clamps
    xs_pad = jnp.concatenate([xs, jnp.zeros((cap, d), xs.dtype)], axis=0)
    y_pad = jnp.zeros_like(xs_pad)

    def one_expert(y_acc, inp):
        wg, wu, wd, start, size = inp
        xe = jax.lax.dynamic_slice(xs_pad, (start, 0), (cap, d))
        valid = (jnp.arange(cap) < size)[:, None]
        h = jax.nn.silu(xe @ wg.astype(cd)) * (xe @ wu.astype(cd))
        oe = h @ wd.astype(cd)
        cur = jax.lax.dynamic_slice(y_acc, (start, 0), (cap, d))
        oe = jnp.where(valid, oe, cur)  # keep neighbours outside our group
        return jax.lax.dynamic_update_slice(y_acc, oe, (start, 0)), None

    y_pad, _ = jax.lax.scan(
        one_expert, y_pad,
        (params["wg"], params["wu"], params["wd"], starts, group_sizes),
    )
    return y_pad[:rows]


def moe_apply_ep_local(params, x, cfg: ArchConfig, axis_name: str = "model"):
    """Expert-parallel shard-local body: this model shard owns experts
    [idx*E_loc, (idx+1)*E_loc) with FULL ff width; it routes the (model-
    replicated) local tokens, computes only its experts' share, and a psum
    over ``axis_name`` combines — identical FLOPs and collective volume to
    the TP layout, but expert matmuls stay MXU-wide (qwen3-moe: ff 1536
    vs 1536/16=96 under TP; see EXPERIMENTS.md §Perf B3)."""
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    e_loc = params["wg"].shape[0]  # experts owned by this shard
    t = b * s
    xt = x.reshape(t, d).astype(cd)

    logits = xt.astype(jnp.float32) @ params["router"]  # router replicated
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    f = jnp.zeros((e,)).at[ids.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(f * probs.mean(axis=0))

    offset = jax.lax.axis_index(axis_name) * e_loc
    flat_ids = ids.reshape(-1)
    local = (flat_ids >= offset) & (flat_ids < offset + e_loc)
    # sort with non-local replicas pushed to a tail bucket (id e_loc)
    local_ids = jnp.where(local, flat_ids - offset, e_loc)
    sort_idx = jnp.argsort(local_ids)
    token_of = sort_idx // k
    xs = xt[token_of]
    group_sizes = jnp.bincount(local_ids, length=e_loc + 1)[:-1].astype(jnp.int32)

    cfg_loc = dataclasses.replace(cfg, num_experts=e_loc)
    # capacity must follow the GLOBAL expert count: only ~rows*e_loc/e of
    # this shard's row stream is local (the rest sits in the tail bucket)
    out = _dispatch_sorted(params, xs, group_sizes, cfg_loc, cd,
                           capacity_factor=1.25 * e_loc / e)

    gate_sorted = jnp.where(local[sort_idx], gate.reshape(-1)[sort_idx], 0.0)
    y = jnp.zeros((t, d), cd).at[token_of].add(out * gate_sorted[:, None].astype(cd))
    y = jax.lax.psum(y, axis_name)
    return y.reshape(b, s, d), aux


def _dispatch_sorted(params, xs, group_sizes, cfg_loc, cd,
                     capacity_factor=1.25):
    """Run the configured impl on an already expert-sorted row stream
    (rows beyond sum(group_sizes) belong to other shards and produce 0)."""
    if cfg_loc.moe_impl == "group":
        rows = xs.shape[0]
        sorted_ids = jnp.clip(
            jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(rows),
                             side="right"),
            0, cfg_loc.num_experts - 1,
        ).astype(jnp.int32)
        return _group_experts(params, xs, sorted_ids, jnp.arange(rows),
                              group_sizes, cfg_loc, capacity_factor, cd)
    return _scan_experts(params, xs, group_sizes, cfg_loc, capacity_factor, cd)


def moe_apply(params, x, cfg: ArchConfig, mesh=None):
    """Auto-sharded entry: shard_map over (pod)+data+model axes."""
    if mesh is None:
        return moe_apply_local(params, x, cfg)
    batch_axes = tuple(n for n in mesh.axis_names if n in ("pod", "data"))
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    # decode at global_batch=1 cannot shard the batch dim — replicate it
    shard_batch = x.shape[0] % n_batch_shards == 0
    x_spec = P(batch_axes, None, None) if shard_batch else P(None, None, None)
    ep = cfg.moe_parallel == "ep" and cfg.num_experts % mesh.shape["model"] == 0
    if ep:
        # expert parallelism: each model shard owns E/16 FULL-width experts
        wspecs = {
            "router": P(),
            "wg": P("model", None, None),
            "wu": P("model", None, None),
            "wd": P("model", None, None),
        }
    else:
        # tensor parallelism within experts (ff sharded)
        wspecs = {
            "router": P(),
            "wg": P(None, None, "model"),
            "wu": P(None, None, "model"),
            "wd": P(None, "model", None),
        }
    specs_in = (wspecs, x_spec)

    def local(prm, xloc):
        if ep:
            y, aux = moe_apply_ep_local(prm, xloc, cfg, axis_name="model")
        else:
            y, aux = moe_apply_local(prm, xloc, cfg, axis_name="model")
        if shard_batch:
            aux = jax.lax.pmean(aux, batch_axes)
        return y, aux

    y, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=specs_in,
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params, x)
    return y, aux
