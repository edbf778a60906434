"""Sharding rules: parameter/batch/cache PartitionSpecs for every arch.

Axis semantics (DESIGN.md §7.3):
  * ``pod``   — pure data parallelism across pods; only gradient
    all-reduce crosses it (optionally int8-compressed, distributed/compression.py).
  * ``data``  — batch sharding + FSDP: parameters and optimizer moments
    are additionally sharded over ``data`` and all-gathered on use.
  * ``model`` — tensor parallelism: attention heads, ff, vocab, expert-ff.

Rules are path-based over the parameter pytree and check divisibility:
a dimension that does not divide evenly falls back to replication for
attention heads (tiny archs like smollm-135m) and to GSPMD padding for
vocab (mamba2's 50280).
"""
from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with explicit-Auto axis types.

    Unlike raw ``jax.make_mesh`` — which silently builds the mesh over a
    SUBSET of the platform's devices whenever ``prod(axis_shapes)`` is
    smaller than ``len(jax.devices())`` (the rest of the fleet sits idle
    with no error) — the axis shapes here must account for every device
    the mesh draws from. To deliberately undersubscribe, pass the subset
    explicitly: ``devices=jax.devices()[:n]``.
    """
    want = 1
    for s in axis_shapes:
        want *= int(s)
    avail = list(devices) if devices is not None else jax.devices()
    if want != len(avail):
        source = (
            "the devices argument supplies"
            if devices is not None
            else "the platform exposes"
        )
        raise ValueError(
            f"mesh axis shapes {tuple(axis_shapes)} require {want} device(s) "
            f"but {source} {len(avail)}; pass an explicit subset "
            "(devices=jax.devices()[:n]) to build a smaller mesh"
        )
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


def batch_axes(mesh):
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))


def _nbatch(mesh):
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def _div(n, mesh, axis="model"):
    return n % mesh.shape[axis] == 0


def param_specs(params, cfg: ArchConfig, mesh, fsdp: bool = True):
    """PartitionSpec pytree matching ``params`` (from lm.init_params)."""
    model_ok_heads = _div(cfg.num_heads, mesh) if cfg.num_heads else False
    model_ok_kv = _div(cfg.num_kv_heads, mesh) if cfg.num_kv_heads else False
    dax = "data" if fsdp else None

    vocab_ok = _div(cfg.vocab, mesh)  # pjit arg shardings must divide evenly

    def rule(path: str, leaf) -> P:
        nd = leaf.ndim
        # --- embeddings / head ---
        vax = "model" if vocab_ok else None
        if re.search(r"(^|/)embed$", path):
            if nd == 3:  # audio: (C, V, d)
                return P(None, vax, dax)
            return P(vax, dax)
        if re.search(r"(^|/)head$", path):
            if nd == 3:  # audio: (C, d, V)
                return P(None, dax, vax)
            return P(dax, vax)
        # --- attention ---
        if re.search(r"attn/w[q]$", path):
            return P(dax, "model" if model_ok_heads else None, None)
        if re.search(r"attn/w[kv]$", path):
            return P(dax, "model" if model_ok_kv else None, None)
        if re.search(r"attn/wo$", path):
            return P("model" if model_ok_heads else None, None, dax)
        if re.search(r"attn/(q_norm|k_norm)$", path):
            return P(None)
        # --- dense mlp ---
        if re.search(r"mlp/w[gu]$", path):
            return P(dax, "model")
        if re.search(r"mlp/wd$", path):
            return P("model", dax)
        # --- moe (stored FSDP+TP or FSDP+EP; shard_map view gathers data) ---
        if re.search(r"moe/router$", path):
            return P(None, None)
        if cfg.moe_parallel == "ep" and _div(cfg.num_experts, mesh):
            if re.search(r"moe/w[gud]$", path):
                return P("model", dax, None)
        if re.search(r"moe/w[gu]$", path):
            return P(None, dax, "model")
        if re.search(r"moe/wd$", path):
            return P(None, "model", dax)
        # --- mamba2 ---
        if re.search(r"mix/w[zx]$", path):
            return P(dax, "model")
        if re.search(r"mix/(wb|wc|wdt)$", path):
            return P(dax, None)
        if re.search(r"mix/conv_x$", path):
            return P(None, "model")
        if re.search(r"mix/conv_bias_x$", path):
            return P("model")
        if re.search(r"mix/(conv_b|conv_c|conv_bias_b|conv_bias_c)$", path):
            return P(None) if nd == 1 else P(None, None)
        if re.search(r"mix/norm_scale$", path):
            return P("model")
        if re.search(r"mix/out_proj$", path):
            return P("model", dax)
        if re.search(r"mix/(a_log|d_skip|dt_bias)$", path):
            return P(None)
        # --- norms & everything else: replicated ---
        return P(*([None] * nd))

    def walk(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else k) for k, v in tree.items()}
        # stacked leaves have leading layer axes; specs must be rank-matched.
        return None  # placeholder, handled below

    # flatten with paths so stacked (L, ...) leaves get a leading None axis
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        # count leading stacking axes: blocks/... and groups/... are scanned
        lead = 0
        if re.search(r"(^|/)(blocks|tail)/", pstr):
            lead = 1
        elif re.search(r"(^|/)groups/", pstr):
            lead = 2
        core = pstr
        base_spec = rule(core, _strip_lead(leaf, lead))
        spec = P(*([None] * lead + list(base_spec)))
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


class _FakeLeaf:
    def __init__(self, ndim):
        self.ndim = ndim


def _strip_lead(leaf, lead):
    return _FakeLeaf(leaf.ndim - lead)


def batch_spec(cfg: ArchConfig, mesh, global_batch: int):
    """tokens/labels (B, S[, C]) and patch_embeds (B, S, d)."""
    bspec = batch_axes(mesh) if global_batch % _nbatch(mesh) == 0 else None
    def spec_for(leaf_ndim):
        return P(*([bspec] + [None] * (leaf_ndim - 1)))
    return spec_for


def cache_specs(cache, cfg: ArchConfig, mesh, global_batch: int):
    """Decode-cache specs: batch over (pod,data) when divisible; the KV
    sequence dim over ``model`` (sequence-parallel decode attention —
    XLA completes the softmax with small (B,H) all-reduces); mamba
    d_inner/heads over ``model``."""
    bax = batch_axes(mesh) if global_batch % _nbatch(mesh) == 0 else None

    def rule(path: str, leaf):
        lead = 1  # every cache leaf is stacked over layers/groups
        if re.search(r"(^|/)groups/", path):
            lead = 2
        nd = leaf.ndim - lead
        if re.search(r"(^|/)(k|v|k_scale|v_scale)$", path):  # (B, S, KV, hd|1)
            spec = [bax, "model", None, None]
        elif re.search(r"conv_x$", path):  # (B, K-1, di)
            spec = [bax, None, "model"]
        elif re.search(r"(conv_b|conv_c)$", path):  # (B, K-1, n)
            spec = [bax, None, None]
        elif re.search(r"ssd$", path):  # (B, H, P, N)
            spec = [bax, "model" if _div(cfg.ssm_heads, mesh) else None, None, None]
        else:
            spec = [None] * nd
        return P(*([None] * lead + spec))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        specs.append(rule(pstr, leaf))
    return jax.tree_util.tree_unflatten(treedef, specs)


def constrain(x, mesh, *dims):
    """with_sharding_constraint helper; no-op when mesh is None.

    ``dims`` are per-dimension axis names (or None); the batch entry
    ``"batch"`` expands to the (pod, data) tuple and is dropped when the
    dim does not divide (decode at global_batch=1)."""
    if mesh is None:
        return x
    spec = []
    for i, d in enumerate(dims):
        if d == "batch":
            bax = batch_axes(mesh)
            spec.append(bax if x.shape[i] % _nbatch(mesh) == 0 else None)
        elif d is not None and d.endswith("!"):
            # force the axis even when uneven — GSPMD pads the ragged shard
            # (e.g. 9 attention heads over 16 model shards beats replication)
            spec.append(d[:-1])
        elif d is not None and x.shape[i] % mesh.shape[d] == 0:
            spec.append(d)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec))
    )


def to_named(tree_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
