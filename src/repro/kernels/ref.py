"""Pure-jnp oracles for every kernel, plus memory-sane XLA fallbacks.

Two tiers:
  * ``*_naive`` — the mathematical definition, O(S^2)/recurrent, used as
    the allclose oracle for both the Pallas kernels and the XLA paths.
  * ``*_xla``  — chunked/flash-style jnp implementations that are safe to
    compile at production shapes (no (B,H,S,S) materialisation). These are
    what the dry-run lowers when ``kernel_backend="xla"``.

Activation layout everywhere: (batch, seq, heads, head_dim).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.queue import advance_to_arrival, drain_after_commit

NEG_INF = -1e30


# =============================== RMSNorm ======================================
def rmsnorm_naive(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return ((x32 / rms) * scale.astype(jnp.float32)).astype(dt)


# =============================== Attention ====================================
def _gqa_expand(k, num_q_heads):
    """(B, S, KV, D) -> (B, S, H, D) by repeating kv heads."""
    b, s, kv, d = k.shape
    rep = num_q_heads // kv
    return jnp.repeat(k, rep, axis=2)


def attention_naive(q, k, v, *, causal=True, window=0, q_offset=0):
    """Oracle. q: (B, Sq, H, D); k,v: (B, Sk, KV, D). fp32 math.

    ``q_offset``: absolute position of q[0] (decode: Sk-1 for single token).
    ``window`` > 0: key j visible to query i iff i - window < j <= i.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    k = _gqa_expand(k, h)
    v = _gqa_expand(v, h)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kj <= qi
    if window > 0:
        mask &= kj > qi - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_xla(q, k, v, *, causal=True, window=0, q_offset=0, q_chunk=512):
    """Flash-style: scan over query chunks; scores never exceed
    (B, H, q_chunk, Sk). fp32 accumulation, bf16-safe."""
    b, sq, h, d = q.shape
    if sq <= q_chunk:
        return attention_naive(q, k, v, causal=causal, window=window, q_offset=q_offset)
    assert sq % q_chunk == 0, (sq, q_chunk)
    nq = sq // q_chunk
    qs = q.reshape(b, nq, q_chunk, h, d)

    @jax.checkpoint  # recompute chunk scores in bwd: peak is ONE chunk's scores
    def one(carry, inp):
        qc, idx = inp
        out = attention_naive(
            qc, k, v, causal=causal, window=window, q_offset=q_offset + idx * q_chunk
        )
        return carry, out

    _, outs = jax.lax.scan(one, None, (jnp.moveaxis(qs, 1, 0), jnp.arange(nq)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, d)


def decode_attention_naive(q, k, v, pos, *, window=0):
    """Single-token decode. q: (B, 1, H, D); k,v: (B, S, KV, D); ``pos``
    scalar absolute position of the query. Visible keys: j <= pos (and
    window if set). fp32 math; scores are (B, KV, rep, S) — always small.

    Grouped-GQA form (q reshaped to (B, KV, rep, D)) rather than repeating
    K/V to H heads: no broadcast of the cache, so under SPMD the
    S-sharded KV cache never gets resharded to head sharding (the repeat
    triggered involuntary full rematerialisation in GSPMD)."""
    b, _, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    rep = h // kv
    qg = q[:, 0].reshape(b, kv, rep, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    # bf16 operands + fp32 accumulation via preferred_element_type: an
    # explicit astype(f32) of K/V gets loop-hoisted by XLA into an fp32
    # mirror of the ENTIRE stacked cache (7.9 GiB/dev on llama decode).
    scores = jnp.einsum(
        "bgrd,bkgd->bgrk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    kj = jnp.arange(s)
    mask = kj <= pos
    if window > 0:
        mask &= kj > pos - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bgrk,bkgd->bgrd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, h, d).astype(q.dtype)


# =============================== Mamba2 SSD ===================================
def ssd_naive(x, dt, a_log, b, c, d_skip):
    """Recurrent oracle (sequential over S, fp32).

    x: (B, S, H, P)  dt: (B, S, H)  a_log: (H,)
    b, c: (B, S, N)  d_skip: (H,)   returns (y, final_state)
    state: (B, H, P, N)
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    x32, dt32 = x.astype(jnp.float32), dt.astype(jnp.float32)
    b32, c32 = b.astype(jnp.float32), c.astype(jnp.float32)
    a = -jnp.exp(a_log.astype(jnp.float32))  # (H,) negative decay rates

    def step(state, inp):
        xt, dtt, bt, ct = inp  # (B,H,P), (B,H), (B,N), (B,N)
        decay = jnp.exp(a[None] * dtt)  # (B, H)
        add = jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], bt)
        state = state * decay[..., None, None] + add
        y = jnp.einsum("bhpn,bn->bhp", state, ct)
        return state, y

    init = jnp.zeros((bsz, h, p, n), jnp.float32)
    xs = (
        jnp.moveaxis(x32, 1, 0),
        jnp.moveaxis(dt32, 1, 0),
        jnp.moveaxis(b32, 1, 0),
        jnp.moveaxis(c32, 1, 0),
    )
    state, ys = jax.lax.scan(step, init, xs)
    y = jnp.moveaxis(ys, 0, 1)  # (B, S, H, P)
    y = y + x32 * d_skip.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), state


def ssd_chunked_xla(x, dt, a_log, b, c, d_skip, chunk: int = 256):
    """SSD chunked/blocked algorithm (Mamba2 paper §6) — scan over chunks,
    quadratic only within a chunk. Memory per step: (B, H, Q, Q)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    s_orig = s
    if s % chunk != 0:
        # pad with dt=0 steps: exp(a*0)=1 and x*dt=0, so the state and the
        # unpadded outputs are unaffected
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nc = s // chunk
    f32 = jnp.float32
    x32 = x.astype(f32).reshape(bsz, nc, chunk, h, p)
    dt32 = dt.astype(f32).reshape(bsz, nc, chunk, h)
    b32 = b.astype(f32).reshape(bsz, nc, chunk, n)
    c32 = c.astype(f32).reshape(bsz, nc, chunk, n)
    a = -jnp.exp(a_log.astype(f32))  # (H,)

    def per_chunk(state, inp):
        xc, dtc, bc, cc = inp  # (B,Q,H,P), (B,Q,H), (B,Q,N), (B,Q,N)
        adt = a[None, None] * dtc  # (B, Q, H)
        cum = jnp.cumsum(adt, axis=1)  # (B, Q, H) log-decay from chunk start
        total = cum[:, -1]  # (B, H)

        # intra-chunk (quadratic): L[i,j] = exp(cum_i - cum_j) for j <= i
        li = cum[:, :, None, :] - cum[:, None, :, :]  # (B, Q, Q, H)
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay_mat = jnp.where(causal[None, :, :, None], jnp.exp(li), 0.0)
        scores = jnp.einsum("bin,bjn->bij", cc, bc)  # (B, Q, Q)
        gate = scores[..., None] * decay_mat  # (B, Q, Q, H)
        xdt = xc * dtc[..., None]  # (B, Q, H, P)
        y_intra = jnp.einsum("bijh,bjhp->bihp", gate, xdt)

        # inter-chunk: contribution of carried state
        q_decay = jnp.exp(cum)  # (B, Q, H)
        y_inter = jnp.einsum("bin,bhpn,bih->bihp", cc, state, q_decay)

        # state update: state' = exp(total) * state + sum_j exp(total-cum_j) B_j x_j
        rem = jnp.exp(total[:, None] - cum)  # (B, Q, H)
        add = jnp.einsum("bjn,bjhp,bjh->bhpn", bc, xdt, rem)
        state = state * jnp.exp(total)[..., None, None] + add
        return state, y_intra + y_inter

    init = jnp.zeros((bsz, h, p, n), f32)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x32, dt32, b32, c32))
    state, ys = jax.lax.scan(per_chunk, init, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p)[:, :s_orig]
    y = y + x.astype(f32)[:, :s_orig] * d_skip.astype(f32)[None, None, :, None]
    return y.astype(x.dtype), state


def ssd_decode_naive(state, xt, dtt, a_log, bt, ct, d_skip):
    """One recurrent step. state: (B,H,P,N); xt: (B,H,P); dtt: (B,H);
    bt, ct: (B,N). Returns (y (B,H,P), new_state)."""
    f32 = jnp.float32
    a = -jnp.exp(a_log.astype(f32))
    decay = jnp.exp(a[None] * dtt.astype(f32))
    add = jnp.einsum("bhp,bn->bhpn", xt.astype(f32) * dtt.astype(f32)[..., None],
                     bt.astype(f32))
    new_state = state * decay[..., None, None] + add
    y = jnp.einsum("bhpn,bn->bhp", new_state, ct.astype(f32))
    y = y + xt.astype(f32) * d_skip.astype(f32)[None, :, None]
    return y.astype(xt.dtype), new_state


# ======================= Routing score (paper eq. 11) =========================
def route_score_xla(
    prompt_bits, size_bits, flops_tok, work,
    uplink_bps, backhaul_bps, flops_per_s,
    queue_tokens=None, resident=None, model=None,
    req_cell=None, srv_cell=None, cloud_cell=-1, spill=None,
    eta=None, beta=None,
):
    """XLA oracle for the fused (B, N) routing-score kernel.

    Same plain-array signature as ``route_score.route_score``; the
    eq. 5 + 7 + 9 arithmetic itself lives in
    ``core.costs.edge_score_matrix`` (the single home of the cost
    model), with the residency gather and the multi-cell visibility
    mask applied here. Out-of-cell, non-cloud pairs score ``+inf``.

    ``spill`` — an optional (C, C) bool neighbour-cell adjacency — adds
    spilled pairs (adjacent cell, not home, not cloud) to the visible
    set and prices them with the backhaul surcharge
    ``prompt_bits / backhaul_bps`` (the prompt crosses the inter-cell
    backhaul on top of the uplink — the same generalisation the cloud
    column folds into its effective uplink).

    ``eta`` (B,) scales the transmitted prompt and offloaded work (the
    eq. 16 offload ratio — spilled pairs pay the surcharge on the
    scaled prompt too); ``beta`` (B,) False refuses the eq. 7 download,
    poisoning every non-resident pair to ``+inf``. Both transforms
    happen once at entry via ``costs.apply_eta_beta`` so the kernel
    wrapper and this reference stay bit-identical.
    """
    from repro.core import costs  # leaf module (jnp-only): no cycle

    prompt_bits, size_bits, work = costs.apply_eta_beta(
        prompt_bits, size_bits, work, eta, beta
    )
    res_bn = resident[:, model].T if resident is not None else None
    score = costs.edge_score_matrix(
        prompt_bits, size_bits, flops_tok, work,
        uplink_bps, backhaul_bps, flops_per_s,
        queue_tokens=queue_tokens, resident=res_bn,
    )
    if req_cell is not None and srv_cell is not None:
        home = srv_cell[None, :] == req_cell[:, None]
        visible = home | (srv_cell[None, :] == cloud_cell)
        if spill is not None:
            nc = spill.shape[0]
            rok = (req_cell >= 0) & (req_cell < nc)
            sok = (srv_cell >= 0) & (srv_cell < nc)
            adj = spill[jnp.clip(req_cell, 0, nc - 1)][
                :, jnp.clip(srv_cell, 0, nc - 1)
            ]
            spilled = adj & rok[:, None] & sok[None, :] & ~home
            score = score + jnp.where(
                spilled, prompt_bits[:, None] / backhaul_bps[None, :], 0.0
            )
            visible = visible | spilled
        score = jnp.where(visible, score, jnp.inf)
    return score


# ================ Speculative commit scan (greedy, one chunk) =================
def spec_scan_xla(basez, ftok, gen, queue, time_s, flops_per_s, *,
                  drain_rate=None, arrival=None, drain=None, outage=None,
                  valid=None, deadline=None, tloc=None, has_mask=False,
                  unroll=1):
    """XLA reference of ``route_spec_scan.route_spec_scan``: the chunked
    router's speculative greedy recurrence as a ``lax.scan``, one step a
    request. Same arguments and results: ``(queues, choices, lats,
    times)``, the queue trajectory (c+1, N) with the entry row first.

    Residency (and with it the argmin's score ordering) is frozen at
    chunk entry, so only the queue backlog rides the carry: score,
    argmin, one masked add. Each step emits ``lats[choice]``, the score
    its own gates compared, so the caller never re-derives it. The
    queue moves through ``core.queue``, as in the kernel."""
    c, n = basez.shape
    iota_n = jnp.arange(n, dtype=jnp.int32)
    has_time = drain_rate is not None

    def spec_step(carry, xs_b):
        queue, time_s = carry
        basez_b, ftok_b, gen_b, drain_b, arrival_b, valid_b, dl_b, \
            tloc_b = xs_b
        if has_time:
            queue, time_s = advance_to_arrival(queue, time_s, arrival_b,
                                               drain_rate, valid_b)
        lats = basez_b + (queue * ftok_b) / flops_per_s
        choice = jnp.argmin(lats).astype(jnp.int32)
        lat = lats[choice]
        touch_n = iota_n == choice
        if has_mask:
            touch_n &= jnp.isfinite(basez_b[choice])
        if dl_b is not None:
            # greedy: lats[choice] IS the best score — the SLO check
            best = lat
            if tloc_b is not None:  # eq. 13 device-share floor
                best = jnp.maximum(tloc_b, best)
            touch_n &= best <= dl_b
        if valid_b is not None:
            touch_n &= valid_b
        queue = queue + jnp.where(touch_n, gen_b, 0.0)
        if drain_b is not None:
            queue = drain_after_commit(queue, drain_b, valid_b, outage)
        out = (choice, lat, queue) + ((time_s,) if has_time else ())
        return (queue, time_s), out

    inner = (basez, ftok, gen, drain, arrival, valid, deadline, tloc)
    _, souts = jax.lax.scan(spec_step, (queue, time_s), inner,
                            unroll=min(unroll, c))
    choices, lats = souts[0], souts[1]
    queues = jnp.concatenate([queue[None], souts[2]])            # (c+1, N)
    times = jnp.concatenate([time_s[None], souts[3]]) if has_time else None
    return queues, choices, lats, times
