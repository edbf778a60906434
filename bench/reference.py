"""Plain reference of the router's semantics, and the comparison that
decides ``correct``.

The semantics (paper eqs. 5/7/9/11, the cell mask, the time drain, LRU
residency), written from the paper and the scalar oracle's documented
contract, in float64 numpy and plain Python. It imports nothing of the
program and takes nothing the program made, except the choices it is
checking.

The check is teacher-forced, the way a served model's tokens are checked
against a reference's logits: the reference follows the stream in
arrival order and commits the PROGRAM's choice for every request
(``follow``), so a single wrong decision cannot make every later one
differ. Every decided request is then scored against the state the
reference holds just before it (``score``), over every server it can
see:

* ``gap``: how far the chosen server's eq. 11 latency lies above the
  best visible one, as a share of the best. A greedy router chooses the
  best, so the gap is 0 but for rounding at near ties. A request with no
  decision, a rejection or a server outside its cell reads ``inf``.
* ``lat_err``: the relative error of the latency the program reported
  for its choice. A residency-hit flag that disagrees with the
  reference's residency reads ``inf``: the reported latency then holds
  the wrong eq. 7 term.

Queues drain lazily: a server's backlog is brought forward from its last
commit, ``max(q - rate * dt, 0)``, which in real arithmetic equals the
router's per-arrival decay of every queue.
"""
from __future__ import annotations

import numpy as np


def follow(t: dict, cols: dict, choice: np.ndarray) -> dict:
    """Commit ``choice`` in stream order. Returns per request the
    reference's hit flag and the chosen server's backlog and residency
    bitmask just after the commit."""
    n = len(choice)
    arr = cols["arrival_s"].astype(np.float64)
    if n > 1 and np.any(np.diff(arr[:n]) < 0):
        raise ValueError("the reference needs non-decreasing arrival stamps")
    ch = choice.tolist()
    mdl = cols["model"][:n].tolist()
    gen = cols["gen_tokens"][:n].astype(np.float64).tolist()
    arr_l = arr[:n].tolist()
    drain = t["drain"].tolist()
    slots = t["slots"].tolist()
    n_srv = len(drain)
    cloud = t["cloud"]
    q_srv = [0.0] * n_srv
    t_srv = [0.0] * n_srv
    lru = [{m: pos - len(r) for pos, m in enumerate(r)} for r in t["resident"]]
    mask = [sum(1 << m for m in r) for r in t["resident"]]
    hit_ref = [False] * n
    post_q = [0.0] * n
    post_mask = [0] * n
    for i in range(n):
        s = ch[i]
        if s < 0 or s >= n_srv:
            continue
        ti = arr_l[i]
        q = q_srv[s] - drain[s] * (ti - t_srv[s])
        q = (q if q > 0.0 else 0.0) + gen[i]
        q_srv[s] = q
        t_srv[s] = ti
        m = mdl[i]
        if s == cloud:
            hit_ref[i] = True
            post_mask[i] = mask[s]
            post_q[i] = q
            continue
        lu = lru[s]
        if m in lu:
            hit_ref[i] = True
        else:
            if len(lu) >= slots[s]:
                ev = min(lu, key=lu.get)
                del lu[ev]
                mask[s] &= ~(1 << ev)
            mask[s] |= 1 << m
        lu[m] = i + 1
        post_q[i] = q
        post_mask[i] = mask[s]
    return {
        "hit": np.asarray(hit_ref, bool),
        "post_q": np.asarray(post_q, np.float64),
        "post_mask": np.asarray(post_mask, np.int64),
    }


def _last_before(sel: np.ndarray) -> np.ndarray:
    """(rows, cols) bool -> for each row, the last EARLIER row where the
    column was selected, or -1."""
    a = np.where(sel, np.arange(sel.shape[0])[:, None], -1)
    a = np.maximum.accumulate(a, axis=0)
    return np.concatenate([np.full((1, sel.shape[1]), -1), a[:-1]])


def score(t: dict, cols: dict, choice: np.ndarray, latency: np.ndarray,
          hit: np.ndarray, fol: dict) -> dict:
    """Per-request ``gap`` and ``lat_err`` (see the module docstring) for
    the first ``len(choice)`` requests of the stream."""
    n = len(choice)
    arr = cols["arrival_s"][:n].astype(np.float64)
    mdl = cols["model"][:n].astype(np.int64)
    p = cols["prompt_bits"][:n].astype(np.float64)
    g = cols["gen_tokens"][:n].astype(np.float64)
    cell = cols["cell"][:n].astype(np.int64)
    ftok = t["ftok"][mdl]
    size = t["size_bits"][mdl]
    per, cloud = t["per_cell"], t["cloud"]
    init_mask = np.array([sum(1 << m for m in r) for r in t["resident"]],
                         np.int64)
    gap = np.full(n, np.inf)
    lat_err = np.full(n, np.inf)
    if cloud is not None:
        last_cloud = _last_before((choice == cloud)[:, None])[:, 0]
    for c in range(t["num_cells"]):
        idx = np.nonzero(cell == c)[0]
        if idx.size == 0:
            continue
        srv = c * per + np.arange(per)
        local = choice[idx] - c * per
        in_cell = (local >= 0) & (local < per)
        sel = in_cell[:, None] & (local[:, None] == np.arange(per)[None, :])
        last = _last_before(sel)                       # (k, per) rows of idx
        gl = np.where(last >= 0, idx[np.maximum(last, 0)], -1)
        ta = arr[idx][:, None]
        q = np.where(gl >= 0, fol["post_q"][np.maximum(gl, 0)], 0.0)
        t0 = np.where(gl >= 0, arr[np.maximum(gl, 0)], 0.0)
        q = np.maximum(q - t["drain"][srv][None, :] * (ta - t0), 0.0)
        msk = np.where(gl >= 0, fol["post_mask"][np.maximum(gl, 0)],
                       init_mask[srv][None, :])
        res = ((msk >> mdl[idx][:, None]) & 1).astype(bool)
        lat = (p[idx][:, None] / t["uplink"][srv][None, :]
               + np.where(res, 0.0,
                          size[idx][:, None] / t["backhaul"][srv][None, :])
               + (q * ftok[idx][:, None] + g[idx][:, None] * ftok[idx][:, None])
               / t["flops"][srv][None, :])
        col = np.where(in_cell, local, -1)
        if cloud is not None:
            lc = last_cloud[idx]
            qc = np.where(lc >= 0, fol["post_q"][np.maximum(lc, 0)], 0.0)
            tc = np.where(lc >= 0, arr[np.maximum(lc, 0)], 0.0)
            qc = np.maximum(qc - t["drain"][cloud] * (arr[idx] - tc), 0.0)
            lat_c = (p[idx] / t["uplink"][cloud]
                     + (qc * ftok[idx] + g[idx] * ftok[idx]) / t["flops"][cloud])
            lat = np.concatenate([lat, lat_c[:, None]], axis=1)
            col = np.where(choice[idx] == cloud, per, col)
        best = lat.min(axis=1)
        ok = col >= 0
        at = lat[np.arange(idx.size), np.maximum(col, 0)]
        gap[idx] = np.where(ok, (at - best) / best, np.inf)
        err = np.abs(latency[idx].astype(np.float64) - at) / at
        agree = hit[idx].astype(bool) == fol["hit"][idx]
        lat_err[idx] = np.where(ok & agree, err, np.inf)
    return {"gap": gap, "lat_err": lat_err}


def check(t: dict, cols: dict, choice, latency, hit) -> dict:
    """Follow and score the decided prefix of the stream; the numbers
    compared are the widest ``gap`` and ``lat_err`` over it (the
    per-request arrays come back too, for a look at the worst)."""
    choice = np.asarray(choice, np.int64)
    fol = follow(t, cols, choice)
    s = score(t, cols, choice, np.asarray(latency), np.asarray(hit), fol)
    return {
        "gap_max": float(s["gap"].max()) if len(choice) else 0.0,
        "lat_err_max": float(s["lat_err"].max()) if len(choice) else 0.0,
        **s,
    }
