"""The control of the correctness check: the reference's semantics put in
the program's place, with eq. 11 scored in bfloat16, the next precision
below the float32 the configurations state (the step a later change to
the score panel would be tempted to take). Queues, clocks and residency
stay as exact as the program keeps them; only the scores lose bits. Its
decisions and latencies go through the same check as the program's, and
must fail it.

It is a plain ``lax.scan`` over the stream, one request a step, on the
first device JAX gives it. ``test_control.py`` runs it at a small size on
the CPU; on the chip, at a cell's own size:

    python3 bench/control.py --workload metro-edge.online --seeds 11,12,13 \
        --requests 2640000
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def route(table: dict, cols: dict, score_dtype="bfloat16"):
    """Route every request of ``cols`` in arrival order; returns
    ``(choice, latency, hit)`` as numpy arrays."""
    import jax
    import jax.numpy as jnp

    lo = jnp.dtype(score_dtype)
    f32 = jnp.float32
    num_k = len(table["size_bits"])
    srv_cell = jnp.asarray(table["cell"], jnp.int32)
    flops = jnp.asarray(table["flops"], lo)
    uplink = jnp.asarray(table["uplink"], lo)
    backhaul = jnp.asarray(table["backhaul"], lo)
    drain = jnp.asarray(table["drain"], f32)
    slots = jnp.asarray(table["slots"], jnp.int32)
    size = jnp.asarray(table["size_bits"], lo)
    ftok = jnp.asarray(table["ftok"], lo)
    n_srv = len(table["cell"])
    resident = np.zeros((n_srv, num_k), bool)
    last_use = np.full((n_srv, num_k), np.iinfo(np.int32).max, np.int32)
    for s, r in enumerate(table["resident"]):
        for pos, m in enumerate(r):
            resident[s, m] = True
            last_use[s, m] = pos - len(r)

    def step(carry, x):
        q, t, res, lu, clock = carry
        m, p, g, c, a = x
        t_new = jnp.maximum(t, a)
        q = jnp.maximum(q - drain * (t_new - t), 0.0)
        clock = clock + 1
        hit_row = res[:, m]
        lat = (p.astype(lo) / uplink
               + jnp.where(hit_row, jnp.zeros((), lo), size[m] / backhaul)
               + (q.astype(lo) * ftok[m] + g.astype(lo) * ftok[m]) / flops)
        visible = (srv_cell == c) | (srv_cell == -1)
        lat = jnp.where(visible, lat, jnp.inf)
        ch = jnp.argmin(lat).astype(jnp.int32)
        hit = hit_row[ch]
        row, lrow = res[ch], lu[ch]
        full = row.sum() >= slots[ch]
        victim = jnp.argmin(jnp.where(row, lrow, np.iinfo(np.int32).max))
        row = jnp.where((jnp.arange(num_k) == victim) & ~hit & full, False, row)
        row = row.at[m].set(True)
        res = res.at[ch].set(row)
        lu = lu.at[ch, m].set(clock)
        q = q.at[ch].add(g)
        return (q, t_new, res, lu, clock), (ch, lat[ch].astype(f32), hit)

    xs = (jnp.asarray(cols["model"], jnp.int32),
          jnp.asarray(cols["prompt_bits"], f32),
          jnp.asarray(cols["gen_tokens"], f32),
          jnp.asarray(cols["cell"], jnp.int32),
          jnp.asarray(cols["arrival_s"], f32))
    carry = (jnp.zeros(n_srv, f32), jnp.zeros((), f32), jnp.asarray(resident),
             jnp.asarray(last_use), jnp.zeros((), jnp.int32))
    _, (ch, lat, hit) = jax.jit(lambda c, x: jax.lax.scan(step, c, x))(carry, xs)
    return np.asarray(ch), np.asarray(lat), np.asarray(hit)


def readings(workload: str, seed: int, requests: int, bench_json=None) -> dict:
    """The control's numbers on ``requests`` requests of the cell's
    traffic, drawn from ``seed``."""
    from bench import fleet, reference, streams
    from bench.run import resolve

    r = resolve(workload, bench_json)
    cfg = fleet.load(r["config_file"])
    traffic = json.loads(r["traffic_file"].read_text())
    cols = streams.generate(streams.scenario(traffic["scenario"]), seed=seed,
                            n=requests, num_models=len(cfg["models"]),
                            num_cells=cfg["num_cells"])
    table = fleet.table(cfg)
    ch, lat, hit = route(table, cols)
    got = reference.check(table, cols, ch, lat, hit)
    return {"workload": workload, "seed": seed, "requests": requests,
            "gap_max": got["gap_max"], "lat_err_max": got["lat_err_max"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    dev = jax.devices()[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = readings(args.workload, seed, args.requests)
        out["device"] = f"{dev.platform} {dev.device_kind}"
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
