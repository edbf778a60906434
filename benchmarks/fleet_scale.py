"""Fleet-scale mesh routing: req/s vs device count (BENCH_fleet.json).

Routes ONE reconciliation window of B = 256k requests over a C = 64-cell
fleet (16 servers/cell -> N = 1024 edge + 1 cloud column) through
``core.mesh_router.route_batch_sharded`` on a D-device ``cells`` mesh,
for D in {1, 2, 4, 8}, and records requests/sec per device count.

On an accelerator the sweep runs inline, in this one process, over the
device counts the host has (a four-chip host sweeps D in {1, 2, 4}): a
chip belongs to one process, so no child is started there. On CPU, XLA
fixes the host device count at first jax init, so the sweep runs in ONE
child process spawned under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the child
prints a ``FLEET_RESULT {json}`` line per device count, the parent
parses them, prints the CSV rows and rewrites
``benchmarks/BENCH_fleet.json``.

    PYTHONPATH=src python -m benchmarks.fleet_scale

Honesty note recorded into the JSON: forced host devices share one
CPU's cores, so the D-curve here validates that sharding overhead
(bucketing, reconciliation replay, scatter-back) stays flat — it is not
an accelerator scaling claim. The child also asserts the window is
device-count invariant (choices bitwise across all D).

``main(smoke=True)`` (CI) shrinks to C=8 x 2 servers, B=512, D in
{1, 2}: every path still runs end to end, plus a bitwise parity assert
against the plain single-device ``route_batch`` scan (the smoke fleet
is cloud-free, where the sharded window is exactly the plain scan); no
timing claims, no JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

NUM_CELLS, PER_CELL = 64, 16
BATCH = 262_144
DEVICES = (1, 2, 4, 8)
CHUNK = 256
REPEATS = 3
SMOKE_CELLS, SMOKE_PER_CELL, SMOKE_BATCH = 8, 2, 512
SMOKE_DEVICES = (1, 2)
EDGE_ARCHS = ["smollm_135m", "starcoder2_3b", "mamba2_2p7b", "musicgen_medium"]
JSON_PATH = pathlib.Path(__file__).parent / "BENCH_fleet.json"
_RESULT_TAG = "FLEET_RESULT "


def build_fleet(rng, n_cells, per_cell, catalog, cloud=True):
    from repro.core.router import EdgeServer
    from repro.launch.serve import make_cloud_server

    fleet = [
        EdgeServer(
            name=f"c{c}-es{i}",
            flops_per_s=float(rng.uniform(5e13, 2e14)),
            cache_slots=2,
            uplink_bps=1e8,
            backhaul_bps=1e9,
            resident=[(2 * (c * per_cell + i) + j) % len(catalog)
                      for j in range(2)],
            cell=c,
        )
        for c in range(n_cells)
        for i in range(per_cell)
    ]
    if cloud:
        fleet.append(make_cloud_server(catalog))
    return fleet


def child_sweep(n_cells, per_cell, batch, devices, chunk, repeats, parity):
    """Run the D-sweep in THIS process (needs >= max(devices) jax devices);
    prints one FLEET_RESULT line per device count."""
    import jax
    import jax.numpy as jnp

    from repro.core import batch_router as br
    from repro.core import mesh_router as mr
    from repro.core.catalog import build_catalog

    assert jax.device_count() >= max(devices), (
        f"need {max(devices)} devices, found {jax.device_count()}; "
        "set XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    catalog = build_catalog(EDGE_ARCHS)
    rng = np.random.default_rng(0)
    cloud = not parity  # parity (smoke) runs cloud-free: bitwise vs plain
    fleet = build_fleet(rng, n_cells, per_cell, catalog, cloud=cloud)
    params, state = br.fleet_from_servers(fleet, catalog)
    reqs = br.RequestBatch(
        model=jnp.asarray(rng.integers(0, len(catalog), batch), jnp.int32),
        prompt_bits=jnp.asarray(rng.uniform(1e5, 1e6, batch), jnp.float32),
        gen_tokens=jnp.asarray(rng.integers(1, 32, batch).astype(float),
                               jnp.float32),
        cell=jnp.asarray(rng.integers(0, n_cells, batch), jnp.int32),
    )
    base_choice = None
    for d in devices:
        run = lambda: mr.route_batch_sharded(params, state, reqs,
                                             num_devices=d, chunk=chunk)
        st, out = run()  # compile + warm
        jax.block_until_ready(out.choice)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            st, out = run()
            jax.block_until_ready(out.choice)
            best = min(best, time.perf_counter() - t0)
        choice = np.asarray(out.choice)
        if base_choice is None:
            base_choice = choice
        else:  # device-count invariance, every sweep
            np.testing.assert_array_equal(choice, base_choice)
        if parity:  # smoke: bitwise vs the plain single-device scan
            st_p, out_p = br.route_batch(params, state, reqs, chunk=chunk)
            np.testing.assert_array_equal(choice, np.asarray(out_p.choice))
            np.testing.assert_array_equal(np.asarray(st.queue_tokens),
                                          np.asarray(st_p.queue_tokens))
        print(_RESULT_TAG + json.dumps({
            "devices": d,
            "cells": n_cells,
            "edge_servers": n_cells * per_cell,
            "batch": batch,
            "chunk": chunk,
            "seconds": best,
            "req_per_s": batch / best,
            "completion_rate": float((choice >= 0).mean()),
        }), flush=True)


def _spawn_child(n_cells, per_cell, batch, devices, chunk, repeats, parity):
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={max(devices)}"
    ).strip()
    repo = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(repo / "src"), str(repo), env.get("PYTHONPATH", ""))
        if p
    )
    cmd = [sys.executable, "-m", "benchmarks.fleet_scale", "--child",
           "--cells", str(n_cells), "--per-cell", str(per_cell),
           "--batch", str(batch), "--chunk", str(chunk),
           "--repeats", str(repeats),
           "--devices", ",".join(map(str, devices))]
    if parity:
        cmd.append("--parity")
    proc = subprocess.run(cmd, cwd=str(repo), env=env, capture_output=True,
                          text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"fleet_scale child failed (exit {proc.returncode}):\n"
            f"{(proc.stdout + proc.stderr)[-3000:]}"
        )
    return [json.loads(line[len(_RESULT_TAG):])
            for line in proc.stdout.splitlines()
            if line.startswith(_RESULT_TAG)]


def write_json(rows, platform, kind):
    base = rows[0]["req_per_s"]
    payload = {
        "benchmark": "mesh-sharded fleet routing (core.mesh_router)",
        "device": {"platform": platform, "kind": kind},
        "shape": {
            "cells": rows[0]["cells"],
            "edge_servers": rows[0]["edge_servers"],
            "cloud_columns": 1,
            "batch_requests_per_window": rows[0]["batch"],
            "chunk": rows[0]["chunk"],
        },
        "req_per_s_by_devices": {
            str(r["devices"]): round(r["req_per_s"]) for r in rows
        },
        "speedup_vs_1_device": {
            str(r["devices"]): round(r["req_per_s"] / base, 3) for r in rows
        },
        "note": ("device-count invariance (bitwise choices) is asserted "
                 "in the same run"
                 + ("; forced host devices share one CPU's cores: the "
                    "curve bounds sharding overhead, it is not an "
                    "accelerator scaling claim" if platform == "cpu"
                    else "")),
    }
    JSON_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    return payload


def main(header=True, smoke=False, emit_json=True):
    if smoke:
        shapes = (SMOKE_CELLS, SMOKE_PER_CELL, SMOKE_BATCH)
        devices, repeats, parity, emit_json = SMOKE_DEVICES, 1, True, False
    else:
        shapes = (NUM_CELLS, PER_CELL, BATCH)
        devices, repeats, parity = DEVICES, REPEATS, False
    n_cells, per_cell, batch = shapes

    import jax

    if jax.default_backend() != "cpu":
        devices = tuple(d for d in devices if d <= jax.device_count())
    if jax.device_count() >= max(devices):
        import contextlib
        import io

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            child_sweep(n_cells, per_cell, batch, devices, chunk=CHUNK,
                        repeats=repeats, parity=parity)
        rows = [json.loads(line[len(_RESULT_TAG):])
                for line in buf.getvalue().splitlines()
                if line.startswith(_RESULT_TAG)]
    else:
        rows = _spawn_child(n_cells, per_cell, batch, devices, chunk=CHUNK,
                            repeats=repeats, parity=parity)

    if header:
        print("name,us_per_call,derived")
    for r in rows:
        us = r["seconds"] / r["batch"] * 1e6
        name = (f"fleet_scale_d{r['devices']}_c{r['cells']}"
                f"n{r['edge_servers']}_b{r['batch']}")
        print(f"{name},{us:.4f},req_per_s={r['req_per_s']:.0f}")
    if smoke:
        print("fleet_scale_smoke,0.0,parity=bitwise_vs_plain_scan")
    if emit_json and rows:
        write_json(rows, jax.default_backend(), jax.devices()[0].device_kind)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--child", action="store_true",
                    help="internal: run the sweep in-process (expects the "
                         "forced device count already set)")
    ap.add_argument("--cells", type=int, default=NUM_CELLS)
    ap.add_argument("--per-cell", type=int, default=PER_CELL)
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--chunk", type=int, default=CHUNK)
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--devices", default=",".join(map(str, DEVICES)))
    ap.add_argument("--parity", action="store_true")
    args = ap.parse_args()
    if args.child:
        child_sweep(args.cells, args.per_cell, args.batch,
                    tuple(int(d) for d in args.devices.split(",")),
                    args.chunk, args.repeats, args.parity)
    else:
        main(smoke=args.smoke)
