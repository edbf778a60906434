"""A configuration file's fleet, as plain arrays and as the program's types.

``table(cfg)`` is what the reference reads: one row per server, numbers
rounded through float32 as the configuration states the router computes,
then held as float64. ``program_fleet(cfg)`` builds the same fleet
through the program's own constructors (``EdgeServer``,
``fleet_from_servers``), the normal serving path.

Server order is cell-major: cell 0's edge servers, cell 1's, ..., and the
cloud column last, as ``launch.serve.make_multicell_fleet`` lays it out.
The cloud sits behind the backhaul, so its effective uplink is the two
links in series, ``1 / (1/uplink + 1/backhaul)``; every model is resident
there and it never evicts.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

CLOUD_CELL = -1


def load(path) -> dict:
    cfg = json.loads(pathlib.Path(path).read_text())
    if cfg.get("precision") != "float32":
        raise ValueError(f"{path}: the router states float32 arithmetic; "
                         f"got precision {cfg.get('precision')!r}")
    if len(cfg["resident"]) != cfg["servers_per_cell"]:
        raise ValueError(f"{path}: one resident list per server of a cell")
    return cfg


def _f32(x) -> np.ndarray:
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


def cloud_uplink(cfg: dict) -> float:
    c = cfg["cloud"]
    return 1.0 / (1.0 / c["uplink_bps"] + 1.0 / c["backhaul_bps"])


def table(cfg: dict) -> dict:
    """Per-server and per-model columns of the fleet (float32-exact)."""
    c, n = cfg["num_cells"], cfg["servers_per_cell"]
    e, k = cfg["edge"], len(cfg["models"])
    rows = c * n + (1 if cfg.get("cloud") else 0)

    def col(edge_val, cloud_val):
        v = np.full(rows, float(edge_val))
        if cfg.get("cloud"):
            v[-1] = cloud_val
        return v

    cl = cfg.get("cloud") or {}
    resident = [list(cfg["resident"][i % n]) for i in range(c * n)]
    if cl:
        resident.append(list(range(k)))
    return {
        "num_cells": c,
        "per_cell": n,
        "cloud": rows - 1 if cl else None,
        "cell": np.concatenate([np.repeat(np.arange(c), n),
                                [CLOUD_CELL] if cl else []]).astype(np.int64),
        "flops": _f32(col(e["flops_per_s"], cl.get("flops_per_s", 0.0))),
        "uplink": _f32(col(e["uplink_bps"], cloud_uplink(cfg) if cl else 0)),
        "backhaul": _f32(col(e["backhaul_bps"], cl.get("backhaul_bps", 0.0))),
        "drain": _f32(col(e["drain_rate"], cl.get("drain_rate", 0.0))),
        "slots": np.array([e["cache_slots"]] * (c * n) + ([k] if cl else []),
                          np.int64),
        "resident": resident,
        "size_bits": _f32([m["size_bits"] for m in cfg["models"]]),
        "ftok": _f32([m["decode_flops_per_token"] for m in cfg["models"]]),
    }


def program_fleet(cfg: dict):
    """``(FleetParams, FleetState)`` built by the program's constructors."""
    from repro.core import batch_router
    from repro.core.catalog import CatalogEntry
    from repro.core.router import EdgeServer

    catalog = [
        CatalogEntry(index=i, name=m["name"], family="", param_count=0,
                     size_bits=float(m["size_bits"]),
                     decode_flops_per_token=float(m["decode_flops_per_token"]))
        for i, m in enumerate(cfg["models"])
    ]
    e, n = cfg["edge"], cfg["servers_per_cell"]
    servers = [
        EdgeServer(name=f"c{c}-es{i}", flops_per_s=e["flops_per_s"],
                   cache_slots=e["cache_slots"], uplink_bps=e["uplink_bps"],
                   backhaul_bps=e["backhaul_bps"],
                   resident=list(cfg["resident"][i]), cell=c,
                   drain_rate=e["drain_rate"])
        for c in range(cfg["num_cells"]) for i in range(n)
    ]
    if cfg.get("cloud"):
        cl = cfg["cloud"]
        servers.append(EdgeServer(
            name="cloud", flops_per_s=cl["flops_per_s"],
            cache_slots=len(catalog), uplink_bps=cloud_uplink(cfg),
            backhaul_bps=cl["backhaul_bps"],
            resident=list(range(len(catalog))), cell=CLOUD_CELL,
            drain_rate=cl["drain_rate"]))
    return batch_router.fleet_from_servers(servers, catalog)
