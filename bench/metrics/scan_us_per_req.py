"""Device time of the router's commit scans per request routed, in us.

The scans are the ops under the program's named scopes ``route.spec_scan``
(the speculative scan), ``route.replay`` (the serial replay of a chunk's
suffix) and ``route.commit_scan`` (the plain correction scan and the single
scan), taken as the union of their intervals, since a loop op and the ops
of its body nest. Read within the routing programs that start in the traced
window (``route_us_per_req``'s modules), over (programs x the traffic
file's ``window_requests``), on the chip where the ratio is highest.

The trace's op events carry no ``op_name``. So the routing program is
compiled again here for the run's window, as both drivers call it (the
compile cache holds it), and each op's scopes are read from the
``op_name`` of the instruction of the same name in its optimised HLO. Ops
of other programs may share a name, which is why only ops inside a routing
program count. A program whose ops carry none of these scopes reads
nothing.
"""
import re

import numpy as np

from bench import trace_reduce
from bench.metrics.route_us_per_req import MODULES

SCOPES = {"route.spec_scan", "route.replay", "route.commit_scan"}
HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?(%[\w.\-]+) = .*?\bmetadata=\{op_name="([^"]*)"', re.M)


def scoped_names(hlo: str) -> set:
    """Instruction names of an HLO text whose ``op_name`` passes through
    one of ``SCOPES`` (the last part of an op_name is the op itself)."""
    return {name for name, op_name in HLO_OP_NAME.findall(hlo)
            if not SCOPES.isdisjoint(op_name.split("/")[:-1])}


def scan_s(dev: dict, t0: float, t1: float, names: set):
    """``(scan seconds, programs)`` on one device: the union of the ops
    named in ``names`` that start inside a routing program that starts in
    ``[t0, t1)``. A device runs one program at a time, so that union is
    the sum of each program's own."""
    mods = sorted((s, s + d) for s, d, name
                  in trace_reduce.clipped(dev["modules"], t0, t1)
                  if name.startswith(MODULES))
    ops = [e for e in dev["ops"] if e[2].partition(" = ")[0] in names]
    if not mods or not ops:
        return 0.0, len(mods)
    starts = np.array([s for s, _ in mods])
    ends = np.array([e for _, e in mods])
    at = np.array([s for s, _, _ in ops])
    k = np.searchsorted(starts, at, side="right") - 1
    inside = (k >= 0) & (at < ends[np.maximum(k, 0)])
    ops = [e for e, i in zip(ops, inside) if i]
    return trace_reduce.union_s(ops, starts[0], ends[-1]), len(mods)


def route_hlo(ctx) -> str:
    """The optimised HLO text of the routing program for one window of the
    run, with ``route_batch``'s own defaults, which both drivers use."""
    import jax

    from bench import drivers
    from repro.core import batch_router as br

    dev = jax.devices()[0]
    params, state = jax.device_put(_fleet(ctx["table"]), dev)
    w = ctx["run"].traced["requests_per_module"]
    batch = drivers._batch(ctx["run"].cols, slice(0, w), dev)
    traffic = ctx["traffic"]
    return br._route_batch.lower(
        params, state, batch, None, None, policy="greedy", actor=None,
        chunk=traffic["chunk"], unroll=8, backend=traffic["backend"],
        speculative=True).compile().as_text()


def _fleet(table: dict):
    """``(FleetParams, FleetState)`` of the configuration's table, built
    as ``fleet.program_fleet`` builds them. Only the shapes and which
    fields are set reach the compiled program, not the values."""
    from repro.core import batch_router as br
    from repro.core.catalog import CatalogEntry
    from repro.core.router import EdgeServer

    catalog = [CatalogEntry(index=i, name=f"m{i}", family="", param_count=0,
                            size_bits=float(s),
                            decode_flops_per_token=float(f))
               for i, (s, f) in enumerate(zip(table["size_bits"],
                                              table["ftok"]))]
    servers = [EdgeServer(name=f"s{i}", flops_per_s=float(table["flops"][i]),
                          cache_slots=int(table["slots"][i]),
                          uplink_bps=float(table["uplink"][i]),
                          backhaul_bps=float(table["backhaul"][i]),
                          resident=list(table["resident"][i]),
                          cell=int(table["cell"][i]),
                          drain_rate=float(table["drain"][i]))
               for i in range(len(table["flops"]))]
    return br.fleet_from_servers(servers, catalog)


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    t0, t1 = trace_reduce.window(trace)
    if not any(name.startswith(MODULES)
               for dev in trace["devices"].values()
               for _, _, name in trace_reduce.clipped(dev["modules"], t0, t1)):
        return None
    names = scoped_names(route_hlo(ctx))
    if not names:
        return None
    per = ctx["run"].traced["requests_per_module"]
    best = None
    for dev in trace["devices"].values():
        seconds, programs = scan_s(dev, t0, t1, names)
        if programs and seconds > 0:
            v = 1e6 * seconds / (programs * per)
            best = v if best is None else max(best, v)
    return best
