"""Reduction of a profiler trace to the numbers the per-layer readers use.

``load(log_dir)`` reads the ``.xplane.pb`` the JAX profiler wrote and
keeps, for each TPU device plane, its ``XLA Ops`` events (every operation
the device ran, loop bodies included) and its ``XLA Modules`` events (one
per program execution), and from the host plane the benchmark's own
``bench.*`` spans. All times are on the profiler's one clock, in
seconds. ``from_json`` reads the same structure from a JSON file, which
is how the tests carry a small recorded trace.

The traced window runs from the host marker ``bench.trace_open`` to
``bench.trace_close``; device time is clipped to it.
"""
from __future__ import annotations

import glob
import json
import pathlib

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


def load(log_dir) -> dict:
    import jax

    files = sorted(glob.glob(str(pathlib.Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    devices, host, names = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    # op names are whole HLO instructions: keep them short
                    dev[key] = [(e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                 names.get(n) or names.setdefault(
                                     n, short_name(n)))
                                for e in line.events for n in (e.name,)]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                     e.name))
    return {"devices": devices, "host": host}


def from_json(path) -> dict:
    t = json.loads(pathlib.Path(path).read_text())
    t["host"] = [tuple(e) for e in t["host"]]
    for d in t["devices"].values():
        d["ops"] = [tuple(e) for e in d["ops"]]
        d["modules"] = [tuple(e) for e in d["modules"]]
    return t


def window(trace: dict) -> tuple:
    """``(start, end)`` of the traced window, from the host markers."""
    marks = {name: s for s, d, name in trace["host"]
             if name in ("bench.trace_open", "bench.trace_close")}
    if len(marks) != 2:
        raise ValueError("the trace lacks its bench.trace_open/close markers")
    return marks["bench.trace_open"], marks["bench.trace_close"]


def union_s(events, t0: float, t1: float) -> float:
    """Seconds of ``[t0, t1)`` covered by at least one event."""
    if not events:
        return 0.0
    iv = np.array([(s, s + d) for s, d, _ in events], float)
    iv = np.clip(iv, t0, t1)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not iv.size:
        return 0.0
    iv = iv[np.argsort(iv[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new run starts where an interval begins after every earlier end
    first = np.nonzero(np.concatenate([[True], iv[1:, 0] > ends[:-1]]))[0]
    run_end = np.maximum.reduceat(iv[:, 1], first)
    return float((run_end - iv[first, 0]).sum())


def busy_s(trace: dict) -> dict:
    """Busy seconds in the traced window, per device plane."""
    t0, t1 = window(trace)
    return {name: union_s(d["ops"], t0, t1)
            for name, d in trace["devices"].items()}


def clipped(events, t0: float, t1: float) -> list:
    """Events that start inside ``[t0, t1)``."""
    return [e for e in events if t0 <= e[0] < t1]


def short_name(hlo: str) -> str:
    """``%while.108 = (...) while(...)`` -> ``%while.108``; a Pallas
    kernel keeps its result shape and custom-call target:
    ``%closed_call.100 = f32[256,1152]{1,0} tpu_custom_call``. A module
    name (no `` = ``) is kept whole."""
    head, eq, rest = hlo.partition(" = ")
    if eq and "tpu_custom_call" in rest:
        return f"{head} = {rest.split(' ', 1)[0]} tpu_custom_call"
    return head


def top_ops(trace: dict, k: int = 10) -> list:
    """The device operations that took most time in the window, summed
    over every device plane: ``[[name, seconds], ...]``."""
    t0, t1 = window(trace)
    tot: dict = {}
    for d in trace["devices"].values():
        for s, dur, name in clipped(d["ops"], t0, t1):
            tot[name] = tot.get(name, 0.0) + dur
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: dict, k: int = 10) -> list:
    """Device idle time in the window, by the innermost ``bench.*`` host
    span that covers the middle of each gap, on the busiest device:
    ``[[span name, seconds], ...]`` longest first. A gap that no closed
    bench span covers happened while the host ran the program's own code
    (``simulate``'s call is still open when the trace ends)."""
    t0, t1 = window(trace)
    busy = busy_s(trace)
    if not busy:
        return []
    dev = trace["devices"][max(busy, key=busy.get)]
    iv = sorted((max(s, t0), min(s + d, t1)) for s, d, _ in dev["ops"]
                if s + d > t0 and s < t1)
    gaps, cur = [], t0
    for s, e in iv:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    spans = [(s, s + d, n) for s, d, n in trace["host"]
             if n not in ("bench.trace_open", "bench.trace_close")]
    tot: dict = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [(e - s, n) for s, e, n in spans if s <= mid < e]
        name = min(cover)[1] if cover else "inside the program"
        tot[name] = tot.get(name, 0.0) + (b - a)
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]
