"""Benchmark of the model-aware request router on a TPU.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell ``<name>`` in ``BENCHMARK.json``, its configuration file
(``configs``), its traffic file (``bench/traffic/<traffic>.json``), its
limits (``bench/limits/<name>.json``) and, with ``--trace 1``, a reader
for each of its per-layer metrics (``bench/metrics/<metric>.py``, else
``bench/metrics/<metric up to its first dot>.py``). Nothing here names a
cell: a new cell, traffic mix or metric is new files and entries.

The traffic file's ``driver`` (``bench/drivers.py``) runs the window.
After it the reference (``bench/reference.py``) checks every decided
request, and the numbers compared are printed with their limits as the
last lines of standard error and under ``checks``, the last key of the
result, which is the last line of standard output. An earlier line of
standard output describes the traffic as routed.

Exits 2 with no result when JAX finds no TPU or fewer chips than the
cell asks for.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"


def resolve(workload: str, bench_json=None) -> dict:
    """The cell's entry and the files it names, from ``BENCHMARK.json``."""
    bench_json = pathlib.Path(bench_json or ROOT / "BENCHMARK.json")
    spec = json.loads(bench_json.read_text())
    root = bench_json.parent
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    bench_dir = root / spec["paths"][0]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config_file": root / configs[cell["config"]]["file"],
        "traffic_file": bench_dir / "traffic" / f"{cell['traffic']}.json",
        "limits_file": bench_dir / "limits" / f"{workload}.json",
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
        "metrics_dir": bench_dir / "metrics",
    }


def reader(metrics_dir: pathlib.Path, name: str):
    """The ``read`` function of a per-layer metric's reader file."""
    for stem in (name, name.split(".", 1)[0]):
        path = metrics_dir / f"{stem}.py"
        if path.exists():
            mod_name = "bench.metrics." + stem.replace(".", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                            f"under {metrics_dir}")


def require_chips(chips: int):
    """The first ``chips`` TPU devices; exits 2 without them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def enable_cache():
    """JAX's persistent compile cache at a fixed path in the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def checks(run, table: dict, limits: dict) -> dict:
    from bench import reference

    got = reference.check(table, run.cols, run.choice, run.latency, run.hit)
    return {name: {"value": got[name], "limit": limits[name]["limit"]}
            for name in ("gap_max", "lat_err_max")}


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             devices=None, bench_json=None) -> dict:
    """Run one cell and return the result object (without printing)."""
    from bench import drivers, fleet, trace_reduce

    r = resolve(workload, bench_json)
    cfg = fleet.load(r["config_file"])
    traffic = json.loads(r["traffic_file"].read_text())
    limits = json.loads(r["limits_file"].read_text())
    if devices is None:
        devices = require_chips(r["cell"]["chips"])
    log_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace \
        else None
    try:
        tracer = drivers.Tracer(trace, log_dir, traffic["trace_seconds"])
        run = drivers.DRIVERS[traffic["driver"]](
            cfg, traffic, seed=seed, seconds=seconds, tracer=tracer,
            devices=devices)
        reduced = trace_reduce.load(log_dir) if trace else None
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)
    table = fleet.table(cfg)
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    metrics, breakdown = {}, None
    if trace:
        ctx = {"trace": reduced, "run": run, "traffic": traffic,
               "table": table, "device_kind": kind}
        for m in r["per_layer"]:
            v = reader(r["metrics_dir"], m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        t0, t1 = trace_reduce.window(reduced)
        busy = trace_reduce.busy_s(reduced)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = t1 - t0
        breakdown = {"device_ops": trace_reduce.top_ops(reduced),
                     "idle_gaps": trace_reduce.idle_gaps(reduced)}
    else:
        for m in r["end_to_end"]:
            metrics[m["name"]] = {"value": run.host[m["name"]],
                                  "unit": m["unit"]}
    compared = checks(run, table, limits)
    correct = all(c["value"] <= c["limit"] for c in compared.values()) \
        and all(math.isfinite(v["value"]) for v in metrics.values())
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = compared
    result["traffic"] = run.describe
    return result


def finite(obj):
    """``obj`` with every non-finite float as ``None``: JSON has no
    infinity, and a missing decision reads infinite."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    r = resolve(args.workload)
    devices = require_chips(r["cell"]["chips"])
    enable_cache()
    result = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices)
    print(json.dumps(finite({"traffic": result.pop("traffic")})), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
