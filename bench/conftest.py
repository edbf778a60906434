"""Test set-up for the benchmark's own tests: CPU only, the program's
``src`` on the path, and a small copy of the benchmark tree whose traffic
runs on the CPU (XLA scoring backend, small windows)."""
import json
import os
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def make_tree(dst: pathlib.Path) -> pathlib.Path:
    """Copy ``BENCHMARK.json`` and the benchmark's data and readers under
    ``dst`` with every traffic file cut to a CPU-sized run; returns the
    copied ``BENCHMARK.json``."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    for f in (dst / "bench" / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        t["backend"] = "xla"
        if t["driver"] == "online":
            t["scenario"]["rate"] = 2000.0
            t["window_requests"] = 512
        else:
            t["window_requests"] = 1024
            t["windows_per_call"] = 2
            t["stream_calls"] = 2
        f.write_text(json.dumps(t))
    return dst / "BENCHMARK.json"


def cloud_config(num_cells: int) -> dict:
    """The metro configuration cut to ``num_cells`` cells, with the
    cloud column of ``launch/serve.make_cloud_server`` added back: a
    small fleet on which the cloud paths of the fleet table, the
    reference and the control run."""
    cfg = json.loads((ROOT / "bench" / "configs" / "metro-64x16-edge.json")
                     .read_text())
    cfg["name"] = f"metro-{num_cells}x16-cloud"
    cfg["num_cells"] = num_cells
    cfg["cloud"] = {"flops_per_s": 2e15, "uplink_bps": 1e8,
                    "backhaul_bps": 1e9, "drain_rate": 20000.0}
    return cfg


@pytest.fixture
def tree(tmp_path):
    return make_tree(tmp_path)


@pytest.fixture
def cpu():
    import jax

    return jax.devices("cpu")[:1]
