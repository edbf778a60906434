"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script refuses to run without a TPU; these tests replace its
platform check (and the compiled ``pallas`` backend, which cannot run
on a CPU) inside the test only, and shrink its windows, so its control
flow and checks run here end to end.
"""
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rehearse(monkeypatch):
    """chip_smoke at a tiny size, with the CPU standing in for the chip."""
    import repro.launch.compile_cache

    cs = load_chip_smoke()
    monkeypatch.setattr(cs, "require_tpu", lambda: jax.devices()[0])
    monkeypatch.setattr(cs, "CHIP_BACKENDS", ("pallas-interpret", "xla"))
    monkeypatch.setattr(cs, "CHUNK", 16)
    monkeypatch.setattr(cs, "SINGLE_CELL",
                        dict(cs.SINGLE_CELL, num_requests=96, n_servers=8))
    monkeypatch.setattr(cs, "METRO", dict(cs.METRO, num_requests=256,
                                          n_servers=4, n_cells=4))
    monkeypatch.setattr(cs, "ACTOR", dict(cs.ACTOR, num_requests=96))
    # leave the test process's compilation cache as it was
    monkeypatch.setattr(repro.launch.compile_cache, "enable_compile_cache",
                        lambda: "off")
    return cs


def test_chip_smoke_refuses_a_host_without_tpu(capsys):
    cs = load_chip_smoke()
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code == 2
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_phases_pass_on_cpu_stand_in(monkeypatch, capsys):
    rehearse(monkeypatch).main([])
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": jax.device_count()}}
    for phase in ("single-cell", "metro", "actor"):
        assert any(line.startswith(f"phase {phase}:") for line in out)


def test_chip_smoke_fails_a_choice_that_is_no_near_tie(monkeypatch, capsys):
    cs = rehearse(monkeypatch)
    from repro.launch.serve import make_window

    window = make_window(**cs.SINGLE_CELL)
    cpu = jax.devices("cpu")[0]
    state, out, _ = cs._timed_route(window, "greedy", "xla", cpu)
    # request 5 moved to the server its eq. 11 row ranks worst
    choice = np.asarray(out.choice).copy()
    choice[5] = (choice[5] + 4) % window.params.flops_per_s.shape[0]
    forged = out._replace(choice=choice)
    near_ties = []
    fails = cs._compare("forged", window, (state, forged), (state, out),
                        policy="greedy", cpu=cpu, near_ties=near_ties)
    assert near_ties == []
    assert any("request 5" in f and "not a near tie" in f for f in fails)


def test_four_chip_comparison_fails_one_ulp(monkeypatch):
    cs = rehearse(monkeypatch)
    from repro.launch.serve import make_window

    window = make_window(**cs.SINGLE_CELL)
    state, out, _ = cs._timed_route(window, "greedy", "xla",
                                    jax.devices("cpu")[0])
    latency = np.asarray(out.latency).copy()
    latency[3] = np.nextafter(latency[3], np.inf)
    fails = cs._bitwise_report("forged", (state, out._replace(
        latency=latency)), (state, out))
    assert fails == ["forged: latency differs"]


def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was  # JAX's own read
        monkeypatch.delenv(compile_cache.ENV_VAR)
        default = str(REPO / ".jax_cache")
        assert compile_cache.enable_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
