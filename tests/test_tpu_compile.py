"""The routing path compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so the Pallas score kernel and
the chunked commit are compiled here at the widths the chip runs
(``chip_smoke.py`` and the benchmark's cells): the compile refuses what
interpret mode accepts — unaligned slices, too much fast memory,
programs that do not fit. The tests assert the kernels reached the HLO
as ``tpu_custom_call``s, and that each commit path's ``route.*`` named
scopes reached the ops' ``op_name`` metadata, where the device-trace
readers (``bench/metrics``) look for them: the speculative scan kernel
under ``route.spec_scan``, and only the score kernel taken for a score
panel.

The topology is described inside a module fixture, never at import:
only one process may hold libtpu, and every test worker imports this
file. The persistent compilation cache is off around these compiles —
an entry written for a described chip cannot be read back without one.
"""
import functools
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from bench import fleet
from bench.metrics import route_score_roofline, scan_us_per_req
from repro.core import batch_router as br
from repro.core import mesh_router as mr
from repro.kernels.route_score import route_score
from repro.launch.serve import make_window

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding),
        tree,
    )


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _assert_readers_see_the_kernels(text, chunk, n):
    """The scan kernel is read as scan time, and the score-panel reader
    counts the ``route_score`` calls and nothing else. A trace names a
    custom call ``<instruction> = <result> tpu_custom_call``."""
    calls = re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    names = {name for name, _ in calls}
    scan = [name for name in names if name.startswith("%route_spec_scan")]
    assert scan and set(scan) <= scan_us_per_req.scoped_names(text)
    n_pad = -(-n // 128) * 128
    for name, result in calls:
        as_traced = f"{name} = {result} tpu_custom_call"
        assert route_score_roofline._is_kernel(as_traced, chunk, n_pad) == \
            name.startswith("%route_score"), as_traced


def _scopes(compiled) -> set:
    """The ``route.*`` named scopes in the compiled module's op_names."""
    return set(re.findall(r'op_name="[^"]*?(route\.[a-z_]+)',
                          compiled.as_text()))


@pytest.mark.parametrize("b,n,cells", [(65536, 64, 0), (4096, 1025, 64)],
                         ids=["b65536_n64", "b4096_n1025_cells_spill"])
def test_route_score_compiles_for_v5e(one_chip, b, n, cells):
    k = 4
    f32 = lambda *s: jnp.zeros(s, jnp.float32)
    args = dict(
        prompt_bits=f32(b), size_bits=f32(b), flops_tok=f32(b), work=f32(b),
        uplink_bps=f32(n), backhaul_bps=f32(n), flops_per_s=f32(n),
        queue_tokens=f32(n), resident=jnp.zeros((n, k), bool),
        model=jnp.zeros((b,), jnp.int32),
    )
    if cells:
        args.update(req_cell=jnp.zeros((b,), jnp.int32),
                    srv_cell=jnp.zeros((n,), jnp.int32),
                    spill=jnp.zeros((cells, cells), bool))
    fn = jax.jit(functools.partial(route_score, interpret=False))
    _assert_kernel(fn.lower(**_sds(args, one_chip)).compile())


@pytest.mark.parametrize("window", [
    dict(num_requests=4096, n_servers=64),
    dict(num_requests=262_144, n_servers=16, n_cells=64,
         scenario="popularity-drift", seed=7, drain_rate=20000.0),
], ids=["single_b4096_n64", "metro_b262144_n1025"])
def test_chunked_route_batch_compiles_for_v5e(one_chip, window):
    w = make_window(**window)
    lowered = br._route_batch.lower(
        _sds(w.params, one_chip), _sds(w.state, one_chip),
        _sds(w.reqs, one_chip), w.drain_tokens, None,
        policy="greedy", actor=None, chunk=256, unroll=8, backend="pallas",
        speculative=True,
    )
    compiled = lowered.compile()
    _assert_kernel(compiled)
    # the trace readers find the phases by these names
    assert _scopes(compiled) == {"route.score", "route.spec_scan",
                                 "route.rederive", "route.replay"}
    assert re.search(r'op_name="[^"]*route\.score/route_score/pallas_call"',
                     compiled.as_text())
    _assert_readers_see_the_kernels(compiled.as_text(), 256,
                                    w.params.flops_per_s.shape[0])


@pytest.mark.parametrize("requests", [8192, 32768],
                         ids=["online_b8192", "replay_b32768"])
def test_benchmark_windows_compile_with_the_scan_kernel(one_chip, requests):
    """The benchmark's two windows on its cloud-free metro fleet (N =
    1024), as the benchmark routes them: one ``route_spec_scan`` kernel per
    chunk under ``route.spec_scan``, found by the scan reader and never
    by the score-panel reader."""
    params, state = fleet.program_fleet(
        fleet.load(ROOT / "bench" / "configs" / "metro-64x16-edge.json"))
    z = np.zeros(requests, np.float32)
    reqs = br.RequestBatch(model=np.zeros(requests, np.int32),
                           prompt_bits=z, gen_tokens=z,
                           cell=np.zeros(requests, np.int32), arrival_s=z)
    compiled = br._route_batch.lower(
        _sds(params, one_chip), _sds(state, one_chip), _sds(reqs, one_chip),
        None, None, policy="greedy", actor=None, chunk=256, unroll=8,
        backend="pallas", speculative=True,
    ).compile()
    assert params.flops_per_s.shape[0] == 1024
    _assert_readers_see_the_kernels(compiled.as_text(), 256, 1024)


class _Compiled(Exception):
    """Carries a compiled program out of ``route_batch_sharded``."""


def test_sharded_route_compiles_for_v5e_2x2(v5e_2x2, monkeypatch):
    """``chip_smoke.py --chips 4``'s program: the metro window (64 cells
    of 16 edge servers and a cloud column) over four chips, chunk 256,
    speculative, ``pallas``. Each device ``vmap``s the per-cell router
    over its cell blocks, so both kernels compile batched."""
    mesh = Mesh(np.asarray(v5e_2x2[:4]), ("cells",))
    on_mesh = NamedSharding(mesh, PartitionSpec())
    jitted = mr._sharded_route

    def compile_instead(*args, **kw):
        raise _Compiled(jitted.lower(*_sds(args, on_mesh), **kw).compile())

    monkeypatch.setattr(mr, "_sharded_route", compile_instead)
    w = make_window(num_requests=262_144, n_servers=16, n_cells=64,
                    scenario="popularity-drift", seed=7, drain_rate=20000.0)
    with pytest.raises(_Compiled) as got:
        mr.route_batch_sharded(w.params, w.state, w.reqs, mesh=mesh,
                               chunk=256, backend="pallas", speculative=True)
    text = got.value.args[0].as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%([\w]+)[\w.\-]* = .* custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    assert {"route_score", "route_spec_scan"} <= set(calls)


@pytest.mark.parametrize("chunk,speculative,scopes", [
    (256, False, {"route.score", "route.commit_scan"}),
    (None, True, {"route.commit_scan"}),
], ids=["chunked_plain", "full_scan"])
def test_route_scopes_of_the_other_commit_paths(one_chip, chunk, speculative,
                                                scopes):
    w = make_window(num_requests=4096, n_servers=64)
    compiled = br._route_batch.lower(
        _sds(w.params, one_chip), _sds(w.state, one_chip),
        _sds(w.reqs, one_chip), w.drain_tokens, None,
        policy="greedy", actor=None, chunk=chunk, unroll=8,
        backend="pallas", speculative=speculative,
    ).compile()
    assert _scopes(compiled) == scopes
