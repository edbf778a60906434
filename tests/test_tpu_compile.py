"""The routing path compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so the Pallas score kernel and
the chunked commit are compiled here at the widths the chip runs
(``chip_smoke.py``): the compile refuses what interpret mode accepts —
unaligned slices, too much fast memory, programs that do not fit. The
tests assert the kernel reached the HLO as a ``tpu_custom_call``, and
that each commit path's ``route.*`` named scopes reached the ops'
``op_name`` metadata, where the device-trace readers look for them.

The topology is described inside a module fixture, never at import:
only one process may hold libtpu, and every test worker imports this
file. The persistent compilation cache is off around these compiles —
an entry written for a described chip cannot be read back without one.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import batch_router as br
from repro.kernels.route_score import route_score
from repro.launch.serve import make_window


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding),
        tree,
    )


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


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
