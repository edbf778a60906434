"""HLO analyzer: while-trip-count multipliers must recover true costs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import analyze, xla_cost_analysis


def test_flat_module_matches_xla_cost_analysis():
    g = jax.jit(lambda a, b: (a @ b) @ b)
    co = g.lower(
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
    ).compile()
    res = analyze(co.as_text())
    ca = xla_cost_analysis(co)
    np.testing.assert_allclose(res["flops"], ca["flops"], rtol=0.05)


def test_scanned_matmul_trip_count():
    L, D = 7, 128

    def f(x, w):
        return jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]

    co = jax.jit(f).lower(
        jax.ShapeDtypeStruct((D, D), jnp.float32),
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
    ).compile()
    res = analyze(co.as_text())
    np.testing.assert_allclose(res["flops"], L * 2 * D**3, rtol=0.02)


def test_nested_scan_multiplies():
    L, R, D = 5, 3, 64

    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return c2 @ wi, None
            return jax.lax.scan(inner, c, None, length=R)[0], None
        return jax.lax.scan(outer, x, w)[0]

    co = jax.jit(f).lower(
        jax.ShapeDtypeStruct((D, D), jnp.float32),
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
    ).compile()
    res = analyze(co.as_text())
    np.testing.assert_allclose(res["flops"], L * R * 2 * D**3, rtol=0.02)


def test_collectives_counted_with_ring_formula():
    from repro.distributed import sharding

    mesh = sharding.make_mesh((1,), ("x",))

    def f(a):
        return jax.shard_map(
            lambda v: jax.lax.psum(v, "x"), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec("x"),
            out_specs=jax.sharding.PartitionSpec(),
        )(a)

    co = jax.jit(f).lower(jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    res = analyze(co.as_text())
    # single-device group => zero traffic
    assert res["collective_bytes"] == 0.0
