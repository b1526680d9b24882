"""The roofline's HLO analyzer: loop trip counts, collectives, dot flops."""
import jax
import jax.numpy as jnp

import repro.shardlib as sl
from repro.launch.hlo_analysis import analyze


def test_scan_flops_multiplied_by_trip_count():
    m = 128

    def f(x):
        def body(c, _):
            return c @ x, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((m, m), jnp.float32)).compile()
    r = analyze(c.as_text())
    expected = 10 * 2 * m ** 3
    assert abs(r["flops"] - expected) / expected < 1e-3


def test_nested_loops_multiply():
    m = 64

    def f(x):
        def inner(c, _):
            return c @ x, None

        def outer(c, _):
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((m, m), jnp.float32)).compile()
    r = analyze(c.as_text())
    expected = 15 * 2 * m ** 3
    assert abs(r["flops"] - expected) / expected < 1e-3


def test_collectives_in_loops_counted():
    m = 128
    mesh = sl.make_mesh((1,), ("x",))

    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "x") + c @ x, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    with mesh:
        g = jax.shard_map(f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                          out_specs=jax.sharding.PartitionSpec(),
                          check_vma=False)
        c = jax.jit(g).lower(
            jax.ShapeDtypeStruct((m, m), jnp.float32)).compile()
    r = analyze(c.as_text())
    assert r["collectives"]["all-reduce"] == 7 * m * m * 4


def test_dot_flops_with_batch_dims():
    b, m, k, n = 4, 32, 48, 16

    def f(x, y):
        return jnp.einsum("bmk,bkn->bmn", x, y)

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((b, m, k), jnp.float32),
        jax.ShapeDtypeStruct((b, k, n), jnp.float32)).compile()
    r = analyze(c.as_text())
    expected = 2 * b * m * k * n
    assert abs(r["flops"] - expected) / expected < 0.05


def test_bytes_by_class_present():
    def f(x):
        return jax.nn.relu(x @ x)
    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    r = analyze(c.as_text())
    assert set(r["bytes_by_class"]) == {
        "dot", "elementwise", "gather_scatter", "copy_layout", "collective",
        "other"}
    assert r["bytes_by_class"]["dot"] > 0
